"""Pallas TPU kernel: fused BFP quantization (the representation mapping).

The paper's Fig. 1(a) circuit as one VMEM-resident pass: bitcast ->
unpack -> shift-align to the shared exponent -> threshold-compare
stochastic round -> pack to int8. On TPU this fuses what the jnp emulation
materializes as ~6 HBM-round-trip elementwise ops into a single
read(f32)+read(u32 rand) -> write(int8) stream, turning the quantizer from
~7x tensor traffic into ~2.25x (the memory-roofline win quantified in
EXPERIMENTS.md §Perf).

Grid: rows are tiled (block_rows x N); the shared exponent arrives as a
per-row-block (block_rows, 1) int32 ref (per-tensor mode passes a
broadcast exponent), so one kernel covers both scale granularities.
Tile geometry: (block_rows, N) with N a multiple of 128 lanes; block_rows
a multiple of 8 sublanes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tile import quantize_tile

__all__ = ["bfp_quantize_pallas"]


def _kernel(x_ref, rand_ref, e_ref, out_ref):
    # e_ref: (block_rows, 1) shared exponents, broadcast along the row
    out_ref[...] = quantize_tile(x_ref[...], rand_ref[...], e_ref[...], 7,
                                 stochastic=True)


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bfp_quantize_pallas(x: jnp.ndarray, rand: jnp.ndarray,
                        e_shared: jnp.ndarray, *, block_rows: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """x (M, N) f32, rand (M, N) uint32, e_shared (M, 1) int32 -> int8 (M, N).

    M must be divisible by block_rows; N should be a multiple of 128 for
    TPU lane alignment (the ops.py wrapper pads).
    """
    m, n = x.shape
    assert m % block_rows == 0, (m, block_rows)
    grid = (m // block_rows,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret,
    )(x, rand, e_shared)
