"""jit'd wrappers for the *unfused* Pallas kernels: padding, shared-exponent
prep, random-bit generation, and an automatic jnp fallback.

These are the standalone building blocks (quantizer kernel -> HBM int8 ->
GEMM kernel).  Routing between them, the fused pipeline in
``kernels.fused_linear`` and the jnp oracle is owned by
``kernels.dispatch`` — model code goes through ``core.qops``, which plans
via dispatch; call these wrappers directly only for sweeps and benchmarks.

``use_pallas`` selects the kernel path.  ``interpret`` defaults to the
backend, as ``kernels.dispatch`` does: compiled on a TPU, interpret mode
everywhere else (the same code validates on CPU and compiles for TPU).
Note ``quantize_op`` exposes
*per-row-block* scale granularity (one exponent per ``block_rows`` rows),
which differs from ``core.bfp`` per-tensor / per-K-block modes; per-tensor
(``per_tensor=True``) matches ``core.bfp.quantize`` bit-for-bit given the
same random bits.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.bfp import pow2
from . import ref
from .bfp_quant import bfp_quantize_pallas
from .int8_matmul import int8_matmul_pallas

__all__ = ["quantize_op", "int8_matmul_op"]


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pad_to(x: jnp.ndarray, mult_rows: int, mult_cols: int) -> jnp.ndarray:
    m, n = x.shape
    pm = (-m) % mult_rows
    pn = (-n) % mult_cols
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@partial(jax.jit, static_argnames=("per_tensor", "use_pallas", "interpret",
                                   "block_rows"))
def quantize_op(x: jnp.ndarray, key: jax.Array, *, per_tensor: bool = True,
                use_pallas: bool = True, interpret: Optional[bool] = None,
                block_rows: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize a 2-D f32 tensor to (int8 mantissas, per-row-block biased
    exponent). per_tensor=True broadcasts one shared exponent everywhere
    (the paper's mode); otherwise one exponent per block_rows rows."""
    m, n = x.shape
    eff = ref.max_biased_exp_ref(x, axis=None if per_tensor else 1)
    if per_tensor:
        e_rows = jnp.broadcast_to(eff, (m,))
    else:
        e_rows = jax.lax.reduce_window(
            eff, -jnp.inf if eff.dtype == jnp.float32 else jnp.int32(0),
            jax.lax.max, (block_rows,), (block_rows,), "valid")
        e_rows = jnp.repeat(e_rows, block_rows, total_repeat_length=m)
    rand = jax.random.bits(key, (m, n), jnp.uint32)
    if not use_pallas:
        mant = ref.bfp_quantize_ref(x, rand, e_rows[:, None])
        return mant, e_rows
    xp = _pad_to(x, block_rows, 128)
    rp = _pad_to(rand, block_rows, 128)
    ep = jnp.pad(e_rows, (0, xp.shape[0] - m), constant_values=1)[:, None]
    mant = bfp_quantize_pallas(xp, rp, ep, block_rows=block_rows,
                               interpret=_interpret(interpret))
    return mant[:m, :n], e_rows


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bm", "bn", "bk"))
def int8_matmul_op(a_m: jnp.ndarray, b_m: jnp.ndarray, ea: jnp.ndarray,
                   eb: jnp.ndarray, *, use_pallas: bool = True,
                   interpret: Optional[bool] = None, bm: int = 128,
                   bn: int = 128, bk: int = 128) -> jnp.ndarray:
    """(M,K) x (K,N) int8 mantissas with scalar biased exponents -> f32.

    Exponents add (integer add); the combined scale is one f32 multiply on
    the accumulator (Fig. 2), delivered to the kernel through SMEM scalar
    prefetch.  Operands are zero-padded up to tile multiples; padding is
    exact through the rescale because zero mantissas contribute nothing to
    the int32 accumulator (tested in test_kernels.py)."""
    scale = pow2((ea - 133) + (eb - 133))
    if not use_pallas:
        return ref.int8_matmul_ref(a_m, b_m, scale)
    m, k = a_m.shape
    n = b_m.shape[1]
    ap = _pad_to(a_m, bm, bk)
    bp = _pad_to(b_m, bk, bn)
    out = int8_matmul_pallas(ap, bp, scale, bm=bm, bn=bn, bk=bk,
                             interpret=_interpret(interpret))
    return out[:m, :n]
