"""Pallas TPU kernels: fused quantize -> int8 GEMM -> exponent-add rescale.

The paper's Fig. 2 integer linear layer as ONE ``pallas_call``: f32 tiles
stream HBM -> VMEM, the shared-exponent int8 quantization (threshold-compare
stochastic rounding against caller-supplied random bits) happens in VMEM,
the mantissas feed the MXU int32 accumulator directly, and the exponent-add
scale is applied as a single f32 multiply before the output tile is written.
Unlike the unfused ``bfp_quant`` + ``int8_matmul`` pipeline, no f32 or int8
intermediate ever round-trips HBM between the quantizer and the GEMM.

Variants (all contraction-last: ``a (M, K) x b (N, K) -> y (M, N)``):

  qq  both operands f32, quantized in-kernel (forward pass);
  qi  ``a`` f32 quantized in-kernel, ``b`` pre-quantized int8 mantissas
      (backward ``dX = Ĝ Ŵᵀ``: the fresh gradient is quantized fused, the
      stored weight mantissas are reused);
  ii  both operands pre-quantized int8 (backward ``dW = X̂ᵀ Ĝ``: both
      mantissa tensors come from residuals — a pure int8 GEMM).

Grid / residency contract (see docs/KERNELS.md):

  * grid = (M / bm,): one program per row-strip of ``a``.  Each ``a`` strip
    (f32 + random bits) is fetched exactly once.
  * ``b`` (and its random bits / exponents) use a constant index map, so
    they are fetched once and stay VMEM-resident across the whole grid; the
    quantized ``b`` mantissas are written into the mantissa *output* block
    at program 0 and re-read from VMEM by every later program.
  * Quantized mantissas are also kernel outputs: the ``custom_vjp``
    residuals come straight from the fused call, so the 4x activation
    memory saving of the integer pipeline is preserved.  Callers with no
    use for them (the per-block backward requantization) pass
    ``emit_residuals=False``: the quantized-``b`` cache then lives in VMEM
    scratch and no int8 ever reaches HBM.
  * ``stochastic=False`` (nearest rounding, inference paths) drops the
    random-bit inputs entirely — no zero-filled rand arrays are streamed.

Per-tensor exponents ride in SMEM via ``PrefetchScalarGridSpec``; per-block
(along-K) exponents are int32 VMEM blocks.  All wrappers assume shapes are
pre-padded by ``kernels.dispatch`` (M % bm == 0, K and N multiples of 128).
Zero padding is exact end-to-end: a zero float quantizes to a zero mantissa
for any shared exponent, and zero mantissas contribute nothing to the dot.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tile import eff_exp, int8_dot, pow2_f32, quantize_tile, scale_exp

__all__ = [
    "fused_qq_pt_pallas",
    "fused_qi_pt_pallas",
    "fused_ii_pt_pallas",
    "fused_qq_blk_pallas",
    "fused_gemm_epi_pallas",
    "gemm_epi_ref",
]

# ---------------------------------------------------------------------------
# per-tensor scale kernels (the paper's mode)
# ---------------------------------------------------------------------------

def _qq_pt_kernel(es_ref, *refs, p, stochastic, emit_residuals):
    """Ref layout follows the static flags: inputs (a[, ra], b[, rb]);
    outputs (y, am, bm) with residuals, else (y,) + a bm VMEM scratch."""
    if stochastic:
        a_ref, ra_ref, b_ref, rb_ref = refs[:4]
        rest = refs[4:]
    else:
        a_ref, b_ref = refs[:2]
        ra_ref = rb_ref = None
        rest = refs[2:]
    if emit_residuals:
        y_ref, am_ref, bm_ref = rest
    else:
        y_ref, bm_ref = rest            # bm_ref: persistent VMEM scratch
        am_ref = None
    ea = es_ref[0]
    eb = es_ref[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        bm_ref[...] = quantize_tile(
            b_ref[...], None if rb_ref is None else rb_ref[...], eb,
            p, stochastic)

    am = quantize_tile(a_ref[...],
                        None if ra_ref is None else ra_ref[...], ea,
                        p, stochastic)
    if am_ref is not None:
        am_ref[...] = am
    acc = int8_dot(am, bm_ref[...])
    y_ref[...] = acc.astype(jnp.float32) * pow2_f32(
        scale_exp(ea, p) + scale_exp(eb, p))


def _qi_pt_kernel(es_ref, *refs, pa, pb, stochastic):
    if stochastic:
        a_ref, ra_ref, b_ref, y_ref, am_ref = refs
    else:
        a_ref, b_ref, y_ref, am_ref = refs
        ra_ref = None
    ea = es_ref[0]
    eb = es_ref[1]
    am = quantize_tile(a_ref[...],
                        None if ra_ref is None else ra_ref[...], ea,
                        pa, stochastic)
    am_ref[...] = am
    acc = int8_dot(am, b_ref[...])
    y_ref[...] = acc.astype(jnp.float32) * pow2_f32(
        scale_exp(ea, pa) + scale_exp(eb, pb))


def _ii_pt_kernel(es_ref, a_ref, b_ref, y_ref, *, pa, pb):
    ea = es_ref[0]
    eb = es_ref[1]
    acc = int8_dot(a_ref[...], b_ref[...])
    y_ref[...] = acc.astype(jnp.float32) * pow2_f32(
        scale_exp(ea, pa) + scale_exp(eb, pb))


@partial(jax.jit, static_argnames=("p", "bm", "stochastic", "interpret",
                                   "emit_residuals"))
def fused_qq_pt_pallas(a, ra, b, rb, ea, eb, *, p=7, bm=256,
                       stochastic=True, interpret=False,
                       emit_residuals=True):
    """Fused quantize-both + GEMM, per-tensor scale.

    a (M, K) f32, ra (M, K) uint32, b (N, K) f32, rb (N, K) uint32,
    ea / eb scalar int32 biased shared exponents ->
    (y (M, N) f32, a mantissas (M, K) int8, b mantissas (N, K) int8),
    or just y when ``emit_residuals=False`` (mantissas stay in VMEM).
    ``stochastic=False`` takes ra = rb = None — no rand is streamed.
    M % bm == 0; K, N multiples of 128 (dispatch pads).
    """
    m, k = a.shape
    n = b.shape[0]
    assert m % bm == 0, (m, bm)
    es = jnp.stack([jnp.asarray(ea), jnp.asarray(eb)]).astype(jnp.int32)
    a_spec = pl.BlockSpec((bm, k), lambda i, s: (i, 0))
    b_spec = pl.BlockSpec((n, k), lambda i, s: (0, 0))
    if stochastic:
        in_specs = [a_spec, a_spec, b_spec, b_spec]
        operands = (es, a, ra, b, rb)
    else:
        in_specs = [a_spec, b_spec]
        operands = (es, a, b)
    if emit_residuals:
        out_specs = [pl.BlockSpec((bm, n), lambda i, s: (i, 0)),
                     pl.BlockSpec((bm, k), lambda i, s: (i, 0)),
                     pl.BlockSpec((n, k), lambda i, s: (0, 0))]
        out_shape = [jax.ShapeDtypeStruct((m, n), jnp.float32),
                     jax.ShapeDtypeStruct((m, k), jnp.int8),
                     jax.ShapeDtypeStruct((n, k), jnp.int8)]
        scratch_shapes = ()
    else:
        out_specs = pl.BlockSpec((bm, n), lambda i, s: (i, 0))
        out_shape = jax.ShapeDtypeStruct((m, n), jnp.float32)
        scratch_shapes = (pltpu.VMEM((n, k), jnp.int8),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        partial(_qq_pt_kernel, p=p, stochastic=stochastic,
                emit_residuals=emit_residuals),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)


@partial(jax.jit, static_argnames=("pa", "pb", "bm", "stochastic", "interpret"))
def fused_qi_pt_pallas(a, ra, b_m, ea, eb, *, pa=7, pb=7, bm=256,
                       stochastic=True, interpret=False):
    """Fused quantize-a + GEMM against pre-quantized b, per-tensor scale.

    a (M, K) f32, ra (M, K) uint32 (None when ``stochastic=False``),
    b_m (N, K) int8 mantissas -> (y (M, N) f32, a mantissas (M, K) int8).
    """
    m, k = a.shape
    n = b_m.shape[0]
    assert m % bm == 0, (m, bm)
    es = jnp.stack([jnp.asarray(ea), jnp.asarray(eb)]).astype(jnp.int32)
    a_spec = pl.BlockSpec((bm, k), lambda i, s: (i, 0))
    b_spec = pl.BlockSpec((n, k), lambda i, s: (0, 0))
    if stochastic:
        in_specs = [a_spec, a_spec, b_spec]
        operands = (es, a, ra, b_m)
    else:
        in_specs = [a_spec, b_spec]
        operands = (es, a, b_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, n), lambda i, s: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, s: (i, 0)),
        ],
    )
    return pl.pallas_call(
        partial(_qi_pt_kernel, pa=pa, pb=pb, stochastic=stochastic),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                   jax.ShapeDtypeStruct((m, k), jnp.int8)],
        interpret=interpret,
    )(*operands)


@partial(jax.jit, static_argnames=("pa", "pb", "bm", "interpret"))
def fused_ii_pt_pallas(a_m, b_m, ea, eb, *, pa=7, pb=7, bm=256,
                       interpret=False):
    """Pure int8 GEMM on residual mantissas, per-tensor scale via SMEM.

    a_m (M, K) int8, b_m (N, K) int8 -> y (M, N) f32.
    """
    m, k = a_m.shape
    n = b_m.shape[0]
    assert m % bm == 0, (m, bm)
    es = jnp.stack([jnp.asarray(ea), jnp.asarray(eb)]).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, s: (i, 0)),
            pl.BlockSpec((n, k), lambda i, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i, s: (i, 0)),
    )
    return pl.pallas_call(
        partial(_ii_pt_kernel, pa=pa, pb=pb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(es, a_m, b_m)


# ---------------------------------------------------------------------------
# per-block (along-K) scale kernel — the MX-style TPU adaptation
# ---------------------------------------------------------------------------

def _bcast_blk(e, blk):
    """Per-block exponents (R, nb) -> per-element (R, nb*blk)."""
    return jnp.broadcast_to(e[:, :, None],
                            (*e.shape, blk)).reshape(e.shape[0], -1)


def _blk_combine(am, bq, sea, seb, blk, out_shape):
    """Sequential f32 combine of per-block int32 partials, in block order
    (= the order of ref.bfp_block_matmul_ref, so parity tests are exact)."""
    nb = sea.shape[1]

    def body(bi, acc):
        a_blk = lax.dynamic_slice_in_dim(am, bi * blk, blk, axis=1)
        b_blk = lax.dynamic_slice_in_dim(bq, bi * blk, blk, axis=1)
        part = int8_dot(a_blk, b_blk)
        sa = lax.dynamic_slice_in_dim(sea, bi, 1, axis=1)        # (bm, 1)
        sb = lax.dynamic_slice_in_dim(seb, bi, 1, axis=1)        # (N, 1)
        return acc + part.astype(jnp.float32) * pow2_f32(sa + sb.reshape(1, -1))

    return lax.fori_loop(0, nb, body, jnp.zeros(out_shape, jnp.float32))


def _qq_blk_kernel(*refs, p, blk, stochastic, emit_residuals):
    """Inputs (a[, ra], ea, b[, rb], eb); outputs (y, am, bm) with
    residuals, else (y,) + a bm VMEM scratch (the quantized-b cache)."""
    if stochastic:
        a_ref, ra_ref, ea_ref, b_ref, rb_ref, eb_ref = refs[:6]
        rest = refs[6:]
    else:
        a_ref, ea_ref, b_ref, eb_ref = refs[:4]
        ra_ref = rb_ref = None
        rest = refs[4:]
    if emit_residuals:
        y_ref, am_ref, bm_ref = rest
    else:
        y_ref, bm_ref = rest
        am_ref = None
    ea = ea_ref[...]                                     # (bm, nb) int32
    eb = eb_ref[...]                                     # (N, nb) int32

    @pl.when(pl.program_id(0) == 0)
    def _():
        bm_ref[...] = quantize_tile(
            b_ref[...], None if rb_ref is None else rb_ref[...],
            _bcast_blk(eb, blk), p, stochastic)

    am = quantize_tile(a_ref[...],
                        None if ra_ref is None else ra_ref[...],
                        _bcast_blk(ea, blk), p, stochastic)
    if am_ref is not None:
        am_ref[...] = am
    y_ref[...] = _blk_combine(am, bm_ref[...], scale_exp(ea, p),
                              scale_exp(eb, p), blk, y_ref.shape)


@partial(jax.jit, static_argnames=("p", "blk", "bm", "stochastic",
                                   "interpret", "emit_residuals"))
def fused_qq_blk_pallas(a, ra, ea, b, rb, eb, *, p=7, blk=32, bm=256,
                        stochastic=True, interpret=False,
                        emit_residuals=True):
    """Fused quantize-both + GEMM with per-K-block shared exponents.

    a (M, K) f32, ra (M, K) uint32, ea (M, K/blk) int32,
    b (N, K) f32, rb (N, K) uint32, eb (N, K/blk) int32 ->
    (y (M, N) f32, a mantissas (M, K) int8, b mantissas (N, K) int8),
    or just y (M, N) when ``emit_residuals=False`` — the backward
    requantization path has no use for the mantissas, so they never touch
    HBM (the quantized-b cache is a VMEM scratch instead of an output).
    ``stochastic=False`` takes ra = rb = None — no rand is streamed.
    Per-block int32 partials are rescaled and combined in f32 inside VMEM —
    the accumulator never sums more than ``blk`` int8 x int8 products.
    """
    m, k = a.shape
    n = b.shape[0]
    assert m % bm == 0 and k % blk == 0, (m, bm, k, blk)
    nb = k // blk
    a_spec = pl.BlockSpec((bm, k), lambda i: (i, 0))
    ea_spec = pl.BlockSpec((bm, nb), lambda i: (i, 0))
    b_spec = pl.BlockSpec((n, k), lambda i: (0, 0))
    eb_spec = pl.BlockSpec((n, nb), lambda i: (0, 0))
    if stochastic:
        in_specs = [a_spec, a_spec, ea_spec, b_spec, b_spec, eb_spec]
        operands = (a, ra, ea, b, rb, eb)
    else:
        in_specs = [a_spec, ea_spec, b_spec, eb_spec]
        operands = (a, ea, b, eb)
    kernel = partial(_qq_blk_kernel, p=p, blk=blk, stochastic=stochastic,
                     emit_residuals=emit_residuals)
    if emit_residuals:
        return pl.pallas_call(
            kernel,
            grid=(m // bm,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bm, n), lambda i: (i, 0)),
                pl.BlockSpec((bm, k), lambda i: (i, 0)),
                pl.BlockSpec((n, k), lambda i: (0, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                       jax.ShapeDtypeStruct((m, k), jnp.int8),
                       jax.ShapeDtypeStruct((n, k), jnp.int8)],
            interpret=interpret,
        )(*operands)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, k), jnp.int8)],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# GEMM -> bias/activation/out-quantize epilogue kernels
# (docs/KERNELS.md §Cross-op fusion)
# ---------------------------------------------------------------------------

_EPI_ACTS = (None, "relu", "gelu", "silu_glu", "gelu_glu")
_EPI_META_LANES = 128


def epi_apply(y, bias, act, n_out):
    """The f32 epilogue on a GEMM output tile: bias add, then activation.
    ``*_glu`` acts gate the left half against the right half (the merged
    gate|up projection), halving the output width to ``n_out``.  These are
    the *same* f32 ops the unfused model code applies, in the same order —
    the epilogue is bit-identical to the unfused composition."""
    assert act in _EPI_ACTS, act
    if bias is not None:
        y = y + bias
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "gelu":
        y = jax.nn.gelu(y)
    elif act == "silu_glu":
        y = jax.nn.silu(y[:, :n_out]) * y[:, n_out:]
    elif act == "gelu_glu":
        y = jax.nn.gelu(y[:, :n_out]) * y[:, n_out:]
    return y


def _gemm_epi_kernel(es_ref, *refs, kind, p, pa, pb, stochastic, act,
                     has_bias, out_q, qp, n_out, m_true, emit_residuals):
    """GEMM with a fused f32 epilogue and optional per-tensor out-quantize.

    Without ``out_q`` the grid is (M/bm,) — one pass.  With ``out_q`` the
    grid is (2, M/bm): phase 0 runs the GEMM+epilogue per strip only to
    fold the strip max |y| into an SMEM amax scratch; phase 1 recomputes
    the (deterministic) strip and quantizes it against the tensor-wide
    shared exponent — the ``quantize-after-global-max`` contract of
    ``core.qops._quantize_out``, bit-for-bit, with 2x MXU work instead of
    an f32 HBM round-trip.
    """
    it = iter(refs)
    a_ref = next(it)
    ra_ref = next(it) if (kind != "ii" and stochastic) else None
    b_ref = next(it)
    rb_ref = next(it) if (kind == "qq" and stochastic) else None
    bias_ref = next(it) if has_bias else None
    rq_ref = next(it) if (out_q and stochastic) else None
    yo_ref = next(it)
    emeta_ref = next(it) if out_q else None
    am_ref = next(it) if (kind != "ii" and emit_residuals) else None
    bm_ref = next(it) if (kind == "qq" and emit_residuals) else None
    ylin_ref = next(it) if (act is not None and emit_residuals) else None
    scratch = tuple(it)
    if kind == "qq" and bm_ref is None:
        bm_ref = scratch[0]
        scratch = scratch[1:]
    amax_ref = scratch[0] if out_q else None

    if out_q:
        ph = pl.program_id(0)
        i = pl.program_id(1)
        first = (ph == 0) & (i == 0)
    else:
        ph = None
        i = pl.program_id(0)
        first = i == 0
    ea = es_ref[0]
    eb = es_ref[1]

    if kind == "qq":
        @pl.when(first)
        def _():
            bm_ref[...] = quantize_tile(
                b_ref[...], None if rb_ref is None else rb_ref[...], eb,
                pb, stochastic)
        bmant = bm_ref[...]
    else:
        bmant = b_ref[...]
    if kind == "ii":
        am = a_ref[...]
    else:
        am = quantize_tile(a_ref[...],
                            None if ra_ref is None else ra_ref[...], ea,
                            pa, stochastic)
        if am_ref is not None:
            am_ref[...] = am
    ylin = int8_dot(am, bmant).astype(jnp.float32) * pow2_f32(
        scale_exp(ea, pa) + scale_exp(eb, pb))
    if bias_ref is not None:
        ylin = ylin + bias_ref[...]
    if ylin_ref is not None:
        ylin_ref[...] = ylin
    y = epi_apply(ylin, None, act, n_out)

    if not out_q:
        yo_ref[...] = y
        return

    @pl.when(ph == 0)
    def _():
        @pl.when(i == 0)
        def _():
            amax_ref[0, 0] = 0.0
        av = jnp.abs(y)
        if m_true is not None:
            # Zero-padded a-rows stop being zero after the bias add; mask
            # them out of the tensor-wide amax so the shared exponent
            # matches the unfused quantize of the *cropped* output.
            rows = (lax.broadcasted_iota(jnp.int32, av.shape, 0)
                    + i * av.shape[0])
            av = jnp.where(rows < m_true, av, 0.0)
        amax_ref[0, 0] = jnp.maximum(amax_ref[0, 0], av.max())

    @pl.when(ph == 1)
    def _():
        e_out = eff_exp(amax_ref[0, 0])
        yo_ref[...] = quantize_tile(
            y, None if rq_ref is None else rq_ref[...], e_out, qp,
            stochastic)
        emeta_ref[...] = jnp.broadcast_to(e_out, (1, _EPI_META_LANES))


@partial(jax.jit, static_argnames=("kind", "p", "pa", "pb", "bm",
                                   "stochastic", "act", "out_q", "qp",
                                   "m_true", "emit_residuals", "interpret"))
def fused_gemm_epi_pallas(a, ra, b, rb, bias, rq, ea, eb, *, kind="qq",
                          p=7, pa=None, pb=None, bm=256, stochastic=True,
                          act=None, out_q=False, qp=7, m_true=None,
                          emit_residuals=True, interpret=False):
    """Fused GEMM -> bias/activation -> (optional) per-tensor out-quantize.

    Operand layout follows the per-tensor kernels above: a (M, K), b (N, K)
    contraction-last, ea/eb scalar biased shared exponents.  ``kind``:

      qq  a f32 + ra, b f32 + rb (both quantized in-kernel);
      qi  a f32 + ra, b int8 mantissas (persistent weights);
      ii  a int8, b int8 (fully pre-quantized — the serving ``pp`` path).

    bias (1, N) f32 or None; ``act`` one of ``None | relu | gelu |
    silu_glu | gelu_glu`` (the ``_glu`` forms halve the width);
    ``out_q=True`` emits int8 mantissas under ONE tensor-wide shared
    exponent plus a (1, 128) int32 meta row carrying it at [0, 0] —
    bit-identical to quantizing the unfused f32 output with the same
    random bits ``rq`` (M, N_out).

    Returns a tuple: (y | (ym, emeta)) [+ am][+ bmq if qq]
    [+ ylin if act and emit_residuals].
    """
    pa = p if pa is None else pa
    pb = p if pb is None else pb
    m, k = a.shape
    n = b.shape[0]
    n_out = n // 2 if (act or "").endswith("_glu") else n
    assert m % bm == 0, (m, bm)
    es = jnp.stack([jnp.asarray(ea), jnp.asarray(eb)]).astype(jnp.int32)
    nsp = 3 if out_q else 2                       # index-map arity
    strip_k = pl.BlockSpec((bm, k), lambda *a_: (a_[-2], 0))
    full_b = pl.BlockSpec((n, k), lambda *a_: (0, 0))
    strip_n = pl.BlockSpec((bm, n), lambda *a_: (a_[-2], 0))
    strip_no = pl.BlockSpec((bm, n_out), lambda *a_: (a_[-2], 0))
    row_n = pl.BlockSpec((1, n), lambda *a_: (0, 0))
    del nsp

    in_specs = [strip_k]
    operands = [es, a]
    if kind != "ii" and stochastic:
        in_specs.append(strip_k)
        operands.append(ra)
    in_specs.append(full_b)
    operands.append(b)
    if kind == "qq" and stochastic:
        in_specs.append(full_b)
        operands.append(rb)
    if bias is not None:
        in_specs.append(row_n)
        operands.append(bias)
    if out_q and stochastic:
        in_specs.append(strip_no)
        operands.append(rq)

    out_specs = [strip_no]
    out_shape = [jax.ShapeDtypeStruct((m, n_out),
                                      jnp.int8 if out_q else jnp.float32)]
    if out_q:
        out_specs.append(pl.BlockSpec((1, _EPI_META_LANES),
                                      lambda *a_: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, _EPI_META_LANES),
                                              jnp.int32))
    if kind != "ii" and emit_residuals:
        out_specs.append(strip_k)
        out_shape.append(jax.ShapeDtypeStruct((m, k), jnp.int8))
    scratch_shapes = []
    if kind == "qq":
        if emit_residuals:
            out_specs.append(full_b)
            out_shape.append(jax.ShapeDtypeStruct((n, k), jnp.int8))
        else:
            scratch_shapes.append(pltpu.VMEM((n, k), jnp.int8))
    if act is not None and emit_residuals:
        out_specs.append(strip_n)
        out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
    if out_q:
        scratch_shapes.append(pltpu.SMEM((1, 1), jnp.float32))

    grid = (2, m // bm) if out_q else (m // bm,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=tuple(scratch_shapes),
    )
    out = pl.pallas_call(
        partial(_gemm_epi_kernel, kind=kind, p=p, pa=pa, pb=pb,
                stochastic=stochastic, act=act, has_bias=bias is not None,
                out_q=out_q, qp=qp, n_out=n_out, m_true=m_true,
                emit_residuals=emit_residuals),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return tuple(out)


@partial(jax.jit, static_argnames=("kind", "p", "pa", "pb", "stochastic",
                                   "act", "out_q", "qp", "m_true",
                                   "emit_residuals"))
def gemm_epi_ref(a, ra, b, rb, bias, rq, ea, eb, *, kind="qq", p=7, pa=None,
                 pb=None, stochastic=True, act=None, out_q=False, qp=7,
                 m_true=None, emit_residuals=True):
    """Bit-exact jnp mirror of :func:`fused_gemm_epi_pallas`: identical
    per-tensor quantize / dot / epilogue steps on the full arrays (the
    tensor-wide amax equals the kernel's sequential strip-max fold)."""
    pa = p if pa is None else pa
    pb = p if pb is None else pb
    n = b.shape[0]
    n_out = n // 2 if (act or "").endswith("_glu") else n
    ea = jnp.asarray(ea, jnp.int32)
    eb = jnp.asarray(eb, jnp.int32)
    if kind == "qq":
        bmant = quantize_tile(b, rb if stochastic else None, eb, pb,
                               stochastic)
    else:
        bmant = b
    if kind == "ii":
        am = a
    else:
        am = quantize_tile(a, ra if stochastic else None, ea, pa,
                            stochastic)
    ylin = int8_dot(am, bmant).astype(jnp.float32) * pow2_f32(
        scale_exp(ea, pa) + scale_exp(eb, pb))
    if bias is not None:
        ylin = ylin + bias
    y = epi_apply(ylin, None, act, n_out)
    if out_q:
        av = jnp.abs(y)
        if m_true is not None:
            av = jnp.where(jnp.arange(a.shape[0])[:, None] < m_true, av, 0.0)
        e_out = eff_exp(av.max())
        ym = quantize_tile(y, rq if stochastic else None, e_out, qp,
                            stochastic)
        out = [ym, jnp.full((1, _EPI_META_LANES), e_out, jnp.int32)]
    else:
        out = [y]
    if kind != "ii" and emit_residuals:
        out.append(am)
    if kind == "qq" and emit_residuals:
        out.append(bmant)
    if act is not None and emit_residuals:
        out.append(ylin)
    return tuple(out)
