"""Fused integer flash-attention Pallas kernels: QKᵀ→softmax→PV in one pass.

The paper's ViT attention recipe (§5) — integer QKᵀ and PV around a float
softmax — executed as ONE ``pallas_call`` per direction instead of a
``lax.scan`` of separately-dispatched GEMMs.  The K/V int8 mantissas are
loaded into VMEM once and stay resident across every query row-strip; the
scores ``s``, the online-softmax probabilities ``p`` and their freshly
quantized mantissas live entirely in VMEM/registers and **never touch
HBM** — the same residency argument as ``fused_linear``, applied to the
hottest multi-GEMM chain in the model.

Operand contract (all per-tensor int8 BFP, quantized ONCE by the caller —
the qflow quantize-once rule):

  qm (GS, D) int8   grouped, pre-scaled query mantissas; scalar biased
                    exponent ``eq``.  GS = g·S with g = Hq/Hkv queries per
                    KV head, laid out g-major (rows r ↔ query position
                    r mod S) exactly like ``models.attention._group_q``.
  km, vm (T, D)     key/value mantissas; scalar biased exponents ek, ev.
  rp (GS, T) u32    rounding bits for the in-kernel quantization of ``p``
                    (dropped entirely when ``stochastic=False``).

Forward (grid over GS/bq row strips, ``fori_loop`` over T/bt KV blocks):
int8×int8→int32 QKᵀ on the MXU, one f32 exponent-add rescale, causal /
sliding-window / kv-length masks, the float online softmax (row max ``m``,
row sum ``l`` carried in registers), then ``p`` is quantized **in-kernel**
with one shared exponent per query row per KV block (``QuantConfig(bits,
block=bt)`` semantics — the per-row scale factors out of the PV integer
dot as a per-output-row epilogue) and immediately contracted against the
resident V mantissas.  Fully-masked KV blocks are *skipped* by tightening
the ``fori_loop`` bounds per strip — a banded (sliding-window) prefill
does O(S·window) work, not O(S²).

Backward (grid over T/bt KV blocks, Q-side resident): the A.2-style
integer backward with probabilities *recomputed* from the saved row stats
(m, l) — the O(GS·T) probability mantissas are never stored.  Per block:
``dV = P̂ᵀĜ``, ``dP = ĜV̂ᵀ``, ``dS = P∘(dP − δ)``, ``dQ += dŜK̂`` (f32
accumulation across the sequential grid), ``dK = dŜᵀQ̂`` — every multiply
an int8 GEMM, P/dS quantized in-kernel with one shared exponent per
(GS, bt) tile against caller-supplied rounding bits.

Decode (one program): consumes qcache row mantissas + per-row exponents
directly (docs/SERVING.md).  K row exponents are applied as a per-output-
column epilogue on the scores; V row exponents are folded into the float
probabilities before their single in-kernel quantization (exact ×2^e —
the same factorization as ``core.qops.qcache_qk``/``qcache_pv``, now
without dispatching two separate GEMMs or round-tripping ``p``).

Every kernel has a pure-jnp reference (``*_ref``) built from the SAME
block-core functions, so parity is bit-exact in interpret mode: identical
rounding bits, identical int32 accumulation, identical f32 op order.
Wrappers assume pre-padded shapes (``kernels.dispatch`` geometry: GS % bq
== 0, T % bt == 0, D a lane multiple; zero padding is exact end-to-end —
padded KV positions are masked via ``kv_len``, padded query rows are
cropped by the caller).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tile import eff_exp, pow2_f32, quantize_tile, round_up, scale_exp

__all__ = [
    "fused_attn_fwd_pallas",
    "fused_attn_bwd_pallas",
    "fused_attn_decode_pallas",
    "attn_fwd",
    "attn_bwd",
    "attn_decode",
]

_NEG = -1e30  # matches models.attention._NEG


def _qk_dot(qm, km_j):
    """(bq, D) int8 × (bt, D) int8 → (bq, bt) int32 (contraction-last)."""
    return lax.dot_general(qm, km_j, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _pv_dot(ph, vm_j):
    """(bq, bt) int8 × (bt, D) int8 → (bq, D) int32."""
    return lax.dot_general(ph, vm_j, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _tn_dot(a, b):
    """(GS, bt) int8 ᵀ× (GS, D) int8 → (bt, D) int32 (contract rows)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _block_mask(qpos, kpos, kv_len, causal, window):
    """The causal / sliding-window / kv-length mask of one score tile.

    qpos (R, 1) int32, kpos (R, C) int32; ``causal`` static, ``window``
    static (0 = off), ``kv_len`` traced (masks T padding too).
    """
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    return mask


# ---------------------------------------------------------------------------
# shared block cores — called by BOTH the Pallas kernels (on VMEM refs) and
# the jnp references (on array slices): bit-exact parity by construction.
# ---------------------------------------------------------------------------

def _fwd_blocks(qm, kblk, vblk, rpblk, eq, ek, ev, qpos, kv_len, lo, hi, *,
                p, bt, d, causal, window, stochastic):
    """Online-softmax loop over KV blocks ``j`` ∈ [lo, hi).

    ``kblk(j)``/``vblk(j)`` return the (bt, D) int8 mantissa block,
    ``rpblk(j)`` the (bq, bt) uint32 rounding bits.  Returns the final
    (m, l, acc) carry; blocks outside [lo, hi) are provably no-ops (all
    their scores mask to −1e30, so m, l and acc pass through unchanged).
    """
    bq = qm.shape[0]
    sc_qk = pow2_f32(scale_exp(eq, p) + scale_exp(ek, p))
    sev = scale_exp(ev, p)

    def body(j, carry):
        m, l, acc = carry
        km_j = kblk(j)
        kpos = j * bt + lax.broadcasted_iota(jnp.int32, (bq, bt), 1)
        mask = _block_mask(qpos, kpos, kv_len, causal, window)
        sf = _qk_dot(qm, km_j).astype(jnp.float32) * sc_qk
        sf = jnp.where(mask, sf, _NEG)
        m_new = jnp.maximum(m, sf.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pt = jnp.where(mask, jnp.exp(sf - m_new), 0.0)
        # one shared exponent per query row per block: QuantConfig(bits,
        # block=bt) semantics, entirely tile-local.  The per-row scale
        # factors out of the integer PV dot as a per-output-row epilogue.
        e_row = eff_exp(pt).max(axis=-1, keepdims=True)
        ph = quantize_tile(pt, None if rpblk is None else rpblk(j), e_row,
                            p, stochastic)
        pv = _pv_dot(ph, vblk(j)).astype(jnp.float32)
        acc = acc * alpha + pv * pow2_f32(scale_exp(e_row, p) + sev)
        return m_new, l * alpha + pt.sum(axis=-1, keepdims=True), acc

    init = (jnp.full((bq, 1), _NEG, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, d), jnp.float32))
    return lax.fori_loop(lo, hi, body, init)


def _bwd_block(j, qm, gm, km_j, vm_j, m, l, delta, rs_j, rp_j, eq, ek, ev,
               eg, qpos, row_ok, kv_len, *, p, bt, causal, window,
               stochastic):
    """One KV block of the A.2 integer backward: returns (dq_contrib (GS,
    D), dk_j (bt, D), dv_j (bt, D)) in value scale.

    Probabilities are recomputed from the saved row stats (m = final row
    max, l = final row sum): ``pn = exp(s − m) / l`` is the *normalized*
    softmax, so no per-chunk replay of the forward's online rescaling is
    needed.  pn and dS are quantized with one shared exponent per (GS, bt)
    tile — masked entries are exact zeros, so they contribute nothing to
    any of the three integer contractions.  ``row_ok`` (GS, 1) masks
    padded query rows: their saved stats are garbage (l = 0 would blow pn
    up to 1/ε and poison the tile-shared exponent), so they must quantize
    as exact zeros.
    """
    gs = qm.shape[0]
    kpos = j * bt + lax.broadcasted_iota(jnp.int32, (gs, bt), 1)
    mask = _block_mask(qpos, kpos, kv_len, causal, window) & row_ok
    sc_qk = pow2_f32(scale_exp(eq, p) + scale_exp(ek, p))
    sf = _qk_dot(qm, km_j).astype(jnp.float32) * sc_qk
    sf = jnp.where(mask, sf, _NEG)
    pt = jnp.where(mask, jnp.exp(sf - m), 0.0)
    pn = pt / jnp.maximum(l, 1e-30)
    # dV = P̂ᵀ Ĝ — pn's scale rides the contraction rows, so one shared
    # exponent per tile (a scalar) is what factors out of the int32 dot.
    e_pn = eff_exp(pn).max()
    pn_h = quantize_tile(pn, rp_j, e_pn, p, stochastic)
    dv_j = _tn_dot(pn_h, gm).astype(jnp.float32) * pow2_f32(
        scale_exp(e_pn, p) + scale_exp(eg, p))
    # dP = Ĝ V̂ᵀ ; dS = P ∘ (dP − δ)
    dp = _qk_dot(gm, vm_j).astype(jnp.float32) * pow2_f32(
        scale_exp(eg, p) + scale_exp(ev, p))
    ds = pn * (dp - delta)
    e_ds = eff_exp(ds).max()
    ds_h = quantize_tile(ds, rs_j, e_ds, p, stochastic)
    sc_ds = scale_exp(e_ds, p)
    dq_c = _pv_dot(ds_h, km_j).astype(jnp.float32) * pow2_f32(
        sc_ds + scale_exp(ek, p))
    dk_j = _tn_dot(ds_h, qm).astype(jnp.float32) * pow2_f32(
        sc_ds + scale_exp(eq, p))
    return dq_c, dk_j, dv_j


def _decode_core(qm, km, vm, ek_rows, ev_rows, rp, eq, qpos, kv_len, *,
                 p, causal, window):
    """One-shot decode attention off per-row-scaled cache mantissas.

    K row exponents become a per-output-column epilogue on the scores;
    V row exponents are folded into the float probabilities before their
    single quantization (one shared exponent per query row over the whole
    band) — the in-kernel fusion of ``qcache_qk`` + softmax + ``qcache_pv``.
    """
    gs, t = qm.shape[0], km.shape[0]
    sek = scale_exp(ek_rows, p).reshape(1, t)
    sev = scale_exp(ev_rows, p).reshape(1, t)
    kpos = lax.broadcasted_iota(jnp.int32, (gs, t), 1)
    mask = _block_mask(qpos, kpos, kv_len, causal, window)
    sf = _qk_dot(qm, km).astype(jnp.float32) * pow2_f32(
        scale_exp(eq, p) + sek)
    sf = jnp.where(mask, sf, _NEG)
    mrow = sf.max(axis=-1, keepdims=True)
    pe = jnp.exp(sf - mrow)
    pn = jnp.where(mask, pe / pe.sum(axis=-1, keepdims=True), 0.0)
    p2 = pn * pow2_f32(sev)                    # exact ×2^e fold
    e_row = eff_exp(p2).max(axis=-1, keepdims=True)
    ph = quantize_tile(p2, rp, e_row, p, rp is not None)
    y = _pv_dot(ph, vm).astype(jnp.float32)
    return y * pow2_f32(scale_exp(e_row, p))  # V runs at unit ref scale


def _strip_bounds(i, bq, s, q_off, kv_len, *, bt, causal, window, contig):
    """KV-block ``fori_loop`` bounds for query row-strip ``i``.

    Blocks past ``kv_len`` are always skipped.  When the strip is
    qpos-contiguous (``contig``: bq divides S, so a strip never crosses a
    GQA group boundary and has no padded rows), causal skips blocks past
    the strip's last query position and a sliding window skips blocks
    before its first reachable position.  Skipped blocks are exact no-ops
    (see ``_fwd_blocks``), so the bounds are a pure FLOP/traffic saving.
    """
    hi = (kv_len + bt - 1) // bt
    lo = jnp.int32(0)
    if contig:
        base = lax.rem(i * bq, s) + q_off
        if causal:
            hi = jnp.minimum(hi, (base + bq - 1) // bt + 1)
        if window:
            lo = jnp.maximum(lo, (base - (window - 1)) // bt)
    return lo, hi


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _attn_fwd_kernel(es_ref, *refs, p, s, bq, bt, causal, window, contig,
                     stochastic):
    if stochastic:
        qm_ref, km_ref, vm_ref, rp_ref = refs[:4]
        y_ref, m_ref, l_ref = refs[4:]
    else:
        qm_ref, km_ref, vm_ref = refs[:3]
        rp_ref = None
        y_ref, m_ref, l_ref = refs[3:]
    eq, ek, ev = es_ref[0], es_ref[1], es_ref[2]
    q_off, kv_len = es_ref[3], es_ref[4]
    d = qm_ref.shape[1]
    i = pl.program_id(0)
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    qpos = lax.rem(rows, s) + q_off
    lo, hi = _strip_bounds(i, bq, s, q_off, kv_len, bt=bt, causal=causal,
                           window=window, contig=contig)
    m, l, acc = _fwd_blocks(
        qm_ref[...],
        lambda j: km_ref[pl.ds(j * bt, bt), :],
        lambda j: vm_ref[pl.ds(j * bt, bt), :],
        None if rp_ref is None else (lambda j: rp_ref[:, pl.ds(j * bt, bt)]),
        eq, ek, ev, qpos, kv_len, lo, hi,
        p=p, bt=bt, d=d, causal=causal, window=window, stochastic=stochastic)
    y_ref[...] = acc / jnp.maximum(l, 1e-30)
    m_ref[...] = m
    l_ref[...] = l


@partial(jax.jit, static_argnames=("p", "s", "bq", "bt", "causal", "window",
                                   "stochastic", "interpret"))
def fused_attn_fwd_pallas(qm, km, vm, rp, eq, ek, ev, q_off, kv_len, *,
                          p=7, s, bq=128, bt=128, causal=True, window=0,
                          stochastic=True, interpret=False):
    """One fused attention pass over one (batch · KV-head) slice.

    qm (GS, D) int8, km/vm (T, D) int8, rp (GS, T) uint32 (None when
    ``stochastic=False``); eq/ek/ev scalar biased exponents; q_off /
    kv_len traced int32 scalars → (y (GS, D) f32, m (GS, 1), l (GS, 1)).
    GS % bq == 0 and T % bt == 0 (dispatch pads; padded KV masked by
    kv_len, padded rows cropped by the caller).
    """
    gs, d = qm.shape
    t = km.shape[0]
    assert gs % bq == 0 and t % bt == 0, (gs, bq, t, bt)
    es = jnp.stack([jnp.asarray(eq), jnp.asarray(ek), jnp.asarray(ev),
                    jnp.asarray(q_off), jnp.asarray(kv_len)]).astype(jnp.int32)
    q_spec = pl.BlockSpec((bq, d), lambda i, sc: (i, 0))
    kv_spec = pl.BlockSpec((t, d), lambda i, sc: (0, 0))
    if stochastic:
        in_specs = [q_spec, kv_spec, kv_spec,
                    pl.BlockSpec((bq, t), lambda i, sc: (i, 0))]
        operands = (es, qm, km, vm, rp)
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (es, qm, km, vm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gs // bq,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bq, d), lambda i, sc: (i, 0)),
                   pl.BlockSpec((bq, 1), lambda i, sc: (i, 0)),
                   pl.BlockSpec((bq, 1), lambda i, sc: (i, 0))],
    )
    return pl.pallas_call(
        partial(_attn_fwd_kernel, p=p, s=s, bq=bq, bt=bt, causal=causal,
                window=window, contig=(s % bq == 0), stochastic=stochastic),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((gs, d), jnp.float32),
                   jax.ShapeDtypeStruct((gs, 1), jnp.float32),
                   jax.ShapeDtypeStruct((gs, 1), jnp.float32)],
        interpret=interpret,
    )(*operands)


def _attn_fwd_ref_slice(qm, km, vm, rp, eq, ek, ev, q_off, kv_len, *,
                        p, s, bq, bt, causal, window, stochastic):
    """jnp mirror of the forward kernel: same strips, same block cores."""
    gs, d = qm.shape
    contig = (s % bq == 0)
    ys, ms, ls = [], [], []
    for i in range(gs // bq):
        rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        qpos = lax.rem(rows, s) + q_off
        lo, hi = _strip_bounds(jnp.int32(i), bq, s, q_off, kv_len, bt=bt,
                               causal=causal, window=window, contig=contig)
        m, l, acc = _fwd_blocks(
            lax.dynamic_slice_in_dim(qm, i * bq, bq, 0),
            lambda j: lax.dynamic_slice_in_dim(km, j * bt, bt, 0),
            lambda j: lax.dynamic_slice_in_dim(vm, j * bt, bt, 0),
            None if rp is None else
            (lambda j: lax.dynamic_slice(rp, (i * bq, j * bt), (bq, bt))),
            eq, ek, ev, qpos, kv_len, lo, hi,
            p=p, bt=bt, d=d, causal=causal, window=window,
            stochastic=stochastic)
        ys.append(acc / jnp.maximum(l, 1e-30))
        ms.append(m)
        ls.append(l)
    return (jnp.concatenate(ys, 0), jnp.concatenate(ms, 0),
            jnp.concatenate(ls, 0))


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

def _attn_bwd_kernel(es_ref, *refs, p, s, bt, causal, window, stochastic):
    if stochastic:
        (qm_ref, gm_ref, m_ref, l_ref, d_ref, km_ref, vm_ref,
         rs_ref, rp_ref) = refs[:9]
        rest = refs[9:]
    else:
        qm_ref, gm_ref, m_ref, l_ref, d_ref, km_ref, vm_ref = refs[:7]
        rs_ref = rp_ref = None
        rest = refs[7:]
    dq_ref, dk_ref, dv_ref = rest
    eq, ek, ev, eg = es_ref[0], es_ref[1], es_ref[2], es_ref[3]
    q_off, kv_len, gs_len = es_ref[4], es_ref[5], es_ref[6]
    gs = qm_ref.shape[0]
    j = pl.program_id(0)
    rows = lax.broadcasted_iota(jnp.int32, (gs, 1), 0)
    qpos = lax.rem(rows, s) + q_off
    dq_c, dk_j, dv_j = _bwd_block(
        j, qm_ref[...], gm_ref[...], km_ref[...], vm_ref[...],
        m_ref[...], l_ref[...], d_ref[...],
        None if rs_ref is None else rs_ref[...],
        None if rp_ref is None else rp_ref[...],
        eq, ek, ev, eg, qpos, rows < gs_len, kv_len,
        p=p, bt=bt, causal=causal, window=window, stochastic=stochastic)
    dk_ref[...] = dk_j
    dv_ref[...] = dv_j

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    dq_ref[...] += dq_c


@partial(jax.jit, static_argnames=("p", "s", "bt", "causal", "window",
                                   "stochastic", "interpret"))
def fused_attn_bwd_pallas(qm, gm, km, vm, m, l, delta, rs, rp2,
                          eq, ek, ev, eg, q_off, kv_len, gs_len, *, p=7, s,
                          bt=128, causal=True, window=0, stochastic=True,
                          interpret=False):
    """Fused integer attention backward over one (batch · KV-head) slice.

    qm/gm (GS, D) int8 (Q and quantized-dO mantissas, VMEM-resident across
    the whole grid), km/vm (T, D) int8 (one (bt, D) strip per program),
    m/l/delta (GS, 1) f32 saved row stats, rs/rp2 (GS, T) uint32 rounding
    bits (None when ``stochastic=False``) → (dq (GS, D), dk (T, D),
    dv (T, D)) f32 in value scale.  dQ accumulates across the sequential
    KV grid into a constant-index-map output block.
    """
    gs, d = qm.shape
    t = km.shape[0]
    assert t % bt == 0, (t, bt)
    es = jnp.stack([jnp.asarray(eq), jnp.asarray(ek), jnp.asarray(ev),
                    jnp.asarray(eg), jnp.asarray(q_off),
                    jnp.asarray(kv_len),
                    jnp.asarray(gs_len)]).astype(jnp.int32)
    res_spec = pl.BlockSpec((gs, d), lambda j, sc: (0, 0))
    stat_spec = pl.BlockSpec((gs, 1), lambda j, sc: (0, 0))
    blk_spec = pl.BlockSpec((bt, d), lambda j, sc: (j, 0))
    rnd_spec = pl.BlockSpec((gs, bt), lambda j, sc: (0, j))
    in_specs = [res_spec, res_spec, stat_spec, stat_spec, stat_spec,
                blk_spec, blk_spec]
    operands = [es, qm, gm, m, l, delta, km, vm]
    if stochastic:
        in_specs += [rnd_spec, rnd_spec]
        operands += [rs, rp2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // bt,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((gs, d), lambda j, sc: (0, 0)),
                   blk_spec, blk_spec],
    )
    return pl.pallas_call(
        partial(_attn_bwd_kernel, p=p, s=s, bt=bt, causal=causal,
                window=window, stochastic=stochastic),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((gs, d), jnp.float32),
                   jax.ShapeDtypeStruct((t, d), jnp.float32),
                   jax.ShapeDtypeStruct((t, d), jnp.float32)],
        interpret=interpret,
    )(*operands)


def _attn_bwd_ref_slice(qm, gm, km, vm, m, l, delta, rs, rp2, eq, ek, ev,
                        eg, q_off, kv_len, gs_len, *, p, s, bt, causal,
                        window, stochastic):
    """jnp mirror of the backward kernel: same blocks, same f32 sum order."""
    gs, d = qm.shape
    t = km.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (gs, 1), 0)
    qpos = lax.rem(rows, s) + q_off
    dq = jnp.zeros((gs, d), jnp.float32)
    dks, dvs = [], []
    for j in range(t // bt):
        dq_c, dk_j, dv_j = _bwd_block(
            jnp.int32(j), qm, gm,
            lax.dynamic_slice_in_dim(km, j * bt, bt, 0),
            lax.dynamic_slice_in_dim(vm, j * bt, bt, 0),
            m, l, delta,
            None if rs is None else
            lax.dynamic_slice(rs, (0, j * bt), (gs, bt)),
            None if rp2 is None else
            lax.dynamic_slice(rp2, (0, j * bt), (gs, bt)),
            eq, ek, ev, eg, qpos, rows < gs_len, kv_len,
            p=p, bt=bt, causal=causal, window=window, stochastic=stochastic)
        dq = dq + dq_c
        dks.append(dk_j)
        dvs.append(dv_j)
    return dq, jnp.concatenate(dks, 0), jnp.concatenate(dvs, 0)


# ---------------------------------------------------------------------------
# decode kernel (qcache rows: per-row exponents consumed in-kernel)
# ---------------------------------------------------------------------------

def _attn_decode_kernel(es_ref, *refs, p, s, causal, window, stochastic):
    if stochastic:
        qm_ref, km_ref, vm_ref, ek_ref, ev_ref, rp_ref = refs[:6]
        rest = refs[6:]
    else:
        qm_ref, km_ref, vm_ref, ek_ref, ev_ref = refs[:5]
        rp_ref = None
        rest = refs[5:]
    y_ref, = rest
    eq, q_off, kv_len = es_ref[0], es_ref[1], es_ref[2]
    gs = qm_ref.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (gs, 1), 0)
    qpos = lax.rem(rows, s) + q_off
    y_ref[...] = _decode_core(
        qm_ref[...], km_ref[...], vm_ref[...], ek_ref[...], ev_ref[...],
        None if rp_ref is None else rp_ref[...], eq, qpos, kv_len,
        p=p, causal=causal, window=window)


@partial(jax.jit, static_argnames=("p", "s", "causal", "window",
                                   "stochastic", "interpret"))
def fused_attn_decode_pallas(qm, km, vm, ek_rows, ev_rows, rp, eq, q_off,
                             kv_len, *, p=7, s, causal=True, window=0,
                             stochastic=True, interpret=False):
    """Fused qcache decode attention over one (batch · KV-head) slice.

    qm (GS, D) int8 (scalar exponent eq), km/vm (T, D) int8 cache row
    mantissas with per-row int32 exponents ek_rows/ev_rows (T, 1), rp
    (GS, T) uint32 (None when ``stochastic=False``) → y (GS, D) f32.
    One program: decode GS is tiny, the whole band stays in VMEM.
    """
    gs, d = qm.shape
    t = km.shape[0]
    es = jnp.stack([jnp.asarray(eq), jnp.asarray(q_off),
                    jnp.asarray(kv_len)]).astype(jnp.int32)
    const = lambda shape: pl.BlockSpec(shape, lambda i, sc: (0, 0))
    in_specs = [const((gs, d)), const((t, d)), const((t, d)),
                const((t, 1)), const((t, 1))]
    operands = [es, qm, km, vm, ek_rows, ev_rows]
    if stochastic:
        in_specs.append(const((gs, t)))
        operands.append(rp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=in_specs,
        out_specs=[const((gs, d))],
    )
    y, = pl.pallas_call(
        partial(_attn_decode_kernel, p=p, s=s, causal=causal, window=window,
                stochastic=stochastic),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((gs, d), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return y


def _attn_decode_ref_slice(qm, km, vm, ek_rows, ev_rows, rp, eq, q_off,
                           kv_len, *, p, s, causal, window, stochastic):
    gs = qm.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (gs, 1), 0)
    qpos = lax.rem(rows, s) + q_off
    return _decode_core(qm, km, vm, ek_rows, ev_rows,
                        rp if stochastic else None, eq, qpos, kv_len,
                        p=p, causal=causal, window=window)


# ---------------------------------------------------------------------------
# batched entry points: pad → lax.map over (B·Hkv) slices → crop.
# ---------------------------------------------------------------------------

def _pad_rows(x, rows, cols=None):
    pr = rows - x.shape[-2]
    pc = 0 if cols is None else cols - x.shape[-1]
    if pr or pc:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)])
    return x


def attn_fwd(qm, km, vm, rp, eq, ek, ev, q_off, kv_len, *, p, s, bq, bt,
             causal, window, stochastic, interpret, pallas):
    """Batched fused-attention forward: qm (BH, GS, D) int8, km/vm (BH, T,
    D) int8, rp (BH, GS, T) uint32 | None → (y (BH, GS, D) f32, m (BH,
    GS, 1), l (BH, GS, 1)).  Pads GS→bq·⌈·⌉, T→bt·⌈·⌉, D→128·⌈·⌉ (zero
    mantissas; padded KV masked via kv_len), maps the 2-D kernel (or its
    bit-exact jnp mirror when ``pallas=False``) over the slices, crops.
    """
    gs, d = qm.shape[-2], qm.shape[-1]
    t = km.shape[-2]
    gsp, tp, dp = round_up(gs, bq), round_up(t, bt), round_up(d, 128)
    kv = jnp.minimum(jnp.asarray(kv_len, jnp.int32), t)
    qo = jnp.asarray(q_off, jnp.int32)
    qm = _pad_rows(qm, gsp, dp)
    km = _pad_rows(km, tp, dp)
    vm = _pad_rows(vm, tp, dp)
    if stochastic:
        rp = _pad_rows(rp, gsp, tp)
    kw = dict(p=p, s=s, causal=causal, window=window, stochastic=stochastic)

    def one(args):
        if stochastic:
            q2, k2, v2, r2 = args
        else:
            (q2, k2, v2), r2 = args, None
        if pallas:
            return fused_attn_fwd_pallas(q2, k2, v2, r2, eq, ek, ev, qo, kv,
                                         bq=bq, bt=bt, interpret=interpret,
                                         **kw)
        return _attn_fwd_ref_slice(q2, k2, v2, r2, eq, ek, ev, qo, kv,
                                   bq=bq, bt=bt, **kw)

    arrs = (qm, km, vm) + ((rp,) if stochastic else ())
    y, m, l = lax.map(one, arrs)
    return y[..., :gs, :d], m[..., :gs, :], l[..., :gs, :]


def attn_bwd(qm, gm, km, vm, m, l, delta, rs, rp2, eq, ek, ev, eg, q_off,
             kv_len, *, p, s, bt, causal, window, stochastic, interpret,
             pallas):
    """Batched fused-attention backward (same padding contract as
    :func:`attn_fwd`; ``m``/``l``/``delta`` are (BH, GS, 1) saved stats).
    Returns (dq (BH, GS, D), dk (BH, T, D), dv (BH, T, D)) f32.
    """
    gs, d = qm.shape[-2], qm.shape[-1]
    t = km.shape[-2]
    # the Q side stays whole-resident: pad rows to the int8 sublane pack
    gsp, tp, dp = round_up(gs, 32), round_up(t, bt), round_up(d, 128)
    kv = jnp.minimum(jnp.asarray(kv_len, jnp.int32), t)
    qo = jnp.asarray(q_off, jnp.int32)
    qm, gm = _pad_rows(qm, gsp, dp), _pad_rows(gm, gsp, dp)
    km, vm = _pad_rows(km, tp, dp), _pad_rows(vm, tp, dp)
    m, l = _pad_rows(m, gsp), _pad_rows(l, gsp)
    delta = _pad_rows(delta, gsp)
    if stochastic:
        rs, rp2 = _pad_rows(rs, gsp, tp), _pad_rows(rp2, gsp, tp)
    kw = dict(p=p, s=s, bt=bt, causal=causal, window=window,
              stochastic=stochastic)

    def one(args):
        if stochastic:
            q2, g2, k2, v2, m2, l2, d2, r1, r2 = args
        else:
            (q2, g2, k2, v2, m2, l2, d2), r1, r2 = args, None, None
        if pallas:
            return fused_attn_bwd_pallas(q2, g2, k2, v2, m2, l2, d2, r1, r2,
                                         eq, ek, ev, eg, qo, kv,
                                         jnp.int32(gs), interpret=interpret,
                                         **kw)
        return _attn_bwd_ref_slice(q2, g2, k2, v2, m2, l2, d2, r1, r2,
                                   eq, ek, ev, eg, qo, kv, jnp.int32(gs),
                                   **kw)

    arrs = (qm, gm, km, vm, m, l, delta) + ((rs, rp2) if stochastic else ())
    dq, dk, dv = lax.map(one, arrs)
    return dq[..., :gs, :d], dk[..., :t, :d], dv[..., :t, :d]


def attn_decode(qm, km, vm, ek_rows, ev_rows, rp, eq, q_off, kv_len, *,
                p, s, causal, window, stochastic, interpret, pallas):
    """Batched fused qcache decode: qm (BH, GS, D) int8, km/vm (BH, T, D)
    int8 cache mantissas, ek_rows/ev_rows (BH, T, 1) int32 per-row
    exponents, rp (BH, GS, T) | None → y (BH, GS, D) f32.  Padded cache
    rows carry exponent 1 (the qcache zero-row convention) and are masked
    via kv_len anyway.
    """
    gs, d = qm.shape[-2], qm.shape[-1]
    t = km.shape[-2]
    gsp, tp, dp = round_up(gs, 32), round_up(t, 32), round_up(d, 128)
    kv = jnp.minimum(jnp.asarray(kv_len, jnp.int32), t)
    qo = jnp.asarray(q_off, jnp.int32)
    qm = _pad_rows(qm, gsp, dp)
    km, vm = _pad_rows(km, tp, dp), _pad_rows(vm, tp, dp)
    pe = [(0, 0)] * (ek_rows.ndim - 2) + [(0, tp - t), (0, 0)]
    ek_rows = jnp.pad(ek_rows, pe, constant_values=1)
    ev_rows = jnp.pad(ev_rows, pe, constant_values=1)
    if stochastic:
        rp = _pad_rows(rp, gsp, tp)
    kw = dict(p=p, s=s, causal=causal, window=window, stochastic=stochastic)

    def one(args):
        if stochastic:
            q2, k2, v2, e1, e2, r2 = args
        else:
            (q2, k2, v2, e1, e2), r2 = args, None
        if pallas:
            return fused_attn_decode_pallas(q2, k2, v2, e1, e2, r2, eq, qo,
                                            kv, interpret=interpret, **kw)
        return _attn_decode_ref_slice(q2, k2, v2, e1, e2, r2, eq, qo, kv,
                                      **kw)

    arrs = (qm, km, vm, ek_rows, ev_rows) + ((rp,) if stochastic else ())
    y = lax.map(one, arrs)
    return y[..., :gs, :d]
