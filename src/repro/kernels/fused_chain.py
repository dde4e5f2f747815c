"""Cross-op fused Pallas chains (docs/KERNELS.md §Cross-op fusion).

Two kernel families that keep BFP operands resident in VMEM *across* op
boundaries, so no float intermediate round-trips HBM between a producer
and its consumer:

  norm→quantize→GEMM (``fused_norm_gemm_pallas``)
      One ``pallas_call`` runs an integer RMS/LayerNorm datapath on a
      row-strip of the input, emits per-row int8 mantissas straight into
      the MXU against the VMEM-resident weight mantissas, and writes only
      the f32 GEMM output (plus the int8 residuals the backward needs).
      The unfused composition (``core.qnorm`` → quantize → ``qmatmul``)
      materializes the normalized activation, its BFP copy, and the GEMM
      input in HBM; the fused chain materializes none of them.

  whole-block decode megakernel (``fused_decode_block_pallas``)
      For small decode batches the *entire* transformer layer —
      norm → QKV GEMM → rope → fused decode attention over the quantized
      KV cache → out-proj → norm → gated MLP — runs as one ``pallas_call``
      with every weight mantissa and cache row VMEM-resident.  The fresh
      K/V rows are quantized in-kernel with the same nearest/per-row rule
      as ``qcache_append`` and returned for the caller to write into the
      cache, so the cache currency is unchanged.

Numerics contract (docs/KERNELS.md):

  * The fused chains are *allowed to deviate* from the unfused composition
    (like PR 5's fused attention): the norm datapath here is a leaner
    per-row fx variant of ``core.qnorm``'s tensor-wide calculus.  What is
    NOT allowed to deviate is kernel-vs-mirror: every kernel body calls
    the same block-core functions (``_norm_rows_core``,
    ``_norm_gemm_core``, ``_decode_block_core``) as its jnp mirror, and
    every step of those cores is row-independent, so the mirror on the
    full array is bit-identical to any row-strip decomposition by
    construction.  Tests assert ``==``.
  * Stochastic rounding bits come from caller-supplied ``rounding_bits``
    arrays streamed as kernel operands (the ``fused_attention``
    precedent) — exactly one array for the input quantize and one for the
    output quantize; every intermediate narrowing is deterministic
    (half-up), so the kernel is TPU-lowerable with no in-kernel PRNG.
  * ``stochastic=False`` (serving / decode) streams no random bits at all.

Shape contract: callers (``kernels.dispatch``) pre-pad rows to the strip
height and K/N to lane multiples; the true feature width ``n`` is passed
statically so the norm statistics (Σx, Σx², 1/n) ignore padded columns.
Zero-padding is exact end-to-end: padded f32 columns quantize to zero
mantissas, the column mask keeps them out of the LayerNorm centering, and
zero weight rows contribute nothing to the dot.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_attention import _decode_core
from .tile import eff_exp, int8_dot, pow2_f32, quantize_tile, scale_exp

__all__ = [
    "decode_block_ref",
    "div_n_consts",
    "eps_consts",
    "fused_decode_block_pallas",
    "fused_norm_gemm_pallas",
    "norm_gemm_ref",
]

_META_LANES = 128  # per-row metadata is padded out to one int32 lane group


# ---------------------------------------------------------------------------
# integer scalar helpers (static python / traced int32)
# ---------------------------------------------------------------------------

def div_n_consts(n: int):
    """Static fixed-point divide-by-n constants: n = 2^j * q (q odd) and
    inv_q = round(2^14 / q), so x/n ≈ (x * inv_q) * 2^(-14-j)
    (``core.fixed_point.fx_div_n``'s reciprocal trick)."""
    j = (n & -n).bit_length() - 1
    q = n >> j
    return j, round((1 << 14) / q)


def eps_consts(eps: float):
    """Static 15-bit fixed-point mantissa/exponent pair for the norm eps."""
    fr, ex = math.frexp(eps)
    return round(fr * (1 << 15)), ex - 15


def _bitlen(v):
    """Bits needed for non-negative int32 v (0 -> 0); cf. core.bfp.bit_length."""
    return (32 - lax.clz(jnp.maximum(v, 0).astype(jnp.int32))).astype(jnp.int32)


def _sr_shift(v, s, rand):
    """round(v / 2^s) on signed int32 with threshold-compare rounding:
    stochastic against ``rand`` (uint32) when given, else half-up.  The
    magnitude path is the lifted-threshold form of ``core.bfp._shift_round``,
    valid for any int32 magnitude."""
    shape = jnp.broadcast_shapes(jnp.shape(v), jnp.shape(s))
    v = jnp.broadcast_to(v, shape)
    s = jnp.broadcast_to(jnp.asarray(s, jnp.int32), shape)
    mag = jnp.abs(v).astype(jnp.uint32)
    s31 = jnp.minimum(s, 31).astype(jnp.uint32)
    base = jnp.where(s < 32, mag >> s31, jnp.uint32(0))
    m_lo = mag & ((jnp.uint32(1) << s31) - jnp.uint32(1))
    left = jnp.clip(32 - s, 0, 31).astype(jnp.uint32)
    over = jnp.clip(s - 32, 0, 31).astype(jnp.uint32)
    thr = jnp.where(s <= 31, m_lo << left,
                    jnp.where(s == 32, mag, mag >> over))
    if rand is None:
        up = (thr >= jnp.uint32(0x80000000)) & (s > 0)
    else:
        up = (rand < thr) & (s > 0)
    out = (base + up.astype(jnp.uint32)).astype(jnp.int32)
    return jnp.where(v < 0, -out, out)


def _shr(v, s):
    """Plain truncating right shift with a clamped traced amount."""
    return v >> jnp.clip(s, 0, 31).astype(jnp.uint32)


def _int_rsqrt(vm, ev):
    """Integer Newton–Raphson 1/sqrt of vm * 2^ev (vm 15-bit positive):
    the in-kernel replica of ``core.fixed_point.fx_rsqrt`` — normalize to
    [2^15, 2^17) with an even residual exponent, seed from the bit length,
    4 Newton steps in int32.  Returns (r 15-bit, e_r) per element."""
    v = jnp.maximum(vm, 1)
    d = _bitlen(v) - 16
    vn = jnp.where(d >= 0, _shr(v, d),
                   v << jnp.clip(-d, 0, 31).astype(jnp.uint32))
    e2 = ev + d
    odd = (e2 & 1) == 1
    vn = jnp.where(odd, vn << 1, vn)
    e2 = jnp.where(odd, e2 - 1, e2)
    r = jnp.where(vn >= (1 << 16), jnp.int32(11585), jnp.int32(16384))
    for _ in range(4):
        t = (r * r) >> 16
        r = (r * (((3 << 28) - vn * t) >> 14)) >> 15
    return r, -22 - (e2 >> 1)


# ---------------------------------------------------------------------------
# block core: per-row integer normalize -> quantize
# ---------------------------------------------------------------------------

def _row_quantize(x, rand, p, mask=None):
    """Per-row shared-exponent int8 quantize of an f32 tile.
    Returns (mantissas int8, biased row exponents (R, 1) int32)."""
    e = eff_exp(x)
    if mask is not None:
        e = jnp.where(mask, e, 1)
    e_row = e.max(axis=-1, keepdims=True)
    return quantize_tile(x, rand, e_row, p, rand is not None), e_row


def _norm_rows_core(x, rand_in, rand_out, gm, se_g, bm_, se_b, *, n, p,
                    eps_m, eps_e, center, stochastic):
    """The fx-lite per-row RMS/LayerNorm → quantize datapath.

    x (R, Kp) f32 strip (Kp >= n, zero-padded); rand_in/rand_out (R, Kp)
    uint32 or None; gm (1, Kp) int32 15-bit gamma mantissas at scale
    2^se_g; bm_ (1, Kp) int32 beta mantissas at 2^se_b (LayerNorm only).
    Returns (xq int8, se_row, c int8, e_c, r, e_r) with the four per-row
    int32 scale columns shaped (R, 1).  Every step is per-row independent
    — the strip decomposition is bit-invariant.
    """
    del stochastic  # encoded by rand_in/rand_out being None
    kp = x.shape[-1]
    j, inv_q = div_n_consts(n)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) < n

    # 1. per-row input quantize to 7 magnitude bits (the c7 step of qnorm)
    c, e_in = _row_quantize(x, rand_in, 7, mask)
    sc = scale_exp(e_in, 7)                       # (R, 1) value scale of c
    ci = c.astype(jnp.int32)

    if center:
        # mean: Σc exact (<= n*127), deterministic 15-bit narrow, * inv_q
        s1 = jnp.sum(jnp.where(mask, ci, 0), axis=-1, keepdims=True)
        sh1 = jnp.maximum(_bitlen(jnp.abs(s1)) - 15, 0)
        mu = _sr_shift(s1, sh1, None) * inv_q      # <= 2^29
        # center at scale sc - 8: c<<8 minus mu aligned down (always a
        # right shift: 6 + j - sh1 >= 1 for any n >= 2)
        cm = (ci << 8) - _sr_shift(mu, 6 + j - sh1, None)
        cm = jnp.where(mask, cm, 0)
        # deterministic per-row renarrow to 7 bits
        shc = jnp.maximum(
            _bitlen(jnp.abs(cm).max(axis=-1, keepdims=True)) - 7, 0)
        ci = _sr_shift(cm, shc, None)
        c = ci.astype(jnp.int8)
        sc = sc - 8 + shc
        j, inv_q = div_n_consts(n)

    # 2. variance: Σc² exact (<= n*2^14), deterministic narrow, * inv_q
    s2 = jnp.sum(ci * ci, axis=-1, keepdims=True)
    sh2 = jnp.maximum(_bitlen(s2) - 15, 0)
    vm = _shr(s2, sh2) * inv_q                     # <= 2^29
    e_v = 2 * sc + sh2 - 14 - j
    sh3 = jnp.maximum(_bitlen(vm) - 15, 0)
    vm = _shr(vm, sh3)
    e_v = e_v + sh3

    # 3. + eps at the common scale, then integer rsqrt
    e_cm = jnp.maximum(e_v, eps_e)
    vs = _shr(vm, e_cm - e_v) + _shr(jnp.int32(eps_m), e_cm - eps_e)
    r, e_r = _int_rsqrt(vs, e_cm)                  # (R, 1)

    # 4. o = ((c * r) >> 8) * gamma : exact int32 at every step (<= 2^29)
    t = _sr_shift(ci * r, 8, None)                 # <= 2^14
    o = t * gm                                     # gm 15-bit -> <= 2^29
    e_o = sc + e_r + 8 + se_g
    if bm_ is not None:
        sho = jnp.maximum(
            _bitlen(jnp.abs(o).max(axis=-1, keepdims=True)) - 15, 0)
        o = _sr_shift(o, sho, None)
        e_o = e_o + sho
        e_ob = jnp.maximum(e_o, se_b)
        o = _sr_shift(o, e_ob - e_o, None) + \
            jnp.where(mask, _sr_shift(bm_, e_ob - se_b, None), 0)
        e_o = e_ob

    # 5. single per-row SR quantize to p magnitude bits
    shq = jnp.maximum(
        _bitlen(jnp.abs(o).max(axis=-1, keepdims=True)) - p, 0)
    xq = jnp.clip(_sr_shift(o, shq, rand_out),
                  -(1 << p) + 1, (1 << p) - 1).astype(jnp.int8)
    del kp
    return xq, e_o + shq, c, sc, r, e_r


def _pack_meta(se_row, sc, r, e_r):
    """Per-row scale columns -> one (R, 128) int32 lane-padded block."""
    rows = se_row.shape[0]
    pad = jnp.zeros((rows, _META_LANES - 4), jnp.int32)
    return jnp.concatenate([se_row, sc, r, e_r, pad], axis=-1)


def _norm_gemm_core(x, rand_in, rand_out, gm, se_g, bm_, se_b, w_m, se_w, *,
                    n, p, eps_m, eps_e, center):
    """norm rows -> int8 GEMM -> per-row/per-column exponent rescale.
    w_m (N, Kp) int8 contraction-last; se_w (1, N) int32 per-column scale
    exponents (supports stacked weight leaves with distinct exponents)."""
    xq, se_row, c, sc, r, e_r = _norm_rows_core(
        x, rand_in, rand_out, gm, se_g, bm_, se_b, n=n, p=p,
        eps_m=eps_m, eps_e=eps_e, center=center, stochastic=rand_out is not None)
    acc = int8_dot(xq, w_m)
    y = acc.astype(jnp.float32) * pow2_f32(se_row + se_w)
    return y, xq, _pack_meta(se_row, sc, r, e_r), c


# ---------------------------------------------------------------------------
# norm -> quantize -> GEMM kernel + mirror
# ---------------------------------------------------------------------------

def _norm_gemm_kernel(es_ref, *refs, n, p, eps_m, eps_e, center, stochastic,
                      has_beta, emit_residuals):
    """Inputs (x[, rand_in, rand_out], gm[, bm], w, se_w); outputs
    (y[, xq, meta, c]).  One program per row-strip; the weight mantissas,
    gamma/beta and per-column exponents are VMEM-resident across the grid."""
    it = iter(refs)
    x_ref = next(it)
    ri_ref = next(it) if stochastic else None
    ro_ref = next(it) if stochastic else None
    gm_ref = next(it)
    bm_ref = next(it) if has_beta else None
    w_ref = next(it)
    sw_ref = next(it)
    y_ref = next(it)
    if emit_residuals:
        xq_ref, meta_ref, c_ref = next(it), next(it), next(it)
    se_g = es_ref[0]
    se_b = es_ref[1]
    y, xq, meta, c = _norm_gemm_core(
        x_ref[...],
        None if ri_ref is None else ri_ref[...],
        None if ro_ref is None else ro_ref[...],
        gm_ref[...], se_g,
        None if bm_ref is None else bm_ref[...], se_b,
        w_ref[...], sw_ref[...],
        n=n, p=p, eps_m=eps_m, eps_e=eps_e, center=center)
    y_ref[...] = y
    if emit_residuals:
        xq_ref[...] = xq
        meta_ref[...] = meta
        c_ref[...] = c


@partial(jax.jit, static_argnames=("n", "p", "eps_m", "eps_e", "center",
                                   "bm", "stochastic", "interpret",
                                   "emit_residuals"))
def fused_norm_gemm_pallas(x, rand_in, rand_out, gm, se_g, beta_m, se_b,
                           w_m, se_w, *, n, p=7, eps_m=1, eps_e=-32,
                           center=False, bm=256, stochastic=True,
                           interpret=False, emit_residuals=True):
    """Fused integer norm -> per-row quantize -> int8 GEMM.

    x (M, Kp) f32 (rows % bm == 0, Kp lane-padded; true width ``n``),
    rand_in/rand_out (M, Kp) uint32 (None when ``stochastic=False``),
    gm (1, Kp) int32 gamma mantissas at 2^se_g, beta_m (1, Kp) int32 or
    None (RMS), w_m (N, Kp) int8 contraction-last weight mantissas,
    se_w (1, N) int32 per-column scale exponents ->
    (y (M, N) f32[, xq (M, Kp) int8, meta (M, 128) int32, c (M, Kp) int8])
    with meta columns [se_row, e_c, r, e_r] (backward residuals).
    """
    m, kp = x.shape
    nn = w_m.shape[0]
    assert m % bm == 0, (m, bm)
    es = jnp.stack([jnp.asarray(se_g), jnp.asarray(se_b)]).astype(jnp.int32)
    strip = pl.BlockSpec((bm, kp), lambda i, s: (i, 0))
    row1 = pl.BlockSpec((1, kp), lambda i, s: (0, 0))
    in_specs = [strip]
    operands = [es, x]
    if stochastic:
        in_specs += [strip, strip]
        operands += [rand_in, rand_out]
    in_specs.append(row1)
    operands.append(gm)
    if beta_m is not None:
        in_specs.append(row1)
        operands.append(beta_m)
    in_specs += [pl.BlockSpec((nn, kp), lambda i, s: (0, 0)),
                 pl.BlockSpec((1, nn), lambda i, s: (0, 0))]
    operands += [w_m, se_w]
    out_specs = [pl.BlockSpec((bm, nn), lambda i, s: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((m, nn), jnp.float32)]
    if emit_residuals:
        out_specs += [pl.BlockSpec((bm, kp), lambda i, s: (i, 0)),
                      pl.BlockSpec((bm, _META_LANES), lambda i, s: (i, 0)),
                      pl.BlockSpec((bm, kp), lambda i, s: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((m, kp), jnp.int8),
                      jax.ShapeDtypeStruct((m, _META_LANES), jnp.int32),
                      jax.ShapeDtypeStruct((m, kp), jnp.int8)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        partial(_norm_gemm_kernel, n=n, p=p, eps_m=eps_m, eps_e=eps_e,
                center=center, stochastic=stochastic,
                has_beta=beta_m is not None,
                emit_residuals=emit_residuals),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return tuple(out) if emit_residuals else (out[0],)


@partial(jax.jit, static_argnames=("n", "p", "eps_m", "eps_e", "center",
                                   "emit_residuals"))
def norm_gemm_ref(x, rand_in, rand_out, gm, se_g, beta_m, se_b, w_m, se_w, *,
                  n, p=7, eps_m=1, eps_e=-32, center=False,
                  emit_residuals=True):
    """Bit-exact jnp mirror of :func:`fused_norm_gemm_pallas`: the same
    ``_norm_gemm_core`` on the full (M, Kp) array.  Row-independence of
    every core step makes this equal to any strip decomposition."""
    y, xq, meta, c = _norm_gemm_core(
        x, rand_in, rand_out, gm, jnp.asarray(se_g, jnp.int32),
        beta_m, jnp.asarray(se_b, jnp.int32), w_m, se_w,
        n=n, p=p, eps_m=eps_m, eps_e=eps_e, center=center)
    return (y, xq, meta, c) if emit_residuals else (y,)


# ---------------------------------------------------------------------------
# whole-block decode megakernel + mirror
# ---------------------------------------------------------------------------

def _rope_half(x, cos, sin):
    """Half-rotation rope on (..., dh): matches models.attention."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


def _decode_block_core(x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu, wd_m,
                      se_d, g1m, se_g1, g2m, se_g2, km, ke, vm, ve, cos, sin,
                      pos, *, n_d, n_ff, hq, hkv, dh, p, window,
                      eps_m, eps_e):
    """One decoder layer on (B, d) rows, everything resident.

    Weights arrive contraction-last as int8 mantissas with per-column
    int32 scale exponents (1, N); the KV cache arrives as per-row-scaled
    mantissas km/vm (B, Hkv, T, dh) int8 with biased row exponents
    ke/ve (B, Hkv, T, 1).  Fresh K/V rows are quantized with the cache's
    nearest/per-row rule and returned for the caller's append.  All
    rounding is deterministic (serving path) — no random bits.
    Returns (x_out (B, d) f32, k_new (B*Hkv, dh) int8, ek_new (B*Hkv, 1),
    v_new, ev_new).
    """
    b = x.shape[0]
    gs = hq // hkv

    # --- norm 1 -> QKV GEMM (merged projection) ---
    xq1, se1, *_ = _norm_rows_core(
        x, None, None, g1m, se_g1, None, None, n=n_d, p=p,
        eps_m=eps_m, eps_e=eps_e, center=False, stochastic=False)
    qkv = int8_dot(xq1, wqkv_m).astype(jnp.float32) * pow2_f32(se1 + se_qkv)
    nq, nk = hq * dh, hkv * dh

    def head(col, bi):                  # one head of one row: (1, dh)
        return qkv[bi:bi + 1, col:col + dh]

    # --- fresh K/V rows: the qcache_append currency (nearest, per-row) ---
    k2 = jnp.concatenate([_rope_half(head(nq + h * dh, bi), cos, sin)
                          for bi in range(b) for h in range(hkv)], axis=0)
    v2 = jnp.concatenate([head(nq + nk + h * dh, bi)
                          for bi in range(b) for h in range(hkv)], axis=0)
    k_new, ek_new = _row_quantize(k2, None, p)
    v_new, ev_new = _row_quantize(v2, None, p)

    # --- decode attention per (batch, kv-head) group over the cache ---
    qpos = jnp.full((gs, 1), pos, jnp.int32)
    at_pos = lax.broadcasted_iota(jnp.int32, (km.shape[2], 1), 0) == pos
    rows = []
    for bi in range(b):
        attn = []
        for h in range(hkv):
            r = bi * hkv + h
            km_f = jnp.where(at_pos, k_new[r:r + 1], km[bi, h])
            ke_f = jnp.where(at_pos, ek_new[r:r + 1], ke[bi, h])
            vm_f = jnp.where(at_pos, v_new[r:r + 1], vm[bi, h])
            ve_f = jnp.where(at_pos, ev_new[r:r + 1], ve[bi, h])
            qg = _rope_half(jnp.concatenate(
                [head((h * gs + g) * dh, bi) for g in range(gs)], axis=0),
                cos, sin)
            eq = eff_exp(qg).max()
            qm = quantize_tile(qg, None, eq, p, False)
            y_g = _decode_core(
                qm, km_f, vm_f, ke_f[:, 0], ve_f[:, 0], None, eq, qpos,
                pos + 1, p=p, causal=True, window=window)
            attn += [y_g[g:g + 1] for g in range(gs)]
        rows.append(jnp.concatenate(attn, axis=1))
    y = jnp.concatenate(rows, axis=0)

    # --- out projection + residual ---
    aq, ea = _row_quantize(y, None, p)
    o = int8_dot(aq, wo_m).astype(jnp.float32) * pow2_f32(
        scale_exp(ea, p) + se_o)
    h2 = x + o

    # --- norm 2 -> gated MLP (merged gate|up GEMM, silu-GLU epilogue) ---
    xq2, se2, *_ = _norm_rows_core(
        h2, None, None, g2m, se_g2, None, None, n=n_d, p=p,
        eps_m=eps_m, eps_e=eps_e, center=False, stochastic=False)
    gu = int8_dot(xq2, wgu_m).astype(jnp.float32) * pow2_f32(se2 + se_gu)
    act = jax.nn.silu(gu[:, :n_ff]) * gu[:, n_ff:]
    mq, em = _row_quantize(act, None, p)
    dn = int8_dot(mq, wd_m).astype(jnp.float32) * pow2_f32(
        scale_exp(em, p) + se_d)
    return h2 + dn, k_new, ek_new, v_new, ev_new


def _decode_block_kernel(pos_ref, x_ref, wqkv_ref, sqkv_ref, wo_ref, so_ref,
                         wgu_ref, sgu_ref, wd_ref, sd_ref, g1_ref, g2_ref,
                         km_ref, ke_ref, vm_ref, ve_ref, cs_ref,
                         y_ref, kn_ref, ekn_ref, vn_ref, evn_ref, *,
                         n_d, n_ff, hq, hkv, dh, p, window, eps_m, eps_e,
                         se_g1, se_g2):
    """grid=(1,): the whole layer in one program, all operands resident."""
    dh_ = cs_ref.shape[-1] // 2
    cos = cs_ref[:, :dh_]
    sin = cs_ref[:, dh_:]
    out, kn, ekn, vn, evn = _decode_block_core(
        x_ref[...], wqkv_ref[...], sqkv_ref[...], wo_ref[...], so_ref[...],
        wgu_ref[...], sgu_ref[...], wd_ref[...], sd_ref[...],
        g1_ref[...], se_g1, g2_ref[...], se_g2,
        km_ref[...], ke_ref[...], vm_ref[...], ve_ref[...],
        cos, sin, pos_ref[0],
        n_d=n_d, n_ff=n_ff, hq=hq, hkv=hkv, dh=dh, p=p, window=window,
        eps_m=eps_m, eps_e=eps_e)
    y_ref[...] = out
    kn_ref[...] = kn
    ekn_ref[...] = ekn
    vn_ref[...] = vn
    evn_ref[...] = evn


@partial(jax.jit, static_argnames=("n_d", "n_ff", "hq", "hkv", "dh", "p",
                                   "window", "eps_m", "eps_e", "se_g1",
                                   "se_g2", "interpret"))
def fused_decode_block_pallas(x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu,
                              wd_m, se_d, g1m, g2m, km, ke, vm, ve, cossin,
                              pos, *, n_d, n_ff, hq, hkv, dh, p=7, window=0,
                              eps_m=1, eps_e=-32, se_g1=0, se_g2=0,
                              interpret=False):
    """One decoder layer as a single ``pallas_call`` (see module docstring).

    x (B, d) f32; weight mantissas contraction-last int8 with (1, N) int32
    per-column exponents; g1m/g2m (1, d) int32 gamma mantissas at the
    static 2^se_g1 / 2^se_g2 scales; km/ke/vm/ve the quantized cache
    (pre-append); cossin (1, 2*dh) f32 rope row for this position;
    pos () int32.  Returns (x_out, k_new, ek_new, v_new, ev_new).
    """
    b, d = x.shape
    t = km.shape[2]
    rows = b * hkv
    res = pl.pallas_call(
        partial(_decode_block_kernel, n_d=n_d, n_ff=n_ff, hq=hq, hkv=hkv,
                dh=dh, p=p, window=window, eps_m=eps_m, eps_e=eps_e,
                se_g1=se_g1, se_g2=se_g2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(a.shape,
                                   lambda i, s, nd=a.ndim: (0,) * nd)
                      for a in (x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu,
                                wd_m, se_d, g1m, g2m, km, ke, vm, ve,
                                cossin)],
            out_specs=[pl.BlockSpec(sh, lambda i, s, nd=len(sh): (0,) * nd)
                       for sh in ((b, d), (rows, dh), (rows, 1),
                                  (rows, dh), (rows, 1))],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, dh), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rows, dh), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), x, wqkv_m, se_qkv, wo_m, se_o,
      wgu_m, se_gu, wd_m, se_d, g1m, g2m, km, ke, vm, ve, cossin)
    del t
    return tuple(res)


@partial(jax.jit, static_argnames=("n_d", "n_ff", "hq", "hkv", "dh", "p",
                                   "window", "eps_m", "eps_e", "se_g1",
                                   "se_g2"))
def decode_block_ref(x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu, wd_m, se_d,
                     g1m, g2m, km, ke, vm, ve, cossin, pos, *, n_d, n_ff, hq,
                     hkv, dh, p=7, window=0, eps_m=1, eps_e=-32, se_g1=0,
                     se_g2=0):
    """Bit-exact jnp mirror of :func:`fused_decode_block_pallas`."""
    dh_ = cossin.shape[-1] // 2
    return _decode_block_core(
        x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu, wd_m, se_d,
        g1m, jnp.int32(se_g1), g2m, jnp.int32(se_g2), km, ke, vm, ve,
        cossin[:, :dh_], cossin[:, dh_:], jnp.asarray(pos, jnp.int32),
        n_d=n_d, n_ff=n_ff, hq=hq, hkv=hkv, dh=dh, p=p, window=window,
        eps_m=eps_m, eps_e=eps_e)
