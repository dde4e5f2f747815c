"""Integer GEMM-shaped ops with integer forward AND integer backward.

Every op here is a ``jax.custom_vjp`` whose forward quantizes its float32
operands to BFP (linear fixed-point mapping), runs the contraction on
integer mantissas (int8 multiply -> int32 accumulate, exponents add — the
paper's Fig. 2 integer linear layer), and whose backward quantizes the
upstream gradient and computes *both* dW and dX as integer GEMMs — exactly
Appendix A.2 (``dW = X̂ᵀĜ``, ``dX = ĜŴᵀ``).  Residuals hold int8 mantissas
(+ a scalar scale), not float activations: the 4x activation-memory saving
of the integer pipeline is real in this implementation.

All contractions are arranged *contraction-last*, quantized (per-tensor
scale = paper-faithful; per-block scale along the contraction axis =
TPU-adapted variant), and contracted with ``preferred_element_type=int32``.
Contractions longer than ``policy.accum_chunk`` are split so worst-case
int8 x int8 sums can never overflow the int32 accumulator (hardware
accumulator flush).

Execution routing: every contraction asks ``kernels.dispatch`` for a path —
the fused Pallas quantize->GEMM pipeline (default on TPU), the unfused
two-kernel pipeline, or the jnp emulation below (the bit-exact oracle and
the default off-TPU).  ``policy.kernel_mode`` overrides the choice; see
docs/KERNELS.md.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import dispatch as kdispatch
from ..kernels import fused_attention as kfattn
from .bfp import (BFP, PER_TENSOR, QuantConfig, bfp_value, biased_exponent,
                  dequantize, pow2, quantize, quantize_cache, quantize_weight,
                  rounding_bits, scale_exponent)
from .policy import NumericPolicy

__all__ = ["qmatmul", "qbmm", "qembed", "qconv", "qcontract", "qrelu",
           "qattention", "qcache_attention",
           "qcache_quantize", "qcache_prefill", "qcache_append",
           "qcache_qk", "qcache_pv",
           "qmatmul_epi", "qnorm_gemm", "qdecode_block"]


# ---------------------------------------------------------------------------
# contraction-last integer contraction
# ---------------------------------------------------------------------------

def _chunk_count(k: int, chunk: int) -> int:
    """Number of accumulator chunks covering a contraction of length k.

    Always ``ceil(k / chunk)``: ``_pt_dot`` lets the last chunk run
    short, so no divisor search is needed.  (The previous
    ``while k % n: n += 1`` walk was O(k) for prime K and could silently
    shrink chunks to size 1 — e.g. k=509, chunk=128 used to yield 509
    chunks of one element.)
    """
    if chunk <= 0 or k <= chunk:
        return 1
    return -(-k // chunk)


def _pt_dot(am: jnp.ndarray, bm: jnp.ndarray, nbatch: int, nchunk: int) -> jnp.ndarray:
    """Integer dot, per-tensor scale: a (*B, M, K) x b (*B, N, K) -> (*B, M, N) int32->f32.

    ``nchunk`` > 1 splits K so each int32 accumulator only ever sums
    ceil(K/nchunk) int8 x int8 products; partials are combined in f32 in
    chunk order (emulating periodic accumulator flushes).  The last chunk
    may be shorter — the same partial sums as zero-padding K up to
    nchunk * ceil(K/nchunk), so the split is exact for any K, including
    primes.  (One dot per chunk slice: the TPU compiler takes over a
    minute on the equivalent reshape into a chunk-batched dot.)
    """
    k = am.shape[-1]
    dims = (((am.ndim - 1,), (bm.ndim - 1,)),
            (tuple(range(nbatch)), tuple(range(nbatch))))
    kc = -(-k // nchunk)
    acc = None
    for lo in range(0, k, kc):
        part = lax.dot_general(am[..., lo:lo + kc], bm[..., lo:lo + kc], dims,
                               preferred_element_type=jnp.int32)
        part = part.astype(jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def _blk_dot(aq: BFP, bq: BFP, nbatch: int) -> jnp.ndarray:
    """Integer dot with per-block scales along the (last) contraction axis.

    Partial int32 products per block are combined in f32 with their block
    scales — the MX-style contraction (in the Pallas kernel these partials
    live in VMEM/registers; the jnp emulation materializes them).
    """
    blk = aq.cfg.block
    nb = aq.m.shape[-1] // blk
    a4 = jnp.moveaxis(aq.m.reshape(*aq.m.shape[:-1], nb, blk), -2, nbatch)
    b4 = jnp.moveaxis(bq.m.reshape(*bq.m.shape[:-1], nb, blk), -2, nbatch)
    acc = lax.dot_general(
        a4, b4,
        (((a4.ndim - 1,), (b4.ndim - 1,)),
         (tuple(range(nbatch + 1)), tuple(range(nbatch + 1)))),
        preferred_element_type=jnp.int32)
    # acc: (*B, nb, M, N); block scale exponents: aq.e (*B, M, nb), bq.e (*B, N, nb)
    ea = jnp.moveaxis(scale_exponent(aq.e, aq.cfg), -1, nbatch)[..., :, None]
    eb = jnp.moveaxis(scale_exponent(bq.e, bq.cfg), -1, nbatch)[..., None, :]
    return (acc.astype(jnp.float32) * pow2(ea + eb)).sum(axis=nbatch)


def _contract_q(aq: BFP, bq: BFP, nbatch: int, chunk: int) -> jnp.ndarray:
    """Contraction of two pre-quantized contraction-last BFP operands -> f32."""
    if aq.cfg.block == PER_TENSOR:
        nchunk = _chunk_count(aq.m.shape[-1], chunk)
        acc = _pt_dot(aq.m, bq.m, nbatch, nchunk)
        return acc * pow2(scale_exponent(aq.e, aq.cfg) + scale_exponent(bq.e, bq.cfg))
    return _blk_dot(aq, bq, nbatch)


def _cfg_for_dim(cfg: QuantConfig, dim: int) -> QuantConfig:
    """Per-block scale needs the contraction dim divisible by the block;
    otherwise fall back to the per-tensor (paper-faithful) scale."""
    if cfg.block and dim % cfg.block != 0:
        return QuantConfig(cfg.bits, PER_TENSOR, cfg.stochastic, cfg.rng)
    return cfg


def qcontract(a: jnp.ndarray, b: jnp.ndarray, nbatch: int, cfg: QuantConfig,
              key: jax.Array, chunk: int = 65536) -> jnp.ndarray:
    """Quantize-and-contract: a (*B, M, K), b (*B, N, K) -> f32 (*B, M, N)."""
    ka, kb = jax.random.split(key)
    return _contract_q(quantize(a, cfg, ka), quantize(b, cfg, kb), nbatch, chunk)


def _t(m: jnp.ndarray) -> jnp.ndarray:
    """Swap the last two axes."""
    return jnp.swapaxes(m, -1, -2)


def _tq(q: BFP) -> BFP:
    """Transpose the last two axes of a per-tensor-scale BFP tensor."""
    assert q.cfg.block == PER_TENSOR
    return BFP(_t(q.m), q.e, q.cfg)


def _requant_t(q: BFP, cfg: QuantConfig, key: jax.Array) -> BFP:
    """Dequantize + requantize the transpose (per-block residual reuse path).

    Per-block scales live along the contraction axis, so reusing a stored
    operand in a *different* contraction requires re-blocking; composing two
    unbiased mappings stays unbiased (E{SR(SR(x))} = x).
    """
    from .bfp import dequantize
    return quantize(_t(dequantize(q)), cfg, key)


# ---------------------------------------------------------------------------
# qmatmul: x (..., K) @ w (K, N)   [the paper's Fig. 2 linear layer]
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qmatmul(x, w, key, policy: NumericPolicy):
    y, _ = _qmatmul_fwd(x, w, key, policy)
    return y


def _plan(op: str, m: int, k: int, n: int, cfg: QuantConfig,
          policy: NumericPolicy, kind: str = "qq",
          cfg2: Optional[QuantConfig] = None) -> "kdispatch.Decision":
    """Trace-time routing query for one contraction (see kernels.dispatch)."""
    return kdispatch.plan_contract(
        op, m, k, n, cfg, kind=kind, cfg2=cfg2,
        kernel_mode=policy.kernel_mode, accum_chunk=policy.accum_chunk,
        autotune_measure=policy.kernel_autotune)


def _qmatmul_fwd(x, w, key, policy: NumericPolicy):
    cfg = _cfg_for_dim(policy.fwd_cfg(), x.shape[-1])
    kx, kw, kb = jax.random.split(key, 3)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])                      # (M, K)
    plan = _plan("qmatmul_fwd", x2.shape[0], x2.shape[1], w.shape[-1],
                 cfg, policy)
    if plan.path == kdispatch.JNP:
        xq = quantize(x2, cfg, kx)                       # blocks along K
        wq = quantize_weight(_t(w), cfg, kw)             # (N, K), blocks along K
        y = _contract_q(xq, wq, 0, policy.accum_chunk)   # (M, N)
    else:
        y, xq, wq = kdispatch.contract_qq(x2, _t(w), cfg, kx, kw, plan)
    return y.reshape(*lead, w.shape[-1]), (xq, wq, kb, lead)


def _qmatmul_bwd(policy: NumericPolicy, res, gy):
    xq, wq, kb, lead = res
    cfg_b = policy.bwd_cfg()
    kg, kg2, kx2, kw2 = jax.random.split(kb, 4)
    g2 = gy.reshape(-1, gy.shape[-1])                    # (M, N)
    m, n = g2.shape
    k = xq.m.shape[-1]
    if policy.block == PER_TENSOR:
        # dX = G Wᵀ : contract N -> a=(M,N) g, b=(K,N) w
        plan_dx = _plan("qmatmul_dx", m, n, k, cfg_b, policy, kind="qi",
                        cfg2=wq.cfg)
        if plan_dx.path == kdispatch.JNP:
            gqN = quantize(g2, cfg_b, kg)                # scale once
            dx = _contract_q(gqN, _tq(wq), 0, policy.accum_chunk)      # (M, K)
        else:
            dx, gqN = kdispatch.contract_qi(g2, _tq(wq), cfg_b, kg, plan_dx)
        # dW = Xᵀ G : contract M -> a=(K,M), b=(N,M); gqM shares gqN's
        # mantissas (one quantization of the upstream gradient).
        gqM = _tq(gqN)                                   # (N, M) same mantissas
        plan_dw = _plan("qmatmul_dw", k, m, n, gqM.cfg, policy, kind="ii",
                        cfg2=xq.cfg)
        if plan_dw.path == kdispatch.JNP:
            dw = _contract_q(_tq(xq), gqM, 0, policy.accum_chunk)      # (K, N)
        else:
            dw = kdispatch.contract_ii(_tq(xq), gqM, plan_dw)
    else:
        # per-block: each contraction needs blocks along its own axis, so
        # the stored residual is dequantized and requantized along the new
        # contraction (composing two unbiased mappings stays unbiased); the
        # fused qq kernel performs that requantization in VMEM.
        cfg_n = _cfg_for_dim(cfg_b, g2.shape[-1])
        cfg_m = _cfg_for_dim(cfg_b, g2.shape[0])
        plan_dx = _plan("qmatmul_dx", m, n, k, cfg_n, policy)
        if plan_dx.path == kdispatch.JNP:
            gqN = quantize(g2, cfg_n, kg)                # blocks along N
            dx = _contract_q(gqN, _requant_t(wq, cfg_n, kw2), 0,
                             policy.accum_chunk)
        else:
            dx, _, _ = kdispatch.contract_qq(g2, _t(dequantize(wq)), cfg_n,
                                             kg, kw2, plan_dx,
                                             want_residuals=False)
        plan_dw = _plan("qmatmul_dw", k, m, n, cfg_m, policy)
        if plan_dw.path == kdispatch.JNP:
            gqM = quantize(_t(g2), cfg_m, kg2)           # blocks along M
            dw = _contract_q(_requant_t(xq, cfg_m, kx2), gqM, 0,
                             policy.accum_chunk)
        else:
            dw, _, _ = kdispatch.contract_qq(_t(dequantize(xq)), _t(g2),
                                             cfg_m, kx2, kg2, plan_dw,
                                             want_residuals=False)
    return dx.reshape(*lead, dx.shape[-1]), dw, None


_qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


# ---------------------------------------------------------------------------
# q-in / q-out (qflow): BFP operands in, BFP outputs out (docs/DATAFLOW.md)
#
# Integer pytree leaves have float0 tangents, so a BFP-valued edge between
# two ops would sever reverse-mode autodiff. The flex variants below route
# gradients through the BFP's float32 carrier ``g`` instead: the custom_vjp
# takes (m, e, g) as separate arguments, computes on the mantissas, ignores
# ``g`` in the forward (XLA dead-code-eliminates its producer), and returns
# the A.2 input gradient as the cotangent of ``g``.  Cotangents for the
# integer mantissa/exponent arguments are None (zero).
# ---------------------------------------------------------------------------


def _wcfg_for(xcfg: QuantConfig, policy: NumericPolicy) -> QuantConfig:
    """Fresh-operand quantization config matching a pre-quantized operand's
    blocking (mixed blockings cannot share one integer contraction)."""
    return QuantConfig(policy.fwd_bits, xcfg.block, policy.stochastic,
                       policy.rng)


def _flat2d(m: jnp.ndarray, e: jnp.ndarray, cfg: QuantConfig) -> BFP:
    """Flatten the leading dims of contraction-last (m, e) into a 2-D BFP."""
    m2 = m.reshape(-1, m.shape[-1])
    e2 = e if cfg.block == PER_TENSOR else e.reshape(-1, e.shape[-1])
    return BFP(m2, e2, cfg)


def _quantize_out(y: jnp.ndarray, n: int, policy: NumericPolicy,
                  kq: jax.Array):
    """The q-out epilogue: quantize the f32 accumulator output once (the
    quantize the consumer would otherwise perform) and emit (m, e, carrier)."""
    ocfg = _cfg_for_dim(policy.fwd_cfg(), n)
    yq = quantize(y, ocfg, kq)
    return yq.m, yq.e, dequantize(yq)


def _out_cfg(policy: NumericPolicy, n: int) -> QuantConfig:
    return _cfg_for_dim(policy.fwd_cfg(), n)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _qmatmul_flex(x, xe, xg, w, key, policy: NumericPolicy,
                  xcfg: Optional[QuantConfig], out_q: bool):
    y, _ = _qmatmul_flex_fwd(x, xe, xg, w, key, policy, xcfg, out_q)
    return y


def _qmatmul_flex_fwd(x, xe, xg, w, key, policy: NumericPolicy,
                      xcfg: Optional[QuantConfig], out_q: bool):
    # Same (kx, kw, kb) split as the plain path, so out_q only *adds* the
    # output quantization (drawn from a separately folded key): the
    # contraction mantissas stay bit-identical with out_q on or off.
    kx, kw, kb = jax.random.split(key, 3)
    kq = jax.random.fold_in(key, 0xD0)
    lead = x.shape[:-1]
    k, n = x.shape[-1], w.shape[-1]
    x2 = x.reshape(-1, k)
    if xcfg is None:
        cfg = _cfg_for_dim(policy.fwd_cfg(), k)
        plan = _plan("qmatmul_fwd", x2.shape[0], k, n, cfg, policy)
        if plan.path == kdispatch.JNP:
            xq = quantize(x2, cfg, kx)
            wq = quantize_weight(_t(w), cfg, kw)
            y = _contract_q(xq, wq, 0, policy.accum_chunk)
        else:
            y, xq, wq = kdispatch.contract_qq(x2, _t(w), cfg, kx, kw, plan)
    else:
        xq = _flat2d(x, xe, xcfg)
        wcfg = _wcfg_for(xcfg, policy)
        plan = _plan("qmatmul_fwd", x2.shape[0], k, n, wcfg, policy,
                     kind="iq", cfg2=xcfg)
        if plan.path == kdispatch.JNP:
            wq = quantize_weight(_t(w), wcfg, kw)
            y = _contract_q(xq, wq, 0, policy.accum_chunk)
        else:
            y, wq = kdispatch.contract_iq(xq, _t(w), wcfg, kw, plan)
    y = y.reshape(*lead, n)
    res = (xq, wq, kb, lead)
    if not out_q:
        return y, res
    return _quantize_out(y, n, policy, kq), res


def _qmatmul_flex_bwd(policy: NumericPolicy, xcfg: Optional[QuantConfig],
                      out_q: bool, res, cts):
    gy = cts[2] if out_q else cts        # q-out: ct arrives on the carrier
    dx, dw, _ = _qmatmul_bwd(policy, res, gy)
    if xcfg is None:
        return dx, None, None, dw, None
    return None, None, dx, dw, None      # BFP input: ct rides its carrier


_qmatmul_flex.defvjp(_qmatmul_flex_fwd, _qmatmul_flex_bwd)


# ---------------------------------------------------------------------------
# persistent-weight variant: w arrives as pre-quantized BFP mantissas (a
# forward weight derived from the int16 masters, or a load-time-quantized
# serving weight — docs/DATAFLOW.md §Weight currency).  No weight quantize
# runs in-op; dW is returned as the cotangent of the weight's float32
# carrier ``wg`` (the same carrier contract as q-in activations).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _qmatmul_pw(x, xe, xg, wm, we, wg, key, policy: NumericPolicy,
                xcfg: Optional[QuantConfig], wcfg: QuantConfig, out_q: bool):
    y, _ = _qmatmul_pw_fwd(x, xe, xg, wm, we, wg, key, policy, xcfg, wcfg,
                           out_q)
    return y


def _qmatmul_pw_fwd(x, xe, xg, wm, we, wg, key, policy: NumericPolicy,
                    xcfg: Optional[QuantConfig], wcfg: QuantConfig,
                    out_q: bool):
    # (kx, kw, kb) keeps the plain path's split discipline; kw is never
    # consumed (the weight is already on its int8 grid).
    kx, kw, kb = jax.random.split(key, 3)
    del kw
    kq = jax.random.fold_in(key, 0xD0)
    lead = x.shape[:-1]
    k, n = x.shape[-1], wm.shape[-1]
    x2 = x.reshape(-1, k)
    wq = BFP(_t(wm), we, wcfg)                           # (N, K), per-tensor
    if xcfg is None:
        cfg = _wcfg_for(wcfg, policy)
        plan = _plan("qmatmul_fwd", x2.shape[0], k, n, cfg, policy,
                     kind="qi", cfg2=wcfg)
        if plan.path == kdispatch.JNP:
            xq = quantize(x2, cfg, kx)
            y = _contract_q(xq, wq, 0, policy.accum_chunk)
        else:
            y, xq = kdispatch.contract_qi(x2, wq, cfg, kx, plan)
    else:
        xq = _flat2d(x, xe, xcfg)
        plan = _plan("qmatmul_fwd", x2.shape[0], k, n, xcfg, policy,
                     kind="pp", cfg2=wcfg)
        if plan.path == kdispatch.JNP:
            y = _contract_q(xq, wq, 0, policy.accum_chunk)
        else:
            y = kdispatch.contract_pp(xq, wq, plan)
    y = y.reshape(*lead, n)
    res = (xq, wq, kb, lead)
    if not out_q:
        return y, res
    return _quantize_out(y, n, policy, kq), res


def _qmatmul_pw_bwd(policy: NumericPolicy, xcfg: Optional[QuantConfig],
                    wcfg: QuantConfig, out_q: bool, res, cts):
    gy = cts[2] if out_q else cts
    dx, dw, _ = _qmatmul_bwd(policy, res, gy)
    cts_x = (dx, None, None) if xcfg is None else (None, None, dx)
    return (*cts_x, None, None, dw, None)    # dW rides the weight carrier


_qmatmul_pw.defvjp(_qmatmul_pw_fwd, _qmatmul_pw_bwd)


def qmatmul(x, w, key: Optional[jax.Array] = None,
            policy: NumericPolicy = NumericPolicy(), *,
            out_q: bool = False):
    """Quantized linear contraction x(..., K) @ w(K, N).

    ``x`` may be float32 or a pre-quantized ``BFP`` (blocked along K by
    construction): a BFP input skips the in-op activation quantization —
    the quantize-once rule of the qflow dataflow.  ``w`` may likewise be a
    per-tensor ``BFP`` (a forward weight derived from the integer masters,
    or a load-time-quantized serving weight): no weight quantize runs in
    the op, and the contraction is fully pre-quantized (dispatch kind
    ``pp``) when the activation is BFP too.  ``out_q=True`` returns a
    ``BFP`` (with gradient carrier) instead of float32; gradients follow
    the paper's A.2 integer contractions in every combination.  With the
    policy disabled, BFP inputs fall back to their float32 view.
    """
    if not policy.enabled:
        return bfp_value(x) @ bfp_value(w)
    if key is None:
        raise ValueError("qmatmul with an enabled integer policy needs a PRNG key")
    if isinstance(x, BFP) and x.cfg.block != PER_TENSOR \
            and policy.block == PER_TENSOR:
        # backward residual handling follows the *policy* blocking; a
        # per-block input under a per-tensor policy has no residual path
        x = bfp_value(x)
    if isinstance(w, BFP) and (w.cfg.block != PER_TENSOR
                               or policy.block != PER_TENSOR):
        # persistent weights carry per-tensor scales; per-block policies
        # re-quantize along their own blocking (residuals follow policy)
        w = bfp_value(w)
    if isinstance(w, BFP):
        if isinstance(x, BFP):
            out = _qmatmul_pw(x.m, x.e, x.g, w.m, w.e, w.g, key, policy,
                              x.cfg, w.cfg, out_q)
        else:
            out = _qmatmul_pw(x, None, None, w.m, w.e, w.g, key, policy,
                              None, w.cfg, out_q)
    elif isinstance(x, BFP):
        out = _qmatmul_flex(x.m, x.e, x.g, w, key, policy, x.cfg, out_q)
    elif out_q:
        out = _qmatmul_flex(x, None, None, w, key, policy, None, True)
    else:
        return _qmatmul(x, w, key, policy)
    if out_q:
        m_, e_, g_ = out
        return BFP(m_, e_, _out_cfg(policy, w.shape[-1]), g_)
    return out


# ---------------------------------------------------------------------------
# qbmm: batched matmul a (*B, M, K) @ b (*B, K, N)  [attention, MoE experts]
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qbmm(a, b, key, policy: NumericPolicy):
    y, _ = _qbmm_fwd(a, b, key, policy)
    return y


def _qbmm_fwd(a, b, key, policy: NumericPolicy):
    cfg = _cfg_for_dim(policy.fwd_cfg(), a.shape[-1])
    ka, kb_, kres = jax.random.split(key, 3)
    nbatch = a.ndim - 2
    plan = _plan("qbmm_fwd", a.shape[-2], a.shape[-1], b.shape[-1],
                 cfg, policy)
    if plan.path == kdispatch.JNP:
        aq = quantize(a, cfg, ka)                        # (*B, M, K) blocks on K
        bq = quantize(_t(b), cfg, kb_)                   # (*B, N, K) blocks on K
        y = _contract_q(aq, bq, nbatch, policy.accum_chunk)  # (*B, M, N)
    else:
        y, aq, bq = kdispatch.contract_qq(a, _t(b), cfg, ka, kb_, plan,
                                          nbatch=nbatch)
    return y, (aq, bq, kres)


def _qbmm_bwd(policy: NumericPolicy, res, gy):
    aq, bq, kres = res
    cfg_b = policy.bwd_cfg()
    kg, kg2, ka2, kb2 = jax.random.split(kres, 4)
    nbatch = gy.ndim - 2
    m, n = gy.shape[-2], gy.shape[-1]
    k = aq.m.shape[-1]
    if policy.block == PER_TENSOR:
        # da = G Bᵀ: contract N; bq stored (*B, N, K) -> needs (*B, K, N).
        plan_da = _plan("qbmm_dx", m, n, k, cfg_b, policy, kind="qi",
                        cfg2=bq.cfg)
        if plan_da.path == kdispatch.JNP:
            gq = quantize(gy, cfg_b, kg)                 # (*B, M, N)
            da = _contract_q(gq, _tq(bq), nbatch, policy.accum_chunk)
        else:
            da, gq = kdispatch.contract_qi(gy, _tq(bq), cfg_b, kg, plan_da,
                                           nbatch=nbatch)
        plan_db = _plan("qbmm_dw", k, m, n, gq.cfg, policy, kind="ii",
                        cfg2=aq.cfg)
        if plan_db.path == kdispatch.JNP:
            db = _contract_q(_tq(aq), _tq(gq), nbatch, policy.accum_chunk)
        else:
            db = kdispatch.contract_ii(_tq(aq), _tq(gq), plan_db,
                                       nbatch=nbatch)
    else:
        cfg_n = _cfg_for_dim(cfg_b, gy.shape[-1])
        cfg_m = _cfg_for_dim(cfg_b, gy.shape[-2])
        plan_da = _plan("qbmm_dx", m, n, k, cfg_n, policy)
        if plan_da.path == kdispatch.JNP:
            gqN = quantize(gy, cfg_n, kg)
            # bq is (*B, N, K) blocked on K; da needs (*B, K, N) blocked on N.
            da = _contract_q(gqN, _requant_t(bq, cfg_n, kb2), nbatch,
                             policy.accum_chunk)
        else:
            da, _, _ = kdispatch.contract_qq(gy, _t(dequantize(bq)), cfg_n,
                                             kg, kb2, plan_da, nbatch=nbatch,
                                             want_residuals=False)
        plan_db = _plan("qbmm_dw", k, m, n, cfg_m, policy)
        if plan_db.path == kdispatch.JNP:
            gqM = quantize(_t(gy), cfg_m, kg2)
            db = _contract_q(_requant_t(aq, cfg_m, ka2), gqM, nbatch,
                             policy.accum_chunk)
        else:
            db, _, _ = kdispatch.contract_qq(_t(dequantize(aq)), _t(gy),
                                             cfg_m, ka2, kg2, plan_db,
                                             nbatch=nbatch,
                                             want_residuals=False)
    return da, db, None


_qbmm.defvjp(_qbmm_fwd, _qbmm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _qbmm_flex(a, ae, ag, b, be, bg, key, policy: NumericPolicy,
               acfg: Optional[QuantConfig], bcfg: Optional[QuantConfig]):
    y, _ = _qbmm_flex_fwd(a, ae, ag, b, be, bg, key, policy, acfg, bcfg)
    return y


def _qbmm_flex_fwd(a, ae, ag, b, be, bg, key, policy: NumericPolicy,
                   acfg: Optional[QuantConfig], bcfg: Optional[QuantConfig]):
    """a (*B, M, K) and b (*B, K, N), each f32 or pre-quantized mantissas.

    Pre-quantized ``b`` must carry a per-tensor scale (the transpose into
    contraction-last layout is then pure int8 data movement); the public
    wrapper enforces this.
    """
    ka, kb_, kres = jax.random.split(key, 3)
    nbatch = a.ndim - 2
    m, k, n = a.shape[-2], a.shape[-1], b.shape[-1]
    if acfg is not None and bcfg is not None:
        # fully pre-quantized forward (q-in activation x persistent weight,
        # or two q-in activations): dispatch kind "pp" — no quantize stage.
        aq = BFP(a, ae, acfg)
        bq = _tq(BFP(b, be, bcfg))
        plan = _plan("qbmm_fwd", m, k, n, acfg, policy, kind="pp", cfg2=bcfg)
        if plan.path == kdispatch.JNP:
            y = _contract_q(aq, bq, nbatch, policy.accum_chunk)
        else:
            y = kdispatch.contract_pp(aq, bq, plan, nbatch=nbatch)
    elif acfg is not None:
        aq = BFP(a, ae, acfg)
        bcfg_f = _wcfg_for(acfg, policy)
        plan = _plan("qbmm_fwd", m, k, n, bcfg_f, policy, kind="iq", cfg2=acfg)
        if plan.path == kdispatch.JNP:
            bq = quantize(_t(b), bcfg_f, kb_)
            y = _contract_q(aq, bq, nbatch, policy.accum_chunk)
        else:
            y, bq = kdispatch.contract_iq(aq, _t(b), bcfg_f, kb_, plan,
                                          nbatch=nbatch)
    else:
        bq = _tq(BFP(b, be, bcfg))
        acfg_f = _wcfg_for(bcfg, policy)
        plan = _plan("qbmm_fwd", m, k, n, acfg_f, policy, kind="qi", cfg2=bcfg)
        if plan.path == kdispatch.JNP:
            aq = quantize(a, acfg_f, ka)
            y = _contract_q(aq, bq, nbatch, policy.accum_chunk)
        else:
            y, aq = kdispatch.contract_qi(a, bq, acfg_f, ka, plan,
                                          nbatch=nbatch)
    return y, (aq, bq, kres)


def _qbmm_flex_bwd(policy: NumericPolicy, acfg: Optional[QuantConfig],
                   bcfg: Optional[QuantConfig], res, gy):
    da, db, _ = _qbmm_bwd(policy, res, gy)
    cts_a = (da, None, None) if acfg is None else (None, None, da)
    cts_b = (db, None, None) if bcfg is None else (None, None, db)
    return (*cts_a, *cts_b, None)


_qbmm_flex.defvjp(_qbmm_flex_fwd, _qbmm_flex_bwd)


def qbmm(a, b, key: Optional[jax.Array] = None,
         policy: NumericPolicy = NumericPolicy()) -> jnp.ndarray:
    """Quantized batched matmul a(*B, M, K) @ b(*B, K, N) with integer bwd.

    Either operand may be a pre-quantized ``BFP`` (q-in: the quantize-once
    rule). A pre-quantized ``b`` needs a per-tensor scale and a pre-
    quantized pair needs matching blockings; unsupported combinations fall
    back to the operand's float32 view (gradient-preserving).
    """
    if not policy.enabled:
        return bfp_value(a) @ bfp_value(b)
    if key is None:
        raise ValueError("qbmm with an enabled integer policy needs a PRNG key")
    a_q, b_q = isinstance(a, BFP), isinstance(b, BFP)
    if a_q and a.cfg.block != PER_TENSOR and policy.block == PER_TENSOR:
        a, a_q = bfp_value(a), False     # see qmatmul: residuals follow policy
    if b_q and b.cfg.block != PER_TENSOR:
        b, b_q = bfp_value(b), False
    if b_q and a_q and a.cfg.block != PER_TENSOR:
        b, b_q = bfp_value(b), False     # mixed blocking: keep `a` integer
    if not (a_q or b_q):
        return _qbmm(a, b, key, policy)
    am, ae, ag, acfg = (a.m, a.e, a.g, a.cfg) if a_q else (a, None, None, None)
    bm, be, bg, bcfg = (b.m, b.e, b.g, b.cfg) if b_q else (b, None, None, None)
    return _qbmm_flex(am, ae, ag, bm, be, bg, key, policy, acfg, bcfg)


# ---------------------------------------------------------------------------
# qembed: integer embedding gather + integer scatter-add backward
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qembed(tokens, table, key, policy: NumericPolicy):
    y, _ = _qembed_fwd(tokens, table, key, policy)
    return y


def _qembed_fwd(tokens, table, key, policy: NumericPolicy):
    cfg = _cfg_for_dim(policy.fwd_cfg(), table.shape[-1])
    kt, kb = jax.random.split(key)
    tq = quantize_weight(table, cfg, kt)                 # (V, D), blocks along D
    rows = jnp.take(tq.m, tokens, axis=0)                # int8 gather
    scale = pow2(scale_exponent(tq.e, cfg))
    if cfg.block == PER_TENSOR:
        y = rows.astype(jnp.float32) * scale
    else:
        erows = jnp.take(scale, tokens, axis=0)          # (..., D/blk)
        y = (rows.reshape(*rows.shape[:-1], -1, cfg.block).astype(jnp.float32)
             * erows[..., None]).reshape(rows.shape)
    return y, (tokens, table.shape[0], kb)


def _qembed_bwd(policy: NumericPolicy, res, gy):
    tokens, vocab, kb = res
    cfg_b = policy.bwd_cfg()
    flat_tok = tokens.reshape(-1)
    g2 = gy.reshape(-1, gy.shape[-1])
    if policy.block == PER_TENSOR:
        gq = quantize(g2, QuantConfig(cfg_b.bits, PER_TENSOR, cfg_b.stochastic,
                                      cfg_b.rng), kb)
        # integer scatter-add: int8 mantissas accumulated in int32 rows
        acc = jax.ops.segment_sum(gq.m.astype(jnp.int32), flat_tok, num_segments=vocab)
        dtable = acc.astype(jnp.float32) * pow2(scale_exponent(gq.e, gq.cfg))
    else:
        # per-block scales differ per row: scatter in float (documented).
        dtable = jax.ops.segment_sum(g2, flat_tok, num_segments=vocab)
    return None, dtable, None


_qembed.defvjp(_qembed_fwd, _qembed_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qembed_q(tokens, table, key, policy: NumericPolicy):
    y, _ = _qembed_q_fwd(tokens, table, key, policy)
    return y


def _qembed_q_fwd(tokens, table, key, policy: NumericPolicy):
    """q-out embedding: the int8 row gather IS the quantized activation."""
    cfg = _cfg_for_dim(policy.fwd_cfg(), table.shape[-1])
    kt, kb = jax.random.split(key)
    tq = quantize_weight(table, cfg, kt)
    rows = jnp.take(tq.m, tokens, axis=0)
    if cfg.block == PER_TENSOR:
        e = tq.e
    else:
        e = jnp.take(tq.e, tokens, axis=0)               # (..., D/blk)
    carrier = dequantize(BFP(rows, e, cfg))
    return (rows, e, carrier), (tokens, table.shape[0], kb)


def _qembed_q_bwd(policy: NumericPolicy, res, cts):
    _, dtable, _ = _qembed_bwd(policy, res, cts[2])
    return None, dtable, None


_qembed_q.defvjp(_qembed_q_fwd, _qembed_q_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _qembed_p(tokens, tm, te, tg, key, policy: NumericPolicy,
              tcfg: QuantConfig, out_q: bool):
    """Pre-quantized (persistent) table: the gather is pure int8 data
    movement — no quantize runs at all.  dTable rides the table carrier."""
    y, _ = _qembed_p_fwd(tokens, tm, te, tg, key, policy, tcfg, out_q)
    return y


def _qembed_p_fwd(tokens, tm, te, tg, key, policy: NumericPolicy,
                  tcfg: QuantConfig, out_q: bool):
    rows = jnp.take(tm, tokens, axis=0)                  # int8 gather
    if out_q:
        y = (rows, te, dequantize(BFP(rows, te, tcfg)))
    else:
        y = rows.astype(jnp.float32) * pow2(scale_exponent(te, tcfg))
    return y, (tokens, tm.shape[0], key)


def _qembed_p_bwd(policy: NumericPolicy, tcfg: QuantConfig, out_q: bool,
                  res, cts):
    gy = cts[2] if out_q else cts
    _, dtable, _ = _qembed_bwd(policy, res, gy)
    return None, None, None, dtable, None


_qembed_p.defvjp(_qembed_p_fwd, _qembed_p_bwd)


def qembed(tokens: jnp.ndarray, table, key: Optional[jax.Array] = None,
           policy: NumericPolicy = NumericPolicy(), *, out_q: bool = False):
    """Integer embedding lookup (int8 table) with integer scatter-add grads.

    ``table`` may be a per-tensor-scale ``BFP`` (a derived forward weight
    or a load-time-quantized serving table): the int8 row gather then runs
    with *no* table quantization and dTable rides the table's carrier.
    ``out_q=True`` returns the gathered rows as a ``BFP`` sharing the
    table's scale — the gather itself is the (single) quantization of the
    activation.
    """
    if not (policy.enabled and policy.quantize_embed):
        return jnp.take(bfp_value(table), tokens, axis=0)
    if key is None:
        raise ValueError("qembed with an enabled integer policy needs a PRNG key")
    if isinstance(table, BFP) and table.cfg.block != PER_TENSOR:
        table = bfp_value(table)     # per-block rows don't survive the gather
    if isinstance(table, BFP):
        out = _qembed_p(tokens, table.m, table.e, table.g, key, policy,
                        table.cfg, out_q)
        if not out_q:
            return out
        rows, e, g = out
        return BFP(rows, e, table.cfg, g)
    if not out_q:
        return _qembed(tokens, table, key, policy)
    rows, e, g = _qembed_q(tokens, table, key, policy)
    return BFP(rows, e, _out_cfg(policy, table.shape[-1]), g)


# ---------------------------------------------------------------------------
# qconv: NHWC conv as im2col patches + qmatmul (integer fwd + bwd GEMMs)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def qdq_st(x, key, cfg: QuantConfig):
    """Stochastic quantize-dequantize with a straight-through gradient.

    Used to pre-round a tensor that downstream integer ops will touch many
    times (e.g. Q across KV chunks): after one unbiased stochastic QDQ the
    values sit exactly on the int8 grid, so every later requantization at
    the same (per-tensor) scale is exact under *nearest* rounding — no
    further random bits are consumed (§Perf iteration: RNG deduplication).
    """
    from .bfp import dequantize
    return dequantize(quantize(x, cfg, key))


def _qdq_fwd(x, key, cfg):
    return qdq_st(x, key, cfg), None


def _qdq_bwd(cfg, res, g):
    return g, None


qdq_st.defvjp(_qdq_fwd, _qdq_bwd)


def _int_patches(m: jnp.ndarray, kh: int, kw: int,
                 stride: Tuple[int, int], padding: str) -> jnp.ndarray:
    """im2col on integer mantissas: (N, H, W, C) -> (N, Ho, Wo, C*kh*kw).

    Pure data movement (pad with zero mantissas + strided slices), emitting
    the same (cin, kh, kw)-major feature order as
    ``lax.conv_general_dilated_patches`` so weights reshape identically.
    """
    n, h, w_, c = m.shape
    sh, sw = stride
    if padding == "SAME":
        ho, wo = -(-h // sh), -(-w_ // sw)
        ph = max((ho - 1) * sh + kh - h, 0)
        pw = max((wo - 1) * sw + kw - w_, 0)
        pads = ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2))
    elif padding == "VALID":
        ho, wo = (h - kh) // sh + 1, (w_ - kw) // sw + 1
        pads = ((0, 0), (0, 0))
    else:
        raise ValueError(f"unsupported padding {padding!r}")
    mp = jnp.pad(m, ((0, 0), pads[0], pads[1], (0, 0)))
    cols = []
    for dy in range(kh):
        for dx in range(kw):
            cols.append(mp[:, dy:dy + (ho - 1) * sh + 1:sh,
                           dx:dx + (wo - 1) * sw + 1:sw, :])
    pat = jnp.stack(cols, axis=-1)                       # (N,Ho,Wo,C,kh*kw)
    return pat.reshape(n, ho, wo, c * kh * kw)


def qconv(x, w: jnp.ndarray, key: Optional[jax.Array] = None,
          policy: NumericPolicy = NumericPolicy(), *,
          stride: Tuple[int, int] = (1, 1), padding: str = "SAME",
          out_q: bool = False):
    """2-D convolution, NHWC x HWIO -> NHWC, via integer GEMM.

    The im2col patch extraction / fold-back is pure data movement (gather /
    scatter-add of already-quantized values); every multiply of both the
    forward and backward pass happens inside the integer ``qmatmul``.

    ``x`` may be a per-tensor-scale ``BFP`` (q-in: patches are sliced from
    the int8 mantissas, no re-quantization) and ``out_q=True`` returns a
    ``BFP`` — together they keep the conv -> norm -> relu -> conv chain on
    integer activations (docs/DATAFLOW.md).  ``w`` may be a per-tensor
    ``BFP`` filter (persistent weight currency): the im2col weight
    reshuffle is pure mantissa data movement and the GEMM runs fully
    pre-quantized.
    """
    kh, kw_, cin, cout = w.shape
    if not policy.enabled:
        return lax.conv_general_dilated(
            bfp_value(x), bfp_value(w), stride, padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if isinstance(w, BFP) and w.cfg.block != PER_TENSOR:
        w = bfp_value(w)      # per-block filters don't survive the reshuffle
    if isinstance(x, BFP) and x.cfg.block != PER_TENSOR:
        x = bfp_value(x)      # per-block scales don't survive the reshuffle
    if isinstance(x, BFP):
        pm = _int_patches(x.m, kh, kw_, stride, padding)
        pg = None if x.g is None else lax.conv_general_dilated_patches(
            x.g, (kh, kw_), stride, padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        patches = BFP(pm, x.e, x.cfg, pg)
    else:
        patches = lax.conv_general_dilated_patches(
            x, (kh, kw_), stride, padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))  # (N, Ho, Wo, kh*kw*cin) [CIHW order]
    # conv_general_dilated_patches emits feature order (cin, kh, kw); match w.
    if isinstance(w, BFP):
        w2m = jnp.moveaxis(w.m, 2, 0).reshape(cin * kh * kw_, cout)
        w2g = None if w.g is None else \
            jnp.moveaxis(w.g, 2, 0).reshape(cin * kh * kw_, cout)
        w2 = BFP(w2m, w.e, w.cfg, w2g)
    else:
        w2 = jnp.moveaxis(w, 2, 0).reshape(cin * kh * kw_, cout)
    return qmatmul(patches, w2, key, policy, out_q=out_q)


# ---------------------------------------------------------------------------
# fused flash attention: QKᵀ→softmax→PV as ONE kernel launch per direction
# (kernels.fused_attention, planned by kernels.dispatch.plan_attention).
# Operands arrive as pre-quantized per-tensor BFPs (the qflow quantize-once
# rule); gradients ride the float32 carriers exactly like the q-in GEMM
# ops.  The custom_vjp saves only the operand mantissas and the two
# per-row softmax stats — NOT the O(GS·T) probability mantissas the scan
# path's per-chunk qbmm residuals store; the backward recomputes the
# probabilities from the stats inside its own kernel (A.2-style, every
# multiply an int8 GEMM).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(12, 13, 14, 15, 16))
def _qattn(qm, qe, qg, km, ke, kg, vm, ve, vg, q_off, kv_len, key,
           policy: NumericPolicy, s: int, causal: bool, window: int,
           plan: "kdispatch.Decision"):
    y, _ = _qattn_fwd(qm, qe, qg, km, ke, kg, vm, ve, vg, q_off, kv_len,
                      key, policy, s, causal, window, plan)
    return y


def _qattn_fwd(qm, qe, qg, km, ke, kg, vm, ve, vg, q_off, kv_len, key,
               policy: NumericPolicy, s: int, causal: bool, window: int,
               plan: "kdispatch.Decision"):
    lead = qm.shape[:-2]
    gs, d = qm.shape[-2], qm.shape[-1]
    t = km.shape[-2]
    cfg = policy.fwd_cfg()
    sr = cfg.stochastic
    q3 = qm.reshape(-1, gs, d)
    k3 = km.reshape(-1, t, d)
    v3 = vm.reshape(-1, t, d)
    rp = (rounding_bits(jax.random.fold_in(key, 0), (q3.shape[0], gs, t),
                        cfg.rng) if sr else None)
    y3, m3, l3 = kfattn.attn_fwd(
        q3, k3, v3, rp, qe, ke, ve, q_off, kv_len,
        p=cfg.p, s=s, bq=plan.bm, bt=plan.bt, causal=causal, window=window,
        stochastic=sr, interpret=plan.interpret,
        pallas=(plan.path == kdispatch.FUSED))
    y = y3.reshape(*lead, gs, d)
    res = (qm, qe, km, ke, vm, ve, m3, l3, y, q_off, kv_len,
           jax.random.fold_in(key, 1))
    return y, res


def _qattn_bwd(policy: NumericPolicy, s: int, causal: bool, window: int,
               plan: "kdispatch.Decision", res, gy):
    qm, qe, km, ke, vm, ve, m3, l3, y, q_off, kv_len, kb = res
    lead = qm.shape[:-2]
    gs, d = qm.shape[-2], qm.shape[-1]
    t = km.shape[-2]
    # fused attention is a per-tensor op end to end: a per-block policy can
    # still reach it when _cfg_for_dim fell back to per-tensor on the
    # forward (block ∤ head_dim), so the backward's fresh quantizations
    # (dO, pn, dS) follow the op's blocking, not the policy's.
    cb = policy.bwd_cfg()
    cfg_b = QuantConfig(cb.bits, PER_TENSOR, cb.stochastic, cb.rng)
    kg, krs, krp = jax.random.split(kb, 3)
    g3 = gy.reshape(-1, gs, d)
    nbh = g3.shape[0]
    # ONE fresh quantization of the upstream gradient (per-tensor, like the
    # qbmm backward); probabilities are recomputed from (m, l) in-kernel.
    gq = quantize(g3, cfg_b, kg)
    delta = (gy * y).sum(-1, keepdims=True).reshape(-1, gs, 1)
    plan_b = kdispatch.plan_attention(
        "attn_bwd", gs, t, d, cfg_b, s=s, kind="ii",
        kernel_mode=policy.kernel_mode,
        autotune_measure=policy.kernel_autotune)
    sr = cfg_b.stochastic
    rs = rounding_bits(krs, (nbh, gs, t), cfg_b.rng) if sr else None
    rp2 = rounding_bits(krp, (nbh, gs, t), cfg_b.rng) if sr else None
    dq3, dk3, dv3 = kfattn.attn_bwd(
        qm.reshape(-1, gs, d), gq.m, km.reshape(-1, t, d),
        vm.reshape(-1, t, d), m3, l3, delta, rs, rp2,
        qe, ke, ve, gq.e, q_off, kv_len,
        p=cfg_b.p, s=s, bt=plan_b.bt or kdispatch.attn_block_t(t),
        causal=causal, window=window, stochastic=sr,
        interpret=plan_b.interpret,
        pallas=(plan_b.path == kdispatch.FUSED))
    dq = dq3.reshape(*lead, gs, d)
    dk = dk3.reshape(*lead, t, d)
    dv = dv3.reshape(*lead, t, d)
    # gradients ride the float32 carriers (qg, kg, vg): the straight-
    # through contract of every q-in op (docs/DATAFLOW.md).
    return (None, None, dq, None, None, dk, None, None, dv, None, None,
            None)


_qattn.defvjp(_qattn_fwd, _qattn_bwd)


def qattention(qb: BFP, kb: BFP, vb: BFP, q_off, kv_len,
               key: jax.Array, policy: NumericPolicy, *, s: int,
               causal: bool, window: int,
               plan: "kdispatch.Decision") -> jnp.ndarray:
    """Fused integer flash attention over pre-quantized per-tensor BFPs.

    qb (*B, GS, D) is the grouped, pre-scaled query (quantized once —
    g-major GQA layout with per-group length ``s``); kb/vb (*B, T, D) the
    quantized K/V.  ``plan`` comes from ``kernels.dispatch.plan_attention``
    (the caller only routes here when it chose the fused path).  Returns
    f32 (*B, GS, D); dQ/dK/dV flow to the operands' float32 carriers.
    """
    assert qb.cfg.block == PER_TENSOR
    return _qattn(qb.m, qb.e, qb.g, kb.m, kb.e, kb.g, vb.m, vb.e, vb.g,
                  jnp.asarray(q_off, jnp.int32), jnp.asarray(kv_len, jnp.int32),
                  key, policy, s, causal, window, plan)


def qcache_attention(q, kq: BFP, vq: BFP, q_off, kv_len,
                     key: Optional[jax.Array], policy: NumericPolicy, *,
                     s: int, causal: bool, window: int,
                     plan: "kdispatch.Decision") -> jnp.ndarray:
    """Fused decode attention straight off int8 qcache rows (serving,
    gradient-free): QKᵀ, softmax, the V-row exponent fold, p's single
    quantization and PV run in ONE kernel — ``qcache_qk``/``qcache_pv``
    without the two separate GEMM dispatches or the score/probability HBM
    round-trip.  ``q`` is f32 (quantized per-tensor here, once) or an
    already-quantized per-tensor BFP (qflow); kq/vq carry one exponent
    per cache row.
    """
    lead = kq.m.shape[:-2]
    t, d = kq.m.shape[-2], kq.m.shape[-1]
    if isinstance(q, BFP):
        qq = q
    else:
        cfg_q = QuantConfig(policy.fwd_bits, PER_TENSOR, policy.stochastic,
                            policy.rng)
        qq = quantize(lax.stop_gradient(q),
                      cfg_q, None if key is None else
                      jax.random.fold_in(key, 0))
    gs = qq.m.shape[-2]
    q3 = qq.m.reshape(-1, gs, d)
    sr = policy.stochastic and key is not None
    rp = (rounding_bits(jax.random.fold_in(key, 1), (q3.shape[0], gs, t),
                        policy.rng) if sr else None)
    y3 = kfattn.attn_decode(
        q3, kq.m.reshape(-1, t, d), vq.m.reshape(-1, t, d),
        kq.e.reshape(-1, t, 1), vq.e.reshape(-1, t, 1), rp, qq.e,
        jnp.asarray(q_off, jnp.int32), jnp.asarray(kv_len, jnp.int32),
        p=policy.fwd_bits - 1, s=s, causal=causal, window=window,
        stochastic=sr, interpret=plan.interpret,
        pallas=(plan.path == kdispatch.FUSED))
    return y3.reshape(*lead, gs, d)


# ---------------------------------------------------------------------------
# qcache: quantized KV/state caches as the decode-time currency
# (docs/SERVING.md).  The cache layout is int8 (or master-width) mantissas
# plus ONE shared exponent per cache row (the trailing hd / d_model chunk):
# per-row scales are what make the append contract exact — quantizing a
# whole prefill tensor and quantizing its rows one decode-append at a time
# produce bit-identical mantissas, because each row's mapping depends only
# on that row (nearest rounding, no cross-row shared state).  These are
# serving ops: gradient-free by construction (stop_gradient on the float
# input; decode is never differentiated).
# ---------------------------------------------------------------------------


def qcache_quantize(x: jnp.ndarray, policy: NumericPolicy,
                    cfg: Optional[QuantConfig] = None) -> BFP:
    """Append-time cache quantization: one shared exponent per trailing-axis
    row, nearest rounding (deterministic, key-free).  ``cfg`` overrides the
    policy-derived cache config (used to widen accumulator states to
    ``policy.master_bits``)."""
    cfg = cfg or policy.cache_cfg(x.shape[-1])
    return quantize_cache(lax.stop_gradient(x), cfg)


def qcache_prefill(x: jnp.ndarray, pad: int, policy: NumericPolicy) -> BFP:
    """Quantize prefill cache rows once and zero-pad the time (row) axis
    out to the cache length: zero mantissas + exponent 1 are exactly
    representable, invisible under the decode mask, and bit-identical to
    what a later :func:`qcache_append` writes over them."""
    q = qcache_quantize(x, policy)
    if pad:
        widths = [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)]
        return BFP(jnp.pad(q.m, widths),
                   jnp.pad(q.e, widths, constant_values=1), q.cfg)
    return q


def qcache_append(cache: BFP, x: jnp.ndarray, pos, axis: int) -> BFP:
    """Quantize one fresh float row-block ``x`` and write it into the cache
    at ``pos`` along ``axis`` (the decode-time append).  Mantissas and the
    row exponents update together; nothing already stored is touched, so
    the append is bit-identical to having quantized the row during
    prefill."""
    row = quantize_cache(lax.stop_gradient(x), cache.cfg)
    m = lax.dynamic_update_slice_in_dim(cache.m, row.m, pos, axis)
    e = lax.dynamic_update_slice_in_dim(cache.e, row.e, pos, axis)
    return BFP(m, e, cache.cfg)


def _unit_view(m: jnp.ndarray, bits: int, rng: str) -> BFP:
    """Per-tensor BFP view of raw mantissas under a UNIT reference scale
    (biased exponent chosen so scale_exponent == 0): lets the pre-quantized
    cache mantissas enter the existing per-tensor integer contractions
    (dispatch kinds "qi"/"pp") while the true per-row cache exponents are
    applied as a float epilogue outside the GEMM."""
    ucfg = QuantConfig(bits, PER_TENSOR, False, rng)
    e = biased_exponent(jnp.zeros((), jnp.int32), ucfg).astype(jnp.int32)
    return BFP(m, e, ucfg)


def _row_scales(q: BFP) -> jnp.ndarray:
    """(*B, 1, T) float scale of each cache row (exact powers of two)."""
    return jnp.swapaxes(pow2(scale_exponent(q.e, q.cfg)), -1, -2)


def qcache_qk(a, kq: BFP, key: Optional[jax.Array],
              policy: NumericPolicy) -> jnp.ndarray:
    """Decode scores against an int8 cache: a (*B, M, D) f32 | BFP versus
    cache mantissas kq.m (*B, T, D) with one exponent per row -> (*B, M, T).

    The integer GEMM contracts the raw mantissas under a unit reference
    scale — the cache operand pays one int8 read (dispatch kind "qi" for a
    fresh ``a``, "pp" for a pre-quantized one); the per-row cache exponents
    ride along the *output-column* axis, so they are applied afterwards as
    one exact f32 multiply per column: y[..., t] *= 2^{e_t}.
    """
    nbatch = kq.m.ndim - 2
    t, d = kq.m.shape[-2], kq.m.shape[-1]
    bq = _unit_view(kq.m, kq.cfg.bits, kq.cfg.rng)
    col_scale = _row_scales(kq)
    if isinstance(a, BFP) and a.cfg.block != PER_TENSOR:
        a = bfp_value(a)
    if isinstance(a, BFP):
        plan = _plan("qdecode_qk", a.m.shape[-2], d, t, a.cfg, policy,
                     kind="pp", cfg2=bq.cfg)
        aq = BFP(a.m, a.e, a.cfg)
        if plan.path == kdispatch.JNP:
            y = _contract_q(aq, bq, nbatch, policy.accum_chunk)
        else:
            y = kdispatch.contract_pp(aq, bq, plan, nbatch=nbatch)
    else:
        cfg = policy.fwd_cfg()
        plan = _plan("qdecode_qk", a.shape[-2], d, t, cfg, policy,
                     kind="qi", cfg2=bq.cfg)
        if plan.path == kdispatch.JNP:
            y = _contract_q(quantize(a, cfg, key), bq, nbatch,
                            policy.accum_chunk)
        else:
            y, _ = kdispatch.contract_qi(a, bq, cfg, key, plan, nbatch=nbatch)
    return y * col_scale


def qcache_pv(p: jnp.ndarray, vq: BFP, key: Optional[jax.Array],
              policy: NumericPolicy) -> jnp.ndarray:
    """Decode mix against an int8 cache: p (*B, M, T) float softmax weights
    versus cache mantissas vq.m (*B, T, D) with one exponent per row ->
    (*B, M, D).

    Here the per-row cache exponents ride along the CONTRACTION axis, so
    they cannot be factored out of the integer sum; instead they are folded
    into the float probabilities before p's own (single, fresh)
    quantization — p'_t = p_t * 2^{e_t}, an exact power-of-two product —
    and the GEMM contracts p̂' against the raw mantissas under a unit
    reference scale (dispatch kind "qi": the cache operand pays one int8
    read, no dequantize→requantize round-trip).
    """
    nbatch = vq.m.ndim - 2
    t, d = vq.m.shape[-2], vq.m.shape[-1]
    p2 = p * _row_scales(vq)
    bq = _unit_view(jnp.swapaxes(vq.m, -1, -2), vq.cfg.bits, vq.cfg.rng)
    cfg = policy.fwd_cfg()
    plan = _plan("qdecode_pv", p.shape[-2], t, d, cfg, policy,
                 kind="qi", cfg2=bq.cfg)
    if plan.path == kdispatch.JNP:
        return _contract_q(quantize(p2, cfg, key), bq, nbatch,
                           policy.accum_chunk)
    y, _ = kdispatch.contract_qi(p2, bq, cfg, key, plan, nbatch=nbatch)
    return y


def qrelu(x):
    """ReLU on ``f32 | BFP``. Exact on mantissas: relu(m * 2^E) = relu(m) * 2^E
    (the shared scale is positive), so no dequantize/requantize is needed;
    the gradient mask rides the float32 carrier (g > 0 iff m > 0)."""
    if isinstance(x, BFP):
        g = None if x.g is None else jax.nn.relu(x.g)
        return BFP(jnp.maximum(x.m, 0), x.e, x.cfg, g)
    return jax.nn.relu(x)


# ---------------------------------------------------------------------------
# cross-op fused chains (core.qchain) — re-exported lazily so qops stays the
# canonical ops namespace without a circular import (qchain builds its
# backward passes out of this module's integer contraction helpers).
# ---------------------------------------------------------------------------

_CHAIN_OPS = ("qmatmul_epi", "qnorm_gemm", "qdecode_block")


def __getattr__(name):
    if name in _CHAIN_OPS:
        from . import qchain
        return getattr(qchain, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
