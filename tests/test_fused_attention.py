"""Tests for the fused integer flash-attention path (ISSUE 5).

Covers:
  * ``dispatch.plan_attention`` routing rules and autotune key separation;
  * forward parity: Pallas kernel (interpret) bit-identical to its jnp
    mirror under jit, for causal / sliding-window / non-causal masks, GQA
    grouping and prime (padded) sequence lengths; close to the chunk-scan
    path numerically;
  * exact integer oracles for the in-kernel QKᵀ and PV contractions (via
    ``kernels.ref`` and the same rounding-bit stream);
  * backward parity: Pallas bwd bit-identical to its mirror; end-to-end
    gradients through ``chunked_attention`` close to the scan path's, with
    the carrier contract intact;
  * the fused qcache decode kernel vs its mirror and vs the scan decode;
  * the spec pin: with the fused path off (kernel_mode="auto" on CPU),
    every attention entry point is bit-identical to PR-4 HEAD (captured
    goldens in tests/goldens/attention_pr4.npz);
  * the analytic attention traffic model (fused strictly below scan).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BFP, PAPER_INT8, NumericPolicy, dequantize, pow2, quantize
from repro.core.bfp import QuantConfig, rounding_bits, scale_exponent
from repro.core.qops import qcache_quantize
from repro.kernels import dispatch, ref, tile
from repro.kernels import fused_attention as fa
from repro.models.attention import (cache_decode_attention, chunked_attention,
                                    decode_attention, local_attention)

KEY = jax.random.key(7)
QF = dataclasses.replace(PAPER_INT8, qflow=True)
QFF = dataclasses.replace(QF, kernel_mode="fused")
QC = dataclasses.replace(PAPER_INT8, qcache=True)
QCF = dataclasses.replace(QC, kernel_mode="fused")

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens",
                       "attention_pr4.npz")


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / (np.abs(b).max() + 1e-12)


# ---------------------------------------------------------------------------
# plan_attention routing
# ---------------------------------------------------------------------------

def _plan(**kw):
    args = dict(op="attn_fwd", gs=64, t=256, d=64, cfg=QuantConfig(8), s=64)
    args.update(kw)
    return dispatch.plan_attention(args.pop("op"), args.pop("gs"),
                                   args.pop("t"), args.pop("d"),
                                   args.pop("cfg"), **args)


def test_plan_auto_keeps_scan_on_cpu():
    assert _plan(kernel_mode="auto", backend="cpu").path == dispatch.JNP


def test_plan_auto_goes_fused_on_tpu():
    d = _plan(kernel_mode="auto", backend="tpu")
    assert d.path == dispatch.FUSED and d.bm > 0 and d.bt > 0
    assert not d.interpret


def test_plan_forced_fused_on_cpu_uses_interpret():
    d = _plan(kernel_mode="fused", backend="cpu")
    assert d.path == dispatch.FUSED and d.interpret and d.bt > 0


def test_plan_guards():
    assert _plan(kernel_mode="jnp").path == dispatch.JNP
    d = _plan(kernel_mode="unfused")
    assert d.path == dispatch.JNP and "no unfused" in d.reason
    assert _plan(kernel_mode="fused", cfg=QuantConfig(16)).path == dispatch.JNP
    d = _plan(kernel_mode="fused", cfg=QuantConfig(8, block=32))
    assert d.path == dispatch.JNP and "per-tensor" in d.reason
    d = _plan(kernel_mode="fused", gs=4096, t=32768, vmem_budget=1 << 20)
    assert d.path == dispatch.JNP and "vmem" in d.reason
    d = _plan(op="attn_decode", kernel_mode="fused", gs=4, t=65536,
              vmem_budget=1 << 20)
    assert d.path == dispatch.JNP


def test_plan_bwd_and_decode_ops():
    d = _plan(op="attn_bwd", kernel_mode="fused", kind="ii")
    assert d.path == dispatch.FUSED and d.bt > 0
    d = _plan(op="attn_decode", kernel_mode="fused", gs=4, kind="qi")
    assert d.path == dispatch.FUSED and d.bt > 0


def test_plan_attention_autotune_key_separation(tmp_path, monkeypatch):
    """Attention shapes tune under their own "attn_<kind>" keys, separate
    from the GEMM kinds, and the measured bq persists."""
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    d = dispatch.plan_attention("attn_fwd", 32, 128, 64, QuantConfig(8),
                                s=32, kind="pp", kernel_mode="fused",
                                autotune_measure=True)
    assert d.path == dispatch.FUSED and d.bm > 0
    import json
    data = json.load(open(str(tmp_path / "tune.json")))
    (key, entry), = data.items()
    assert key.startswith("attn_pp:32x64x128:") and entry["bm"] == d.bm


def test_attn_block_t_is_static_geometry():
    assert dispatch.attn_block_t(24) == 128
    assert dispatch.attn_block_t(2048) == 256
    assert dispatch.attn_block_t(100000) == 512


# ---------------------------------------------------------------------------
# forward: kernel vs jnp mirror (bit-exact) and vs the chunk scan (close)
# ---------------------------------------------------------------------------

def _quantized_qkv(b, hkv, g, s, t, d, seed=0):
    q = _rand((b * hkv, g * s, d), seed, 0.3)
    k = _rand((b * hkv, t, d), seed + 1)
    v = _rand((b * hkv, t, d), seed + 2)
    cfg = QuantConfig(8)
    qq = quantize(q, cfg, jax.random.fold_in(KEY, 1))
    kq = quantize(k, cfg, jax.random.fold_in(KEY, 2))
    vq = quantize(v, cfg, jax.random.fold_in(KEY, 3))
    return qq, kq, vq


def _fwd_both(qq, kq, vq, s, *, bq=32, bt=128, causal=True, window=0,
              q_off=0, stochastic=True, seed=9):
    bh, gs, d = qq.m.shape
    t = kq.m.shape[1]
    rp = (rounding_bits(jax.random.fold_in(KEY, seed), (bh, gs, t))
          if stochastic else None)
    kw = dict(p=7, s=s, bq=bq, bt=bt, causal=causal, window=window,
              stochastic=stochastic, interpret=True)
    args = (qq.m, kq.m, vq.m, rp, qq.e, kq.e, vq.e, jnp.int32(q_off),
            jnp.int32(t))
    out_p = jax.jit(lambda *a: fa.attn_fwd(*a, pallas=True, **kw))(*args)
    out_r = jax.jit(lambda *a: fa.attn_fwd(*a, pallas=False, **kw))(*args)
    return out_p, out_r


def test_fwd_pallas_matches_mirror_causal_gqa():
    qq, kq, vq = _quantized_qkv(2, 1, 2, 12, 20, 16)
    (y1, m1, l1), (y2, m2, l2) = _fwd_both(qq, kq, vq, s=12)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_fwd_pallas_matches_mirror_window_and_noncausal():
    qq, kq, vq = _quantized_qkv(1, 2, 1, 24, 24, 16, seed=5)
    for kw in (dict(window=8), dict(causal=False), dict(q_off=7),
               dict(stochastic=False)):
        (y1, _, _), (y2, _, _) = _fwd_both(qq, kq, vq, s=24, **kw)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_fwd_prime_lengths_pad_exactly():
    """S=17, T=19, D=12: every axis needs padding; the padded kernel must
    equal its mirror bit-for-bit and stay close to the float oracle."""
    qq, kq, vq = _quantized_qkv(1, 1, 2, 17, 19, 12, seed=11)
    (y1, _, _), (y2, _, _) = _fwd_both(qq, kq, vq, s=17, bq=32, bt=128)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    qd, kd, vd = dequantize(qq), dequantize(kq), dequantize(vq)
    qpos = jnp.tile(jnp.arange(17), 2)
    mask = jnp.arange(19)[None, :] <= qpos[:, None]
    sc = jnp.where(mask[None], jnp.einsum("bqd,btd->bqt", qd, kd), -1e30)
    oracle = jnp.einsum("bqt,btd->bqd", jax.nn.softmax(sc, -1), vd)
    assert _rel(y1, oracle) < 0.1


def test_fwd_multiblock_online_softmax():
    """T spans several KV blocks (bt=128 < T=300): the online rescaling
    path runs for real and still matches the mirror bit-for-bit."""
    qq, kq, vq = _quantized_qkv(1, 1, 1, 64, 300, 16, seed=13)
    (y1, m1, l1), (y2, m2, l2) = _fwd_both(qq, kq, vq, s=64, bq=32, bt=128)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_fused_chunked_attention_close_to_scan():
    q = _rand((2, 4, 24, 16), 1)
    k = _rand((2, 2, 24, 16), 2)
    v = _rand((2, 2, 24, 16), 3)
    o_scan = chunked_attention(q, k, v, KEY, QF, chunk=8)
    with dispatch.record_decisions() as log:
        o_fused = chunked_attention(q, k, v, KEY, QFF, chunk=8)
    d = next(d for d in log if d.op == "attn_fwd")
    assert d.path == dispatch.FUSED and d.interpret and d.kind == "pp"
    assert _rel(o_fused, o_scan) < 0.1
    # jit does not change the fused result
    jf = jax.jit(lambda q, k, v: chunked_attention(q, k, v, KEY, QFF, chunk=8))
    np.testing.assert_array_equal(np.asarray(jf(q, k, v)),
                                  np.asarray(o_fused))


def test_fused_local_attention_close_to_blocked():
    q = _rand((2, 4, 24, 16), 21)
    k = _rand((2, 2, 24, 16), 22)
    v = _rand((2, 2, 24, 16), 23)
    o_blk = local_attention(q, k, v, KEY, QF, window=8)
    with dispatch.record_decisions() as log:
        o_fused = local_attention(q, k, v, KEY, QFF, window=8)
    assert any(d.op == "attn_fwd" and d.path == dispatch.FUSED for d in log)
    assert _rel(o_fused, o_blk) < 0.15


# ---------------------------------------------------------------------------
# exact integer oracles for the in-kernel QKᵀ and PV contractions
# ---------------------------------------------------------------------------

def test_fwd_integer_oracle_single_block():
    """Non-causal single-block case: the fused output must be reproducible
    from pure integer primitives — int32 QKᵀ, float softmax, the
    ``ref.bfp_quantize_ref`` mapping fed the SAME rounding bits with one
    shared exponent per row, int32 PV, one f32 rescale per stage."""
    bh, gs, t, d = 1, 16, 24, 16
    qq, kq, vq = _quantized_qkv(1, 1, 1, gs, t, d, seed=31)
    rp = rounding_bits(jax.random.fold_in(KEY, 9), (bh, gs, t))
    kw = dict(p=7, s=gs, bq=32, bt=128, causal=False, window=0,
              stochastic=True, interpret=True)
    y, m, l = fa.attn_fwd(qq.m, kq.m, vq.m, rp, qq.e, kq.e, vq.e,
                          jnp.int32(0), jnp.int32(t), pallas=True, **kw)
    # oracle (slice 0): integer QKᵀ with exponent-add rescale
    s32 = np.asarray(qq.m[0]).astype(np.int64) @ np.asarray(kq.m[0]).T.astype(np.int64)
    sc = float(pow2(scale_exponent(qq.e, qq.cfg) + scale_exponent(kq.e, kq.cfg)))
    sf = jnp.asarray((s32 * sc).astype(np.float32))
    m_or = sf.max(axis=-1, keepdims=True)
    pt = jnp.exp(sf - m_or)
    np.testing.assert_array_equal(np.asarray(m[0]), np.asarray(m_or))
    np.testing.assert_array_equal(np.asarray(l[0]),
                                  np.asarray(pt.sum(-1, keepdims=True)))
    # p quantization: same rounding bits, one shared exponent per row
    e_row = ref.max_biased_exp_ref(pt, axis=-1)[:, None]
    ph = ref.bfp_quantize_ref(pt, rp[0], e_row)
    np.testing.assert_array_equal(
        np.asarray(ph),
        np.asarray(tile.quantize_tile(pt, rp[0], e_row, 7, True)))
    # integer PV with the per-row p scale + scalar V scale epilogue
    pv = np.asarray(ph).astype(np.int64) @ np.asarray(vq.m[0]).astype(np.int64)
    scale = np.asarray(pow2(scale_exponent(e_row, QuantConfig(8))
                            + scale_exponent(vq.e, vq.cfg)))
    y_or = (pv * scale) / np.maximum(np.asarray(pt.sum(-1, keepdims=True)),
                                     1e-30)
    np.testing.assert_array_equal(np.asarray(y[0]),
                                  y_or.astype(np.float32))


def test_decode_integer_oracle():
    """The fused decode output reproduced from integer primitives: QKᵀ of
    raw mantissas with per-row K exponents as a column epilogue, softmax,
    V-row exponents folded into p, ``ref.bfp_quantize_ref`` with the same
    bits, int32 PV under a unit V scale."""
    b, g, t, d = 1, 4, 24, 16
    q1 = _rand((b, 1, g, d), 41, 0.3)
    kc = _rand((b, 1, t, d), 42)
    vc = _rand((b, 1, t, d), 43)
    kq, vq = qcache_quantize(kc, QC), qcache_quantize(vc, QC)
    cfgq = QuantConfig(8)
    qq = quantize(q1, cfgq, jax.random.fold_in(KEY, 0))
    rp = rounding_bits(jax.random.fold_in(KEY, 1), (b, g, t))
    y = fa.attn_decode(qq.m.reshape(b, g, d), kq.m.reshape(b, t, d),
                       vq.m.reshape(b, t, d), kq.e.reshape(b, t, 1),
                       vq.e.reshape(b, t, 1), rp, qq.e,
                       jnp.int32(t - 1), jnp.int32(t), p=7, s=1,
                       causal=False, window=0, stochastic=True,
                       interpret=True, pallas=True)
    s32 = np.asarray(qq.m[0, 0]).astype(np.int64) @ np.asarray(
        kq.m[0, 0]).T.astype(np.int64)
    col_k = np.asarray(pow2(scale_exponent(kq.e[0, 0], kq.cfg))).reshape(1, t)
    sf = (s32.astype(np.float32)
          * np.asarray(pow2(scale_exponent(qq.e, cfgq)))) * col_k
    p = jax.nn.softmax(jnp.asarray(sf), axis=-1)
    p2 = p * jnp.asarray(
        np.asarray(pow2(scale_exponent(vq.e[0, 0], vq.cfg))).reshape(1, t))
    e_row = ref.max_biased_exp_ref(p2, axis=-1)[:, None]
    ph = ref.bfp_quantize_ref(p2, rp[0], e_row)
    pv = np.asarray(ph).astype(np.int64) @ np.asarray(vq.m[0, 0]).astype(np.int64)
    y_or = pv * np.asarray(pow2(scale_exponent(e_row, QuantConfig(8))))
    np.testing.assert_array_equal(np.asarray(y[0]), y_or.astype(np.float32))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def test_bwd_pallas_matches_mirror():
    qq, kq, vq = _quantized_qkv(2, 1, 2, 12, 20, 16, seed=51)
    bh, gs, d = qq.m.shape
    t = kq.m.shape[1]
    (y, m, l), _ = _fwd_both(qq, kq, vq, s=12)
    gy = _rand((bh, gs, d), 52)
    gq = quantize(gy, QuantConfig(8), jax.random.fold_in(KEY, 53))
    delta = (gy * y).sum(-1, keepdims=True)
    rs = rounding_bits(jax.random.fold_in(KEY, 54), (bh, gs, t))
    rp2 = rounding_bits(jax.random.fold_in(KEY, 55), (bh, gs, t))
    kw = dict(p=7, s=12, bt=128, causal=True, window=0, stochastic=True,
              interpret=True)
    args = (qq.m, gq.m, kq.m, vq.m, m, l, delta, rs, rp2,
            qq.e, kq.e, vq.e, gq.e, jnp.int32(0), jnp.int32(t))
    outs_p = jax.jit(lambda *a: fa.attn_bwd(*a, pallas=True, **kw))(*args)
    outs_r = jax.jit(lambda *a: fa.attn_bwd(*a, pallas=False, **kw))(*args)
    for a, b in zip(outs_p, outs_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_gradients_close_to_scan_and_carriers_flow():
    q = _rand((2, 4, 24, 16), 61)
    k = _rand((2, 2, 24, 16), 62)
    v = _rand((2, 2, 24, 16), 63)

    def loss(pol):
        return lambda q, k, v: (chunked_attention(q, k, v, KEY, pol,
                                                  chunk=8) ** 2).sum()

    with dispatch.record_decisions() as log:
        gf = jax.grad(loss(QFF), argnums=(0, 1, 2))(q, k, v)
    paths = {d.op: d.path for d in log}
    assert paths["attn_fwd"] == dispatch.FUSED
    assert paths["attn_bwd"] == dispatch.FUSED
    gs = jax.grad(loss(QF), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gs):
        assert np.isfinite(np.asarray(a)).all()
        assert np.abs(np.asarray(a)).max() > 0       # carriers carry
        assert _rel(a, b) < 0.35


def test_fused_bwd_per_block_policy_fallback_cfg():
    """A per-block policy whose block doesn't divide head_dim falls back
    to per-tensor operands on the forward (the _cfg_for_dim rule) and may
    legitimately take the fused path; the backward's fresh quantizations
    must then follow the op's per-tensor blocking, not the policy's —
    this used to crash with 'trailing dim not divisible by block'."""
    pol = dataclasses.replace(QFF, block=48)     # 48 ∤ d=16
    q = _rand((1, 2, 16, 16), 75)
    k = _rand((1, 2, 16, 16), 76)
    v = _rand((1, 2, 16, 16), 77)
    with dispatch.record_decisions() as log:
        gq = jax.grad(lambda q: (chunked_attention(
            q, k, v, KEY, pol, chunk=8) ** 2).sum())(q)
    assert any(d.op == "attn_bwd" and d.path == dispatch.FUSED for d in log)
    assert np.isfinite(np.asarray(gq)).all()


def test_fused_decode_gate_keeps_per_block_policy_on_scan():
    """Per-block policies must never take the fused decode path: the scan
    path quantizes a fresh Q on the per-block grid, which the per-tensor
    kernel cannot reproduce."""
    q1 = _rand((1, 2, 1, 16), 78, 0.5)
    kc = _rand((1, 1, 24, 16), 79)
    vc = _rand((1, 1, 24, 16), 80)
    kq, vq = qcache_quantize(kc, QC), qcache_quantize(vc, QC)
    pol = dataclasses.replace(QCF, block=8)
    with dispatch.record_decisions() as log:
        try:
            cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY, pol)
        except ValueError:
            # mixing a per-block fresh Q with per-tensor cache views is
            # unsupported on the scan path too (pre-existing; unreachable
            # in serving — qcache_on requires a per-tensor policy).  This
            # test only pins that the fused gate declined.
            pass
    assert not any(d.op == "attn_decode" for d in log)


def test_fused_gradients_under_jit_and_window():
    q = _rand((1, 2, 16, 16), 71)
    k = _rand((1, 2, 16, 16), 72)
    v = _rand((1, 2, 16, 16), 73)

    @jax.jit
    def g(q, k, v):
        return jax.grad(lambda q: (chunked_attention(
            q, k, v, KEY, QFF, chunk=8, window=8) ** 2).sum())(q)

    out = g(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out)).max() > 0


# ---------------------------------------------------------------------------
# fused qcache decode
# ---------------------------------------------------------------------------

def test_fused_decode_close_to_scan_and_records_decision():
    q1 = _rand((2, 4, 1, 16), 81, 0.5)
    kc = _rand((2, 2, 24, 16), 82)
    vc = _rand((2, 2, 24, 16), 83)
    kq, vq = qcache_quantize(kc, QC), qcache_quantize(vc, QC)
    o_scan = cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY, QC)
    with dispatch.record_decisions() as log:
        o_fused = cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY, QCF)
    d = next(d for d in log if d.op == "attn_decode")
    assert d.path == dispatch.FUSED and d.kind == "qi"
    assert _rel(o_fused, o_scan) < 0.1
    # windowed band slice + fused kernel
    o_w = cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY, QCF,
                                 window=8)
    o_w0 = cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY, QC,
                                  window=8)
    assert _rel(o_w, o_w0) < 0.15
    # qflow decode plans the fully-pre-quantized kind
    with dispatch.record_decisions() as log:
        cache_decode_attention(q1, kq, vq, jnp.int32(11), KEY,
                               dataclasses.replace(QCF, qflow=True))
    d = next(d for d in log if d.op == "attn_decode")
    assert d.kind == "pp"


def test_fused_decode_via_decode_attention_traced_pos():
    q1 = _rand((1, 2, 1, 16), 91, 0.5)
    kc = _rand((1, 1, 24, 16), 92)
    vc = _rand((1, 1, 24, 16), 93)
    kq, vq = qcache_quantize(kc, QC), qcache_quantize(vc, QC)

    f = jax.jit(lambda pos: decode_attention(q1, kq, vq, pos, KEY, QCF))
    y1, y2 = f(jnp.int32(11)), f(jnp.int32(5))
    assert np.isfinite(np.asarray(y1)).all()
    assert np.abs(np.asarray(y1 - y2)).max() > 0    # pos changes the mask


# ---------------------------------------------------------------------------
# spec pin: fused path off == PR-4 HEAD, bit for bit
# ---------------------------------------------------------------------------

def test_spec_pin_fused_off_bit_identical_to_pr4():
    g = np.load(GOLDENS)
    q = _rand((2, 4, 24, 16), 1)
    k = _rand((2, 2, 24, 16), 2)
    v = _rand((2, 2, 24, 16), 3)
    outs = {
        "chunked_int8": chunked_attention(q, k, v, KEY, PAPER_INT8, chunk=8),
        "chunked_qflow": chunked_attention(q, k, v, KEY, QF, chunk=8),
        "chunked_window": chunked_attention(q, k, v, KEY, QF, chunk=8,
                                            window=8),
        "chunked_noncausal": chunked_attention(q, k, v, KEY, QF,
                                               causal=False, chunk=8),
        "local_int8": local_attention(q, k, v, KEY, PAPER_INT8, window=8),
        "local_qflow": local_attention(q, k, v, KEY, QF, window=8),
    }
    def loss(q, k, v):
        return (chunked_attention(q, k, v, KEY, QF, chunk=8) ** 2).sum()
    outs["chunked_qflow_gq"], outs["chunked_qflow_gk"], \
        outs["chunked_qflow_gv"] = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    q1 = _rand((2, 4, 1, 16), 4)
    kc = _rand((2, 2, 24, 16), 5)
    vc = _rand((2, 2, 24, 16), 6)
    outs["decode_float"] = decode_attention(q1, kc, vc, jnp.int32(11), KEY,
                                            PAPER_INT8)
    outs["decode_float_win"] = decode_attention(q1, kc, vc, jnp.int32(11),
                                                KEY, PAPER_INT8, window=8)
    kq, vq = qcache_quantize(kc, QC), qcache_quantize(vc, QC)
    outs["decode_qcache"] = cache_decode_attention(q1, kq, vq, jnp.int32(11),
                                                   KEY, QC)
    outs["decode_qcache_qflow"] = cache_decode_attention(
        q1, kq, vq, jnp.int32(11), KEY, dataclasses.replace(QC, qflow=True))
    outs["decode_qcache_win"] = cache_decode_attention(
        q1, kq, vq, jnp.int32(11), KEY, QC, window=8)
    outs["decode_qcache_xattn"] = cache_decode_attention(
        q1, kq, vq, jnp.int32(0), KEY, QC, causal=False)
    for name, val in outs.items():
        np.testing.assert_array_equal(np.asarray(val), g[name],
                                      err_msg=f"spec pin broken: {name}")


# ---------------------------------------------------------------------------
# traffic model
# ---------------------------------------------------------------------------

def test_attention_bytes_fused_strictly_below_scan():
    for gs, t, d in [(64, 256, 64), (128, 512, 64), (4096, 4096, 128)]:
        f = dispatch.attention_bytes_moved(dispatch.FUSED, gs, t, d)
        s = dispatch.attention_bytes_moved("scan", gs, t, d)
        assert f < s, (gs, t, d, f, s)
    for g, t, d in [(1, 256, 64), (8, 4096, 128)]:
        f = dispatch.attention_bytes_moved(dispatch.FUSED, g, t, d,
                                           op="attn_decode")
        s = dispatch.attention_bytes_moved("scan", g, t, d,
                                           op="attn_decode")
        assert f < s, (g, t, d, f, s)


def test_attn_vmem_model_monotone():
    small = dispatch._attn_vmem_bytes("attn_fwd", 32, 32, 256, 128, 128, True)
    big = dispatch._attn_vmem_bytes("attn_fwd", 256, 256, 4096, 128, 256, True)
    assert 0 < small < big
