"""Dispatch-layer tests: routing rules, end-to-end fused execution of
qmatmul/qbmm forward + both backward GEMMs (introspected via
record_decisions), the bytes-moved model, and the autotune cache."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NumericPolicy, qbmm, qmatmul
from repro.core.bfp import QuantConfig
from repro.kernels import autotune, dispatch

KEY = jax.random.key(42)


def _rand(shape, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


# ---------------------------------------------------------------------------
# plan_contract routing rules
# ---------------------------------------------------------------------------

def _plan(**kw):
    args = dict(op="t", m=64, k=128, n=64, cfg=QuantConfig(8))
    args.update(kw)
    return dispatch.plan_contract(args.pop("op"), args.pop("m"),
                                  args.pop("k"), args.pop("n"),
                                  args.pop("cfg"), **args)


def test_plan_auto_keeps_jnp_oracle_on_cpu():
    d = _plan(kernel_mode="auto", backend="cpu")
    assert d.path == dispatch.JNP


def test_plan_auto_goes_fused_on_tpu():
    d = _plan(kernel_mode="auto", backend="tpu")
    assert d.path == dispatch.FUSED and d.bm > 0 and not d.interpret


def test_plan_forced_fused_on_cpu_uses_interpret():
    d = _plan(kernel_mode="fused", backend="cpu")
    assert d.path == dispatch.FUSED and d.interpret


def test_plan_wide_bits_fall_back_to_jnp():
    d = _plan(kernel_mode="fused", cfg=QuantConfig(16))
    assert d.path == dispatch.JNP and "int8" in d.reason


def test_plan_vmem_overflow_degrades_fused_to_unfused():
    d = _plan(kernel_mode="fused", k=4096, n=4096, m=4096,
              vmem_budget=1 << 20)
    assert d.path == dispatch.UNFUSED and "infeasible" in d.reason


def test_plan_per_block_degrades_to_jnp_not_unfused():
    d = _plan(kernel_mode="fused", cfg=QuantConfig(8, block=32),
              k=4096, n=4096, m=4096, vmem_budget=1 << 20)
    assert d.path == dispatch.JNP


def test_plan_accum_chunk_guard_stays_on_jnp():
    d = _plan(kernel_mode="fused", k=1024, accum_chunk=512)
    assert d.path == dispatch.JNP and "accum_chunk" in d.reason


def test_plan_per_block_ii_variant_unsupported():
    d = _plan(kernel_mode="fused", cfg=QuantConfig(8, block=32), kind="ii")
    assert d.path == dispatch.JNP


def test_plan_nearest_rounding_never_unfused():
    """The standalone quantizer kernel is SR-only: nearest rounding must be
    fused or jnp, never unfused (zero rand bits would turn SR into ceil)."""
    cfg = QuantConfig(8, stochastic=False)
    d = _plan(kernel_mode="unfused", cfg=cfg)
    assert d.path == dispatch.JNP and "SR-only" in d.reason
    assert _plan(kernel_mode="fused", cfg=cfg).path == dispatch.FUSED
    # ii contracts pre-quantized residuals (no fresh rounding): unfused OK
    d = _plan(kernel_mode="unfused", cfg=cfg, kind="ii")
    assert d.path == dispatch.UNFUSED


# ---------------------------------------------------------------------------
# end-to-end: fused path is the execution path for fwd + both bwd GEMMs
# ---------------------------------------------------------------------------

def test_qmatmul_fwd_and_both_bwd_execute_fused():
    """The acceptance-criterion test: with kernel_mode='fused' (interpret on
    CPU), the forward GEMM and both Appendix-A.2 backward GEMMs run on the
    fused Pallas pipeline, and results match the jnp oracle bit-for-bit."""
    x, w = _rand((48, 72), 1), _rand((72, 40), 2)
    pol = NumericPolicy(kernel_mode="fused")
    ref_pol = NumericPolicy(kernel_mode="jnp")

    def loss(pol):
        return lambda x, w: (qmatmul(x, w, KEY, pol) ** 2).sum()

    with dispatch.record_decisions() as log:
        y = qmatmul(x, w, KEY, pol)
        gx, gw = jax.grad(loss(pol), argnums=(0, 1))(x, w)
    paths = {d.op: d.path for d in log}
    assert paths["qmatmul_fwd"] == dispatch.FUSED
    assert paths["qmatmul_dx"] == dispatch.FUSED
    assert paths["qmatmul_dw"] == dispatch.FUSED
    assert all(d.interpret for d in log)

    y_ref = qmatmul(x, w, KEY, ref_pol)
    gx_ref, gw_ref = jax.grad(loss(ref_pol), argnums=(0, 1))(x, w)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(gx_ref))
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(gw_ref))


def test_qbmm_fwd_and_both_bwd_execute_fused():
    a, b = _rand((2, 16, 24), 3), _rand((2, 24, 12), 4)
    pol = NumericPolicy(kernel_mode="fused")
    ref_pol = NumericPolicy(kernel_mode="jnp")

    def loss(pol):
        return lambda a, b: (qbmm(a, b, KEY, pol) ** 2).sum()

    with dispatch.record_decisions() as log:
        y = qbmm(a, b, KEY, pol)
        ga, gb = jax.grad(loss(pol), argnums=(0, 1))(a, b)
    paths = {d.op: d.path for d in log}
    assert paths["qbmm_fwd"] == dispatch.FUSED
    assert paths["qbmm_dx"] == dispatch.FUSED
    assert paths["qbmm_dw"] == dispatch.FUSED

    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(qbmm(a, b, KEY, ref_pol)))
    ga_ref, gb_ref = jax.grad(loss(ref_pol), argnums=(0, 1))(a, b)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(ga_ref))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(gb_ref))


def test_qmatmul_nearest_rounding_fused_matches_jnp():
    """stochastic=False end-to-end: the rand-less kernel variants must be
    bit-identical to the jnp nearest-rounding oracle."""
    x, w = _rand((24, 40), 11), _rand((40, 16), 12)
    pol = NumericPolicy(stochastic=False, kernel_mode="fused")
    ref_pol = NumericPolicy(stochastic=False, kernel_mode="jnp")
    with dispatch.record_decisions() as log:
        y = qmatmul(x, w, KEY, pol)
    assert {d.path for d in log} == {dispatch.FUSED}
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(qmatmul(x, w, KEY, ref_pol)))
    g = jax.grad(lambda w: (qmatmul(x, w, KEY, pol) ** 2).sum())(w)
    g_ref = jax.grad(lambda w: (qmatmul(x, w, KEY, ref_pol) ** 2).sum())(w)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))


def test_qmatmul_per_block_fused_matches_jnp():
    x, w = _rand((32, 64), 5), _rand((64, 32), 6)
    pol = NumericPolicy(block=32, kernel_mode="fused")
    ref_pol = NumericPolicy(block=32, kernel_mode="jnp")
    np.testing.assert_allclose(
        np.asarray(qmatmul(x, w, KEY, pol)),
        np.asarray(qmatmul(x, w, KEY, ref_pol)), rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda x, w: (qmatmul(x, w, KEY, pol) ** 2).sum(),
                 argnums=(0, 1))(x, w)
    g_ref = jax.grad(lambda x, w: (qmatmul(x, w, KEY, ref_pol) ** 2).sum(),
                     argnums=(0, 1))(x, w)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_unfused_path_bit_identical_to_jnp():
    x, w = _rand((24, 56), 7), _rand((56, 24), 8)
    pol = NumericPolicy(kernel_mode="unfused")
    ref_pol = NumericPolicy(kernel_mode="jnp")
    with dispatch.record_decisions() as log:
        y = qmatmul(x, w, KEY, pol)
    assert all(d.path == dispatch.UNFUSED for d in log
               if d.op == "qmatmul_fwd")
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(qmatmul(x, w, KEY, ref_pol)))


def test_dispatch_fallback_on_infeasible_shape_still_correct():
    """kernel_mode='fused' with a contraction the fused kernel can't take
    (K > accum_chunk) must degrade without changing semantics."""
    x, w = _rand((4, 600), 9), _rand((600, 8), 10)
    pol = NumericPolicy(kernel_mode="fused", accum_chunk=512)
    ref_pol = NumericPolicy(kernel_mode="jnp", accum_chunk=512)
    with dispatch.record_decisions() as log:
        y = qmatmul(x, w, KEY, pol)
    assert {d.path for d in log} == {dispatch.JNP}
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(qmatmul(x, w, KEY, ref_pol)))


# ---------------------------------------------------------------------------
# bytes-moved traffic model
# ---------------------------------------------------------------------------

def test_bytes_moved_fused_strictly_below_unfused():
    for m, k, n in [(128, 128, 128), (512, 512, 512), (1024, 4096, 1024)]:
        f = dispatch.bytes_moved(dispatch.FUSED, m, k, n)
        u = dispatch.bytes_moved(dispatch.UNFUSED, m, k, n)
        j = dispatch.bytes_moved(dispatch.JNP, m, k, n)
        assert f < u < j
        # the gap is exactly the eliminated intermediate HBM round-trip:
        # the GEMM's re-reads of the quantizer's int8 writes (the model's
        # default geometry = the executed 128-tile unfused GEMM).
        import math
        gemm_reads = (math.ceil(n / 128) * m * k + math.ceil(m / 128) * n * k)
        assert u - f == gemm_reads


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path):
    cache = autotune.AutotuneCache(str(tmp_path / "tune.json"))
    assert cache.get("k") is None
    cache.put("k", {"bm": 128, "us": {"128": 10.0}})
    assert cache.get("k")["bm"] == 128
    # corrupt file tolerated
    with open(cache.path, "w") as f:
        f.write("{not json")
    assert cache.get("k") is None


def test_select_bm_uses_cache_without_benching(tmp_path):
    cache = autotune.AutotuneCache(str(tmp_path / "tune.json"))
    cache.put("key", {"bm": 64, "us": {}})

    def bench(bm):  # pragma: no cover - must not run
        raise AssertionError("bench called despite cache hit")

    bm = autotune.select_bm("key", 100, lambda bm: True, measure=True,
                            bench=bench, cache=cache)
    assert bm == 64


def test_select_bm_measures_once_and_persists(tmp_path):
    cache = autotune.AutotuneCache(str(tmp_path / "tune.json"))
    calls = []

    def bench(bm):
        calls.append(bm)
        return float(abs(bm - 64))  # 64 is fastest

    bm = autotune.select_bm("key2", 100, lambda bm: bm <= 128, measure=True,
                            bench=bench, cache=cache)
    assert bm == 64
    assert set(calls) == {32, 64, 128}
    on_disk = json.load(open(cache.path))
    assert on_disk["key2"]["bm"] == 64
    # second call: served from cache, no re-measure
    calls.clear()
    assert autotune.select_bm("key2", 100, lambda bm: bm <= 128,
                              measure=True, bench=bench, cache=cache) == 64
    assert calls == []


def test_select_bm_heuristic_is_deterministic():
    fits = lambda bm: bm <= 256
    assert autotune.heuristic_bm(16, fits) == 32
    assert autotune.heuristic_bm(100, fits) == 128
    assert autotune.heuristic_bm(10_000, fits) == 256
    assert autotune.heuristic_bm(64, lambda bm: False) == 0


def test_plan_contract_with_real_autotune_measurement(tmp_path, monkeypatch):
    """kernel_autotune measures the real fused kernel once per shape and
    persists the winner; the cached entry short-circuits the next plan.
    The jnp mirror is itself a measured candidate: when every fused tile
    loses to it (common in interpret mode), the plan routes JNP and the
    cache records the routing as ``{"bm": 0, "jnp": true}``."""
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    d = dispatch.plan_contract("t", 32, 128, 32, QuantConfig(8),
                               kernel_mode="fused", autotune_measure=True)
    data = json.load(open(str(tmp_path / "tune.json")))
    (key, entry), = data.items()
    assert key.startswith("qq:32x128x32:")
    assert len(entry["us"]) >= 1
    if d.path == dispatch.FUSED:
        assert d.bm in autotune.BM_CANDIDATES and entry["bm"] == d.bm
    else:
        assert d.path == dispatch.JNP
        assert entry == {"bm": 0, "jnp": True, "us": entry["us"]}
        assert "jnp" in entry["us"]
    d2 = dispatch.plan_contract("t", 32, 128, 32, QuantConfig(8),
                                kernel_mode="fused", autotune_measure=True)
    assert (d2.path, d2.bm) == (d.path, d.bm)


def test_plan_speculative_verify_prices_the_round_exactly():
    """The round-traffic model is closed-form: k draft steps stream the
    truncated model (layer-count fraction of weight + cache bytes by
    default), the verify pass reads the target's weights once plus k+1
    cache bands.  breakeven_accepted is the fewest landed draft tokens
    that make the round cheaper per emitted token than plain decode."""
    plan = dispatch.plan_speculative_verify(
        4, 2, 8, weight_bytes=1000, cache_bytes=100)
    assert plan["draft_weight_bytes"] == 250
    assert plan["draft_cache_bytes"] == 25
    assert plan["round_bytes"] == 4 * (250 + 25) + 1000 + 5 * 100
    assert plan["sequential_bytes_per_token"] == 1100
    assert plan["sequential_block_bytes"] == 5 * 1100
    # round=2600, seq/token=1100 -> need ceil(2600/1100 - 1) = 2 landed
    assert plan["breakeven_accepted"] == 2
    assert plan["reduction_at_full_accept_pct"] == round(
        100.0 * (1 - 2600 / 5500), 2)
    # explicit draft byte overrides are honoured verbatim
    over = dispatch.plan_speculative_verify(
        1, 1, 2, weight_bytes=10, cache_bytes=10,
        draft_weight_bytes=7, draft_cache_bytes=3)
    assert over["round_bytes"] == (7 + 3) + 10 + 2 * 10
    # a full-depth draft prices the degenerate case: every draft step
    # costs a whole target step, so speculation can never win on bytes
    full = dispatch.plan_speculative_verify(
        2, 8, 8, weight_bytes=1000, cache_bytes=100)
    assert full["round_bytes"] > full["sequential_block_bytes"] - 1000
    assert full["breakeven_accepted"] >= 2


def test_plan_speculative_verify_rejects_bad_geometry():
    with pytest.raises(ValueError, match=r"draft_layers must be in \[1, 4\]"):
        dispatch.plan_speculative_verify(2, 0, 4, weight_bytes=1,
                                         cache_bytes=1)
    with pytest.raises(ValueError, match="k must be >= 1"):
        dispatch.plan_speculative_verify(0, 1, 4, weight_bytes=1,
                                         cache_bytes=1)


def test_plan_on_multi_device_mesh_keeps_jnp_for_compiled_kernels():
    """GSPMD cannot partition a Mosaic kernel: inside a multi-device mesh
    a compiled plan takes the (bit-identical) jnp path with a reason the
    chip smoke run recognizes; interpret-mode kernels and one-device
    meshes are unaffected."""
    import types

    from repro.runtime.sharding import DEFAULT_RULES, use_rules
    four = types.SimpleNamespace(size=4)
    with use_rules(DEFAULT_RULES, four):
        d = _plan(kernel_mode="auto", backend="tpu")
        assert d.path == dispatch.JNP and d.reason == dispatch.UNPARTITIONED
        a = dispatch.plan_attention("attn_fwd", 64, 256, 64, QuantConfig(8),
                                    s=64, backend="tpu")
        assert a.reason == dispatch.UNPARTITIONED
        assert _plan(kernel_mode="fused", backend="cpu").path == dispatch.FUSED
    with use_rules(DEFAULT_RULES, types.SimpleNamespace(size=1)):
        assert _plan(kernel_mode="auto", backend="tpu").path == dispatch.FUSED


def test_decode_block_megakernel_plans_for_mosaic_within_budget():
    """The whole-layer decode kernel lowers through Mosaic: a layer that
    fits the residency budget plans the compiled kernel on a TPU, a
    published-width layer (qwen2-0.5b: 15.5 MiB resident) keeps the
    per-op path, and a multi-device mesh keeps the jnp path."""
    import types

    from repro.runtime.sharding import DEFAULT_RULES, use_rules
    qc = QuantConfig(8, stochastic=False)
    small = ("qdecode_block", 1, 32, 64, 12, 2, 2, 16, qc)
    d = dispatch.plan_decode_block(*small, backend="tpu")
    assert d.path == dispatch.FUSED and not d.interpret
    d = dispatch.plan_decode_block("qdecode_block", 4, 896, 4864, 256, 14,
                                   2, 64, qc, backend="tpu")
    assert d.path == dispatch.JNP and "vmem_budget" in d.reason
    with use_rules(DEFAULT_RULES, types.SimpleNamespace(size=4)):
        d = dispatch.plan_decode_block(*small, backend="tpu")
        assert d.reason == dispatch.UNPARTITIONED
    d = dispatch.plan_decode_block(*small, kernel_mode="fused",
                                   backend="cpu")
    assert d.path == dispatch.FUSED and d.interpret
