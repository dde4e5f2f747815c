"""The trace reduction, the peaks table and the operation and byte
counts of the benchmark, on the CPU."""

import json

import pytest

from bench import ops, spec, trace

FAM = {"gemm": ["fused_qq_pt_pallas"], "attention": ["fused_attn_fwd_pallas"]}


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_events_by_hand():
    """Two devices over a window of 100 ns.  Device 0 runs a while loop
    (5..60) with two operations nested in it, then an attention kernel;
    device 1 one fusion.  Busy is the union, averaged over devices; each
    operation counts its self time, in the family its own name gives."""
    devices = {
        "/device:TPU:0": [
            ("%while.1 = (s32[]) while(...)", 5, 60, ""),
            ("%fusion.2 = f32[8] fusion(%fused_qq_pt_pallas.3)", 10, 30, ""),
            ("%fused_qq_pt_pallas.3 = f32[8] custom-call(...)", 30, 50, ""),
            ("%fused_attn_fwd_pallas.4 = f32[8] custom-call(...)", 70, 80,
             "")],
        "/device:TPU:1": [("%fusion.1 = f32[8] fusion(...)", 0, 40, "")],
    }
    spans = [("bench.window", 0, 100, ""), ("bench.step", 0, 55, ""),
             ("bench.loss_fetch", 55, 100, "")]
    r = trace.reduce_events(devices, spans, FAM)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((65e-9 + 40e-9) / 2)
    assert r["family_s"]["gemm"] == pytest.approx(20e-9 / 2)
    assert r["family_s"]["attention"] == pytest.approx(10e-9 / 2)
    # while 55 - 40 nested, fusion.2 20 (its operand names a kernel, its
    # own name does not), fusion.1 40
    assert r["family_s"]["xla"] == pytest.approx(75e-9 / 2)
    assert sum(r["family_s"].values()) == pytest.approx(r["busy_s"])
    assert dict(r["device_ops"])["fusion"] == pytest.approx(60e-9 / 2)
    gaps = dict(r["idle_gaps"])
    # a gap goes whole to the span that covers most of it: device 0 idle
    # 0..5 (step), 60..70 and 80..100 (fetch); device 1 idle 40..100
    # (fetch 45 of its 60 ns)
    assert gaps["bench.loss_fetch"] == pytest.approx(90e-9 / 2)
    assert gaps["bench.step"] == pytest.approx(5e-9 / 2)
    assert trace.idle_pct(r) == pytest.approx(47.5)


def test_reduce_a_recorded_trace(tmp_path, monkeypatch):
    """A profile recorded here: the CPU client's threads stand in for the
    device.  Busy lies within the window, the families add up to the op
    time, and the idle share is a share."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((192, 192))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    monkeypatch.setattr(trace, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(trace, "OP_LINES", ("tf_XLAPjRtCpuClient",))
    devices, spans = trace.read_xspace(trace.find_xspace(str(tmp_path)))
    assert any(n == "bench.window" for n, *_ in spans)
    r = trace.reduce_events(devices, spans, {"gemm": ["^dot"]})
    assert 0 < r["busy_s"] <= r["window_s"] * r["n_devices"]
    assert r["family_s"].get("gemm", 0) > 0
    assert 0.0 <= trace.idle_pct(r) <= 100.0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


def _cell_gemm_shapes():
    from bench.ops import step_gemms
    shapes = set()
    for name in ("qwen2-0.5b", "minicpm-2b-stage"):
        conf = json.load(open(spec.BENCH / "configs" / f"{name}.json"))
        for op, m, k, n, kind, _ in step_gemms(conf, 4 * 512):
            shapes.add((m, k, n, kind))
    return sorted(shapes)


def test_byte_models_equal_the_programs():
    """The copies keep the program's arithmetic, at every GEMM shape of
    the training cells, on every route, and attention at the cells'
    sequence and head shapes."""
    from repro.kernels import dispatch
    for m, k, n, kind in _cell_gemm_shapes():
        for path in ("fused", "unfused", "jnp", "float"):
            assert ops.bytes_moved(path, m, k, n, kind=kind) == \
                dispatch.bytes_moved(path, m, k, n, kind=kind)
    for gs, t, d in ((7 * 512, 512, 64), (512, 512, 64), (7, 2112, 64)):
        for path in ("fused", "scan"):
            for op in ("attn_fwd", "attn_decode"):
                assert ops.attention_bytes_moved(path, gs, t, d, op=op) == \
                    dispatch.attention_bytes_moved(path, gs, t, d, op=op)


def test_train_ops_per_token_by_hand():
    """qwen2-0.5b at seq 512, counted by hand: per layer q and o 896x896,
    k and v 896x128, gate, up and down 896x4864; 24 layers and the tied
    head 896x151936; 6 ops per weight; attention forward 2 products x 2
    ops x 14 heads x 64 x (512 + 1) / 2 positions per layer, x3 for the
    backward."""
    conf = json.load(open(spec.BENCH / "configs" / "qwen2-0.5b.json"))
    layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    weights = 24 * layer + 896 * 151936
    assert weights == 493_961_216
    attn = 3 * 24 * 2 * 2 * 14 * 64 * 513 / 2
    assert ops.train_ops_per_token(conf, 512) == weights * 6 + attn
    assert ops.train_ops_per_token(conf, 512) == pytest.approx(3.03e9,
                                                               rel=1e-3)


def test_least_time_is_below_every_route():
    """The roofline's bytes are at most what any route's model moves."""
    for m, k, n, kind in _cell_gemm_shapes():
        least = ops.least_bytes(m, k, n, kind)
        assert least <= ops.bytes_moved("fused", m, k, n, kind=kind)
        assert least <= ops.bytes_moved("unfused", m, k, n, kind=kind)
