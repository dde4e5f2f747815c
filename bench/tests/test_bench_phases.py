"""The engine-phase reduction of ``bench/phases.py``, on the CPU."""

import types

import pytest

from bench import phases, trace

DEV = "/device:TPU:0"


def _hand_trace():
    """A window of 100 ns.  The chip runs 10..20 and 40..45.  One step
    (0..80) holds the gather (2..30), the decode (30..50) and the scatter
    (50..70); the benchmark's span around the step is no program span."""
    devices = {DEV: [("%fusion.1 = f32[8] fusion(...)", 10, 20, ""),
                     ("%fusion.2 = f32[8] fusion(...)", 40, 45, "")]}
    spans = [("bench.window", 0, 100, ""), ("bench.engine_step", 0, 80, ""),
             ("engine.step", 0, 80, ""), ("engine.gather", 2, 30, ""),
             ("engine.decode", 30, 50, ""), ("engine.scatter", 50, 70, "")]
    return devices, spans


def test_idle_phases_by_hand():
    """Idle 0..10 goes 2 to the step and 8 to the gather (nested spans go
    to the innermost); 20..40 straddles gather and decode, 10 each;
    45..100 gives the decode 5, the scatter 20, the step 10 and 20 to no
    program span."""
    devices, spans = _hand_trace()
    got = dict(phases.idle_phases(devices, spans, 0, 100))
    want = {"engine.step": 12, "engine.gather": 18, "engine.decode": 15,
            "engine.scatter": 20, phases.OUTSIDE: 20}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(85e-9)
    assert phases.idle_share(phases.idle_phases(devices, spans, 0, 100),
                             phases.COPY_PHASES, 100e-9) == \
        pytest.approx(38.0)


def test_idle_phases_average_over_devices_and_ties():
    """A second device busy all window halves every share; of two spans
    with the same start the shorter is the innermost."""
    devices, spans = _hand_trace()
    devices["/device:TPU:1"] = [("%fusion.3 = f32[8] fusion(...)", 0, 100,
                                 "")]
    spans = spans + [("engine.keys", 0, 1, "")]
    got = dict(phases.idle_phases(devices, spans, 0, 100))
    assert got["engine.keys"] == pytest.approx(0.5e-9)
    assert got["engine.step"] == pytest.approx(5.5e-9)
    assert sum(got.values()) == pytest.approx(42.5e-9)


def test_idle_gaps_by_hand_unchanged():
    """The reduction of the benchmark's spans alone, as the phase run
    hands it the spans, reads the numbers of ``test_reduce_events_by_hand``."""
    from bench.tests.test_bench_trace import FAM
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
             ("bench.loss_fetch", 55, 100, ""), ("engine.step", 0, 100, ""),
             ("engine.scatter", 60, 100, "")]
    bench_spans = [s for s in spans if s[0].startswith("bench.")]
    r = trace.reduce_events(devices, bench_spans, FAM)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"bench.loss_fetch", "bench.step"}
    assert gaps["bench.loss_fetch"] == pytest.approx(90e-9 / 2)
    assert gaps["bench.step"] == pytest.approx(5e-9 / 2)
    got = dict(phases.idle_phases(devices, spans, 0, 100))
    idle = r["window_s"] - r["busy_s"]
    assert sum(got.values()) == pytest.approx(idle)
    # 60..100: device 0 idle 60..70 and 80..100, device 1 all of it
    assert got["engine.scatter"] == pytest.approx((30e-9 + 40e-9) / 2)


def _record():
    """Two decode steps and one that admitted (ms: 1e6 ns), with the
    counters of two decode calls of 8 full lanes at qwen2-0.5b's cache."""
    ms = 1_000_000
    spans = [
        ["engine.step", 0, 100 * ms, -1, {}],
        ["engine.reserve", 0, 1 * ms, 0, {}],
        ["engine.gather", 1 * ms, 31 * ms, 0, {"lanes": 8}],
        ["engine.keys", 31 * ms, 33 * ms, 0, {}],
        ["engine.decode", 33 * ms, 60 * ms, 0, {}],
        ["engine.scatter", 60 * ms, 98 * ms, 0, {}],
        ["engine.step", 100 * ms, 300 * ms, -1, {}],
        ["engine.admit", 100 * ms, 200 * ms, 6, {"rid": 4}],
        ["engine.prefill", 100 * ms, 190 * ms, 7, {"rid": 4}],
        ["engine.step", 300 * ms, 420 * ms, -1, {}],
        ["engine.reserve", 300 * ms, 301 * ms, 9, {}],
        ["engine.gather", 301 * ms, 351 * ms, 9, {"lanes": 8}],
        ["engine.keys", 351 * ms, 352 * ms, 9, {}],
        ["engine.decode", 352 * ms, 380 * ms, 9, {}],
        ["engine.scatter", 380 * ms, 420 * ms, 9, {}],
        ["engine.queued", 10 * ms, 100 * ms, -1, {"rid": 4}],
    ]
    lane = 16_711_680
    counters = {"engine.h2d_bytes": 2 * (8 * lane + 8 * 16),
                "engine.d2h_bytes": 2 * (8 * lane + 8 * 4),
                "engine.lanes": 16, "engine.pad_lanes": 0}
    return {"spans": spans, "counters": counters}


def test_readers_on_a_record():
    rec = _record()
    assert phases.phase_ms_p50(rec, "engine.gather") == pytest.approx(40.0)
    assert phases.phase_ms_p50(rec, "engine.decode") == pytest.approx(27.5)
    assert phases.phase_ms_p50(rec, "engine.scatter") == pytest.approx(39.0)
    assert phases.phase_ms_p50(rec, "engine.none") is None
    assert phases.phase_ms_p50(None, "engine.gather") is None
    # 2 x 8 x 16,711,680 B and 16 B of tokens, positions and keys in,
    # 4 B of tokens out, per lane
    assert phases.host_copy_mb_per_step(rec) == pytest.approx(
        (2 * 8 * 16_711_680 + 8 * 20) / 1e6)
    assert round(phases.host_copy_mb_per_step(rec), 1) == 267.4
    assert phases.host_copy_mb_per_step({"spans": [], "counters": {}}) \
        is None
    assert phases.decode_steps(rec) == [(100.0, 98.0), (120.0, 120.0)]
    s = phases.summary(rec, {"decode_step_ms_p50": 110.0})
    assert s["engine_step_ms_p50"] == pytest.approx(110.0)
    assert s["children_cover_min"] == pytest.approx(0.98)
    assert s["decode_steps"] == 2 and "idle_in_cache_copy" not in s
    assert phases.idle_share([], phases.COPY_PHASES, 1.0) is None


def test_idle_phases_of_a_recorded_trace(tmp_path, monkeypatch):
    """A profile recorded here, with the CPU client's threads standing in
    for the device: the program's spans are read beside the benchmark's,
    and the idle split adds up to the window's idle time."""
    import jax
    import jax.numpy as jnp
    from repro.runtime.spans import Recorder
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    rec = Recorder()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with rec.span("engine.step"):
                    with rec.span("engine.gather", lanes=8):
                        y = x + 1
                    with rec.span("engine.decode"):
                        f(y).block_until_ready()
    monkeypatch.setattr(trace, "DEVICE_PREFIX", "/host:CPU")
    monkeypatch.setattr(trace, "OP_LINES", ("tf_XLAPjRtCpuClient",))
    devices, spans = phases.read_xspace(trace.find_xspace(str(tmp_path)))
    names = {n for n, *_ in spans}
    assert {"bench.window", "engine.step", "engine.gather",
            "engine.decode"} <= names
    (lo, hi), = [(s, e) for n, s, e, _ in spans if n == "bench.window"]
    bench_spans = [s for s in spans if s[0].startswith("bench.")]
    r = trace.reduce_events(devices, bench_spans, {})
    got = phases.idle_phases(devices, spans, lo, hi)
    assert {k for k, _ in got} <= names | {phases.OUTSIDE}
    idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in got) == pytest.approx(idle, rel=1e-6)


def test_tick_records_the_window_before_the_drain():
    """The recorder turns on at the window's first tick and its record
    is taken at the tick of the close; untraced, no profiler runs."""
    from repro.runtime.spans import Recorder
    eng = types.SimpleNamespace(spans=Recorder())
    tick = phases.PhaseTick(types.SimpleNamespace(engine=eng), 5.0,
                            traced=False)
    tick(0.0)
    with eng.spans.span("engine.step"):
        pass
    tick(5.2)
    with eng.spans.span("engine.step"):
        pass
    assert not eng.spans.on and tick.slice is None
    assert [s[0] for s in tick.record["spans"]] == ["engine.step"]
    off = phases.PhaseTick(types.SimpleNamespace(engine=eng), 5.0,
                           recorded=False, traced=False)
    off(0.0)
    off(5.2)
    assert off.record is None and not eng.spans.on
