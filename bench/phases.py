#!/usr/bin/env python3
"""The serving engine's phases on the chip, from its own spans.

    python3 bench/phases.py --workload <cell> --seeds 1,2 --seconds 51 \
        [--cost-seeds 3,4]

Each of ``--seeds`` runs the serving cell as ``bench/run.py --trace 1``
does (set-up, then the window at the cell's own load), with the engine's
span recorder (``Engine.spans``, ``repro/runtime/spans.py``) on from the
window's start to its close and the profiler on the window's middle 10 s
(``run.TraceSlice``).  One JSON line per seed: the phases' medians over
the window's decode steps, the host copy per decode call, the chip's
idle time of the traced slice split among the engine's spans
(``idle_phases``), and the checks that hold the spans to the
benchmark's own clock.  Each of ``--cost-seeds`` runs the window twice
with the profiler off, the recorder off and on (the order alternates by
seed): tokens per second of each, whether the requests finished in both
emitted the same tokens, and the host's cost of one span.  The compile
cache is the one ``bench/run.py`` keeps, so run a cell there first to
fill it.  The benchmark's own runs run none of this.

The functions below need only the record of ``Recorder.stop()`` and
lists of (name, start_ns, end_ns, text) events, so a test can feed them
any trace.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    for _p in (str(ROOT), str(ROOT / "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "engine."
OUTSIDE = "outside engine spans"
COPY_PHASES = ("engine.gather", "engine.scatter")


def read_xspace(path: str, prefixes=("bench.", PROGRAM_PREFIX)):
    """``trace.read_xspace``'s devices and host spans, in one pass over
    the file, with the spans of every name that starts with one of
    ``prefixes`` (the program's own as well as the benchmark's)."""
    from jax.profiler import ProfileData
    devices: Dict[str, List[trace.Event]] = {}
    spans: List[trace.Event] = []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(trace.DEVICE_PREFIX)
        for line in plane.lines:
            if is_device and line.name.startswith(tuple(trace.OP_LINES)):
                devices.setdefault(plane.name, []).extend(
                    (e.name, int(e.start_ns), int(e.end_ns), trace._text(e))
                    for e in line.events if e.duration_ns > 0)
            spans.extend((e.name, int(e.start_ns), int(e.end_ns), "")
                         for e in line.events
                         if e.name.startswith(tuple(prefixes)))
    return devices, spans


def segments(spans: List[trace.Event], lo: int, hi: int):
    """[lo, hi] cut wherever a span starts or ends, each piece named by
    the innermost span that covers it (the latest to start; of two that
    start together, the shorter), ``OUTSIDE`` where none does."""
    inside = [(s, e, n) for n, s, e, _ in spans if e > lo and s < hi]
    cuts = sorted({lo, hi} | {min(max(x, lo), hi)
                              for s, e, _ in inside for x in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, -e, n) for s, e, n in inside if s <= a and e >= b]
        out.append((a, b, max(cover)[2] if cover else OUTSIDE))
    return out


def idle_phases(devices: Dict[str, List[trace.Event]],
                spans: List[trace.Event], lo: int, hi: int) -> List[list]:
    """Seconds of [lo, hi] in which the chip ran nothing, split among the
    innermost program spans that cover each stretch's parts (a stretch
    that straddles two phases is split between them), averaged over the
    devices, the largest first.  They add up to the idle time."""
    segs = segments([s for s in spans if s[0].startswith(PROGRAM_PREFIX)],
                    lo, hi)
    idle_ns: Dict[str, int] = {}
    for evs in devices.values():
        busy = trace.union(trace.clip([(s, e) for _, s, e, _ in evs], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        i = 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            while segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                s, e, name = segs[j]
                idle_ns[name] = idle_ns.get(name, 0) + min(b, e) - max(a, s)
                j += 1
    n = len(devices)
    return [[k, v / n / 1e9] for k, v in
            sorted(idle_ns.items(), key=lambda kv: -kv[1])]


def idle_share(phases: List[list], names, window_s: float) -> Optional[float]:
    """The share of the traced slice in which the chip idled inside one
    of ``names``, in %."""
    if not phases or window_s <= 0:
        return None
    return 100.0 * sum(s for k, s in phases if k in names) / window_s


def durations_ms(record: dict, name: str) -> List[float]:
    return [(e - s) / 1e6 for n, s, e, _, _ in record["spans"] if n == name]


def phase_ms_p50(record: Optional[dict], name: str) -> Optional[float]:
    """The median duration of the record's ``name`` spans, in ms."""
    d = durations_ms(record, name) if record else []
    return statistics.median(d) if d else None


def host_copy_mb_per_step(record: Optional[dict]) -> Optional[float]:
    """Bytes copied into and out of the decode program per decode call,
    in MB (``engine.h2d_bytes`` + ``engine.d2h_bytes`` over the
    ``engine.decode`` spans)."""
    calls = len(durations_ms(record, "engine.decode")) if record else 0
    if not calls:
        return None
    c = record["counters"]
    return (c.get("engine.h2d_bytes", 0) + c.get("engine.d2h_bytes", 0)) \
        / calls / 1e6


def decode_steps(record: dict) -> List[tuple]:
    """(step ms, its children's ms) of each ``engine.step`` that
    admitted nothing."""
    spans = record["spans"]
    kids: Dict[int, List[list]] = {}
    for s in spans:
        kids.setdefault(s[3], []).append(s)
    out = []
    for i, s in enumerate(spans):
        if s[0] != "engine.step":
            continue
        ch = kids.get(i, [])
        if any(c[0] == "engine.admit" for c in ch):
            continue
        out.append(((s[2] - s[1]) / 1e6,
                    sum(c[2] - c[1] for c in ch) / 1e6))
    return out


def summary(record: dict, window: dict, reduced: Optional[dict] = None,
            phases: Optional[List[list]] = None) -> dict:
    """The numbers of one run: each phase's median, the copy per decode
    call, and, for a traced slice, the chip's idle in the cache copy;
    with the checks against the benchmark's step clock."""
    steps = decode_steps(record)
    names = sorted({s[0] for s in record["spans"]})
    out = {
        "phase_ms_p50": {n: phase_ms_p50(record, n) for n in names},
        "host_copy_mb_per_step": host_copy_mb_per_step(record),
        "counters": record["counters"],
        "decode_steps": len(steps),
        "engine_step_ms_p50": (statistics.median(t for t, _ in steps)
                               if steps else None),
        "bench_decode_step_ms_p50": window.get("decode_step_ms_p50"),
        "children_cover_min": (min(c / t for t, c in steps if t > 0)
                               if steps else None),
        "children_cover_p50": (statistics.median(c / t for t, c in steps
                                                 if t > 0)
                               if steps else None),
    }
    if reduced is not None:
        idle_s = reduced["window_s"] - reduced["busy_s"]
        out.update(
            idle_phases=phases,
            idle_phases_s=sum(s for _, s in phases),
            idle_s=idle_s,
            window_s=reduced["window_s"], busy_s=reduced["busy_s"],
            idle_gaps=reduced["idle_gaps"],
            device_ops=reduced["device_ops"],
            idle_in_cache_copy=idle_share(phases, COPY_PHASES,
                                          reduced["window_s"]))
    return out


class PhaseTick:
    """A window's tick: the engine's recorder on from the window's first
    tick to the one at its close (before the drain), and the profiler
    over the middle slice where ``traced``."""

    def __init__(self, run, seconds: float, recorded: bool = True,
                 traced: bool = True):
        from bench.run import TraceSlice
        self.run, self.seconds, self.recorded = run, seconds, recorded
        self.slice = TraceSlice(seconds) if traced else None
        self.record = None

    def __call__(self, elapsed: float) -> None:
        spans = self.run.engine.spans
        if self.recorded and not spans.on and self.record is None:
            spans.start()
        if self.slice is not None:
            self.slice(elapsed)
        if elapsed >= self.seconds and spans.on:
            self.record = spans.stop()

    def reduce(self):
        """(reduced, idle_phases) of the traced slice: ``trace``'s own
        reduction of the device and the benchmark's spans, and the idle
        split by the program's spans."""
        sl = self.slice
        if not sl.done:
            raise RuntimeError("the window ended before its traced slice")
        try:
            devices, spans = read_xspace(trace.find_xspace(sl.logdir))
        finally:
            shutil.rmtree(sl.logdir, ignore_errors=True)
        from bench import spec
        families = spec.kernel_families()
        bench_spans = [s for s in spans if s[0].startswith("bench.")]
        reduced = trace.reduce_events(devices, bench_spans, families)
        win = [(s, e) for n, s, e, _ in bench_spans if n == trace.WINDOW_SPAN]
        lo, hi = win[0]
        return reduced, idle_phases(devices, spans, lo, hi)


def _window(cell, seed: int, prev, recorded: bool, traced: bool, seconds):
    from bench import serve_cell
    run = serve_cell.ServeRun(cell, seed, share=prev)
    run.mix["check_requests"] = 0      # no drain: nothing is checked here
    run.setup()
    tick = PhaseTick(run, seconds, recorded=recorded, traced=traced)
    try:
        w = run.window(seconds, tick)
    finally:
        if tick.slice is not None:
            tick.slice.close()
    return run, tick, w


def span_cost_us(n: int = 100_000) -> dict:
    """The host's cost of one empty span, recorder off and on, in us."""
    from repro.runtime.spans import Recorder
    rec, out = Recorder(), {}
    for label in ("off", "on"):
        if label == "on":
            rec.start()
        t0 = time.perf_counter()
        for _ in range(n):
            with rec.span("engine.cost", rid=1):
                pass
        out[label] = (time.perf_counter() - t0) / n * 1e6
    rec.stop()
    return out


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=lambda t: [int(x) for x in t.split(",")
                                               if x], default=[])
    ap.add_argument("--cost-seeds", type=lambda t: [int(x) for x in
                                                    t.split(",") if x],
                    default=[])
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    import gc
    import jax
    import numpy as np
    from bench import run as bench_run, spec
    bench_run._setup_env()
    cell = spec.load_cell(args.workload)
    bench_run.check_platform(jax.devices(), cell.chips)
    bench_run._enable_cache()
    compiles = bench_run.count_compiles()
    prev = None
    for seed in args.seeds:
        run, tick, w = _window(cell, seed, prev, True, True, args.seconds)
        reduced, phases = tick.reduce()
        _emit(kind="traced", seed=seed, compiled=compiles["compiled"],
              serve_tokens_per_s=w["serve_tokens_per_s"],
              **summary(tick.record, w, reduced, phases))
        prev = run
        run.free()
        gc.collect()
    for k, seed in enumerate(args.cost_seeds):
        got = {}
        for recorded in ((False, True) if k % 2 == 0 else (True, False)):
            run, tick, w = _window(cell, seed, prev, recorded, False,
                                   args.seconds)
            got[recorded] = (w, dict(run.engine.results), tick.record)
            prev = run
            run.free()
            gc.collect()
        (w_off, res_off, _), (w_on, res_on, rec) = got[False], got[True]
        both = sorted(set(res_off) & set(res_on))
        _emit(kind="cost", seed=seed, order="off,on" if k % 2 == 0
              else "on,off",
              tokens_per_s_off=w_off["serve_tokens_per_s"],
              tokens_per_s_on=w_on["serve_tokens_per_s"],
              decode_step_ms_p50_off=w_off["decode_step_ms_p50"],
              decode_step_ms_p50_on=w_on["decode_step_ms_p50"],
              spans_on=len(rec["spans"]), finished_in_both=len(both),
              tokens_equal=all(np.array_equal(res_off[r], res_on[r])
                               for r in both))
    _emit(kind="span_cost_us", compiled=compiles["compiled"],
          device=jax.devices()[0].device_kind, **span_cost_us())
    return 0


if __name__ == "__main__":
    sys.exit(main())
