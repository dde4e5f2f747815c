#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, makes the weights from the seed, warms up every shape
the window uses (set-up, ``setup_s``), measures for ``--seconds``, then
checks what the timed path produced against the plain reference.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics, with the window's middle profiled.

Only a TPU is measured: with no TPU, or fewer chips than the cell asks
for, the run exits non-zero and prints no result.  JAX's compilation
cache lives in the checkout's ``.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says), so only a checkout's first run of a
cell compiles.

The command itself never touches JAX: it runs the cell in a child
process.  A child whose set-up had to compile stops there, and a fresh
child runs the cell with every program loaded from the cache, so that
the window never runs in a process that compiled.  ``setup_s`` counts
from the command's start, both children included.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the command's start and the child's attempt, passed to the child
LAUNCH_T0 = "BENCH_LAUNCH_T0"
LAUNCH_TRY = "BENCH_LAUNCH_TRY"
# a child's exit code: its set-up compiled, so the cell runs again
RECOMPILED = 75


class NoChip(SystemExit):
    def __init__(self, msg: str):
        print(f"bench: {msg}; nothing is measured off the chip",
              file=sys.stderr, flush=True)
        super().__init__(1)


def check_platform(devices, chips: int) -> None:
    """A TPU with at least the cell's chips, or no run."""
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU (JAX sees {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_env() -> None:
    """Routing from committed code alone: autotune off, and its cache
    file kept inside the checkout and never read from a former run."""
    os.environ.pop("REPRO_KERNEL_AUTOTUNE", None)
    at = ROOT / ".autotune" / "bench.json"
    if at.exists():
        at.unlink()
    os.environ["REPRO_KERNEL_AUTOTUNE_CACHE"] = str(at)


def _enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def count_compiles() -> dict:
    """Counts the programs compiled and written to the persistent cache
    from now on (JAX records an event for each)."""
    import jax
    box = {"compiled": 0}

    def on(event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            box["compiled"] += 1

    jax.monitoring.register_event_listener(on)
    return box


def make_run(cell, seed: int):
    kind = cell.traffic["kind"]
    if kind == "train":
        from bench.train_cell import TrainRun
        return TrainRun(cell, seed)
    if kind == "serve":
        from bench.serve_cell import ServeRun
        return ServeRun(cell, seed)
    raise ValueError(f"traffic kind {kind!r} has no runner")


def per_layer(cell, rec: dict) -> dict:
    from bench import spec
    out = {}
    for m in cell.per_layer:
        val = spec.metric_reader(m["name"])(rec)
        if val is not None:
            out[m["name"]] = {"value": _finite(m["name"], val),
                              "unit": m["unit"]}
    return out


def _finite(name: str, value) -> float:
    value = float(value)
    if not math.isfinite(value):
        raise ValueError(f"{name} is {value}: the window measured nothing")
    return value


def result(args, cell, run, w: dict, setup_s: float, dev: dict,
           checks: dict, reduced) -> dict:
    """The result line.  A compared number that could not be read (no
    finished request to check) stands as the largest float, and fails."""
    limits = cell.limits
    compared = {}
    for k in limits:
        v = float(checks[k]["value"])
        compared[k] = {"value": v if math.isfinite(v) else sys.float_info.max,
                       "limit": limits[k]}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    line = {"correct": bool(correct), "attempted": int(w["attempted"]),
            "failed": int(w["failed"])}
    if args.trace:
        from bench import spec
        rec = {"cell": cell.name, "config": cell.config,
               "traffic": cell.traffic, "window": w, "trace": reduced,
               "peaks": spec.peaks_for(dev["kind"]),
               "run": run.record()}
        line["metrics"] = per_layer(cell, rec)
        dev = dict(dev, busy_s=reduced["busy_s"],
                   window_s=reduced["window_s"])
        line["device"] = dev
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            val = setup_s if m["name"] == "setup_s" else w.get(m["name"])
            if val is None:
                raise KeyError(f"{cell.name}: the runner gives no "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": _finite(m["name"], val),
                                  "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = dev
    line["checks"] = compared
    return line


class TraceSlice:
    """Profiles the middle ``SLICE_S`` seconds of the window (all of a
    shorter one): the device's operations and the benchmark's own spans,
    with no Python call tracing.  A whole serving window's trace takes
    minutes to write and read.  The window's loop calls the object with
    the seconds elapsed, so the slice starts and ends between steps; the
    slice is the ``bench.window`` span that the reduction reads."""

    SLICE_S = 10.0

    def __init__(self, seconds: float):
        length = min(seconds, self.SLICE_S)
        self.start = (seconds - length) / 2
        self.stop = self.start + length
        self.logdir = None
        self.span = None
        self.done = False

    def __call__(self, elapsed: float) -> None:
        import jax
        if self.logdir is None and elapsed >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.logdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
        elif elapsed >= self.stop:
            self.close()

    def close(self) -> None:
        import jax
        if self.logdir is not None and not self.done:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.done = True

    def reduce(self) -> dict:
        from bench import spec, trace
        if not self.done:
            raise RuntimeError("the window ended before its traced slice")
        try:
            devices, spans = trace.read_xspace(trace.find_xspace(self.logdir))
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)
        families = spec.kernel_families()
        out = trace.reduce_events(devices, spans, families)
        for line in trace.dump_op_names(devices, limit=25):
            _log(f"[trace] {line}")
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _log(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    _setup_env()
    from bench import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    check_platform(devices, cell.chips)
    cache = _enable_cache()
    compiles = count_compiles()
    _log(f"[bench] {cell.name} seed {args.seed} on {devices[0].device_kind}"
         f" x{len(devices)}, compile cache {cache}")

    run = make_run(cell, args.seed)
    run.setup()
    if compiles["compiled"] and os.environ.get(LAUNCH_TRY) == "1":
        _log(f"[bench] set-up compiled {compiles['compiled']} programs; "
             f"the cell runs again with them loaded from the cache")
        return RECOMPILED
    setup_s = time.monotonic() - float(os.environ.get(LAUNCH_T0, T0))
    _log(f"[bench] setup_s {setup_s!r} (programs compiled in this process's"
         f" set-up: {compiles['compiled']})")
    compiles["compiled"] = 0

    tracer = TraceSlice(args.seconds) if args.trace else None
    try:
        w = run.window(args.seconds, tracer)
    finally:
        if tracer:
            tracer.close()
    if compiles["compiled"]:
        _log(f"[bench] {compiles['compiled']} programs compiled after "
             f"set-up: the window was not all steady state")
    reduced = tracer.reduce() if tracer else None
    dev = device_info(devices, cell.chips)
    for k, v in w.items():
        if not isinstance(v, (list, dict)):
            _log(f"[window] {k} {v!r}")
    run.free()
    gc.collect()
    checks = run.check()
    line = result(args, cell, run, w, setup_s, dev, checks, reduced)
    for k, v in checks.items():
        if k not in line["checks"]:
            _log(f"[check] {k}: {v}")
    for k, v in line["checks"].items():
        extra = {x: y for x, y in checks[k].items() if x != "value"}
        _log(f"[check] {k} {v['value']!r} limit {v['limit']!r} "
             f"{'ok' if v['value'] <= v['limit'] else 'FAIL'} {extra or ''}")
    print(json.dumps(line), flush=True)
    return 0


def launch(argv) -> int:
    """Runs the cell in a child process, and once more in a fresh one
    where the first child's set-up compiled.  Every child is waited for,
    and ended if this process is told to stop."""
    env = dict(os.environ, **{LAUNCH_T0: repr(T0)})
    cmd = [sys.executable, str(Path(__file__).resolve()), *argv]
    for attempt in ("1", "2"):
        env[LAUNCH_TRY] = attempt
        child = subprocess.Popen(cmd, env=env)
        old = signal.signal(signal.SIGTERM,
                            lambda sig, _: child.send_signal(sig))
        try:
            rc = child.wait()
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            signal.signal(signal.SIGTERM, old)
        if rc != RECOMPILED:
            return rc if rc >= 0 else 128 - rc
    return 1


if __name__ == "__main__":
    sys.exit(main() if LAUNCH_TRY in os.environ else launch(sys.argv[1:]))
