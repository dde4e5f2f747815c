"""From a profiler trace to the per-layer metrics' raw numbers.

The profiler writes an XSpace (``*.xplane.pb``).  Its device planes
(``/device:TPU:<n>``) hold one line of XLA operations; the host plane
holds the benchmark's own spans (``jax.profiler.TraceAnnotation`` names
that start with ``bench.``).  ``reduce_events`` needs only lists of
(name, start_ns, end_ns, text) tuples, so a test can feed it any trace.

Busy time is the union of a device's operation intervals, averaged over
the devices; the window is the ``bench.window`` span.  An operation
belongs to the first kernel family (``spec.kernel_families``:
``kernel_names.json``, then ``bench/kernels/<family>.json``) whose
pattern matches its own HLO name (not its text, which names the operands
that fed it); the rest is XLA's own work.  An idle gap is a stretch of the window in which the device ran
nothing, named by the host span that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int, str]          # name, start_ns, end_ns, text

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
WINDOW_SPAN = "bench.window"
_TEXT_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details", "name")


def find_xspace(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return found[-1]


def op_name(name: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole instruction text ("%int8_matmul_pallas.181 = f32[...] ...")."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """The name without its instance number: "int8_matmul_pallas"."""
    return re.sub(r"\.\d+$", "", op_name(name))


def _text(ev) -> str:
    parts = []
    for key, val in ev.stats:
        if key in _TEXT_STATS and isinstance(val, str):
            parts.append(val)
    return " ".join(parts)


def read_xspace(path: str, device_prefix: str = "",
                op_lines: Sequence[str] = ()):
    """({device plane: [Event]}, [host span Event]) of one trace file.
    Device planes are those whose name starts with ``device_prefix``
    (default ``DEVICE_PREFIX``); their operations are on the lines whose
    name starts with one of ``op_lines`` (default ``OP_LINES``)."""
    from jax.profiler import ProfileData
    device_prefix = device_prefix or DEVICE_PREFIX
    op_lines = tuple(op_lines or OP_LINES)
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        is_device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if is_device and line.name.startswith(op_lines):
                evs = devices.setdefault(plane.name, [])
                evs.extend((e.name, int(e.start_ns), int(e.end_ns), _text(e))
                           for e in line.events if e.duration_ns > 0)
            spans.extend((e.name, int(e.start_ns), int(e.end_ns), "")
                         for e in line.events if e.name.startswith("bench."))
    return devices, spans


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def family_of(name: str, families: Dict[str, List[str]]) -> Optional[str]:
    """The kernel family whose pattern matches the operation's own name
    (never its operands, which name the kernels that fed it)."""
    op = op_name(name)
    for fam, patterns in families.items():
        if any(re.search(pat, op) for pat in patterns):
            return fam
    return None


def self_times(events: List[Event]) -> List[Tuple[Event, int]]:
    """Each event with its own time: its duration less that of the events
    nested in it (a TPU trace shows a while loop and the operations of its
    body on one line)."""
    out: List[List] = []
    stack: List[List] = []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            stack.pop()
        item = [ev, ev[2] - ev[1]]
        if stack:
            stack[-1][1] -= min(ev[2], stack[-1][0][2]) - ev[1]
        stack.append(item)
        out.append(item)
    return [(ev, t) for ev, t in out]


def _span_for(gap: Tuple[int, int], spans: List[Event]) -> str:
    best, best_overlap = "outside bench spans", 0
    for name, s, e, _ in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce_events(devices: Dict[str, List[Event]], spans: List[Event],
                  families: Dict[str, List[str]], top: int = 10) -> dict:
    """busy_s and window_s, seconds per kernel family and outside any
    (``xla``), the kinds of device operation that took most time and the
    longest idle gaps, times averaged over the devices.  Times are self
    times, so nested operations count once."""
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operations")
    win = [(s, e) for n, s, e, _ in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for evs in devices.values() for _, s, _, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e, _ in evs)
    n_dev = len(devices)
    busy_ns = 0
    fam_ns: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    for evs in devices.values():
        busy = union(clip([(s, e) for _, s, e, _ in evs], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        inside = [(n, max(s, lo), min(e, hi), t) for n, s, e, t in evs
                  if min(e, hi) > max(s, lo)]
        for (name, _, _, _), dur in self_times(inside):
            fam = family_of(name, families) or "xla"
            fam_ns[fam] = fam_ns.get(fam, 0) + dur
            kind = op_kind(name)
            op_ns[kind] = op_ns.get(kind, 0) + dur
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                who = _span_for((gs, ge), spans)
                gap_ns[who] = gap_ns.get(who, 0) + (ge - gs)
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "family_s": {k: v / n_dev / 1e9 for k, v in fam_ns.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in ops],
        "idle_gaps": [[k, v / n_dev / 1e9] for k, v in gaps],
        "n_devices": n_dev,
    }


def dump_op_names(devices: Dict[str, List[Event]], limit: int = 60) -> List[str]:
    """The most frequent operation names with their text, for a look by
    hand at how a backend names kernels."""
    seen: Dict[str, int] = {}
    example: Dict[str, str] = {}
    for evs in devices.values():
        for ev, t in self_times(evs):
            k = op_kind(ev[0])
            seen[k] = seen.get(k, 0) + t
            example.setdefault(k, ev[0][:160])
    top = sorted(seen.items(), key=lambda kv: -kv[1])[:limit]
    return [f"{ns / 1e6:.3f} ms  {k}  | {example[k]}" for k, ns in top]


def idle_pct(reduced: Optional[dict]) -> Optional[float]:
    """The share of the traced window in which the chip ran nothing, %."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
