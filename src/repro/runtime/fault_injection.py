"""Deterministic fault injection for the robustness harness.

Chaos engineering for the integer pipeline: every failure mode the
supervisor and the dispatch degradation ladder claim to survive has a
seeded, reproducible injector here, so ``tools/chaos_smoke.py`` and the
tier-1 robustness tests can *prove* recovery instead of asserting it
(docs/ROBUSTNESS.md §Chaos harness).

Injector families:

  * State corruption — :func:`corrupt_master_exponent` (exponent blow-up
    ⇒ Inf at dequantize ⇒ genuine NaN loss/grads through the real
    pipeline), :func:`flip_mantissa_bits` (seeded bit flips in the int16
    masters, the silent-corruption model), :func:`nan_carrier` (NaN the
    float32 gradient carriers directly).
  * Kernel failure — :func:`arm_kernel_failure` arms a count-based trip
    wire that ``kernels.dispatch`` checks before launching a fused or
    unfused Pallas kernel (:func:`maybe_fail_kernel`); the armed call
    raises :class:`InjectedKernelFailure`, driving the fused→unfused→jnp
    degradation ladder exactly as a real compile/runtime failure would.
  * Cluster faults — :class:`SimClock` (manually advanced monotonic clock
    for ``Heartbeat`` timeout tests) and :class:`HostSim` (a scripted
    fleet: per-host step durations + a death schedule) let the supervisor
    observe a dead host / straggler without any real multi-host runtime.

Everything is deterministic: injectors take explicit seeds/steps, never
wall-clock or global RNG, so a chaos run is exactly replayable.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from repro.core.bfp import BFP

__all__ = [
    "InjectedKernelFailure", "arm_kernel_failure", "clear_kernel_failure",
    "kernel_failure_armed", "maybe_fail_kernel",
    "corrupt_master_exponent", "flip_mantissa_bits", "nan_carrier",
    "SimClock", "HostSim", "FaultPlan",
    "flip_pool_page_bits", "stall_lane", "lane_stalled",
    "clear_lane_stalls", "ServingFaultPlan",
]


# ---------------------------------------------------------------------------
# kernel-failure trip wire (consumed by kernels.dispatch)
# ---------------------------------------------------------------------------

class InjectedKernelFailure(RuntimeError):
    """Raised by an armed kernel launch — stands in for a Pallas
    compile/runtime failure in tests and chaos runs."""


# module-level arming state: {path_name ("fused"/"unfused"/"any"): remaining
# trigger count}.  -1 = fail every launch until cleared.
_armed: Dict[str, int] = {}


def arm_kernel_failure(path: str = "any", count: int = 1) -> None:
    """Arm the next ``count`` kernel launches on ``path`` to raise
    :class:`InjectedKernelFailure` (``count=-1``: every launch until
    :func:`clear_kernel_failure`).  ``path`` is "fused", "unfused", or
    "any"."""
    _armed[path] = count


def clear_kernel_failure() -> None:
    _armed.clear()


def kernel_failure_armed() -> bool:
    return any(c != 0 for c in _armed.values())


def maybe_fail_kernel(path: str) -> None:
    """Dispatch-side hook: called immediately before a fused or unfused
    Pallas kernel launch.  Decrements and raises if armed for ``path``."""
    for key in (path, "any"):
        c = _armed.get(key, 0)
        if c != 0:
            if c > 0:
                _armed[key] = c - 1
            raise InjectedKernelFailure(
                f"injected kernel failure (path={path}, armed={key})")


# ---------------------------------------------------------------------------
# state-corruption injectors
# ---------------------------------------------------------------------------

def _leaf_paths(tree) -> List[Tuple[tuple, BFP]]:
    return [(p, l) for p, l in jax.tree_util.tree_leaves_with_path(
        tree, is_leaf=lambda x: isinstance(x, BFP))
        if isinstance(l, BFP)]


def corrupt_master_exponent(masters, leaf_index: int = 0,
                            bump: int = 200):
    """Blow up one master leaf's shared exponent by ``bump`` biased steps.

    ``dequantize`` of the corrupted leaf overflows float32 (2^(E+bump) ×
    int16 mantissa ⇒ Inf), so the *real* forward pass produces Inf/NaN
    loss and gradients — the genuine carrier-NaN failure mode, not a
    synthetic one.  Returns a new masters tree (input is not mutated)."""
    leaves = _leaf_paths(masters)
    path, leaf = leaves[leaf_index % len(leaves)]
    bad = BFP(leaf.m, leaf.e + np.asarray(bump, leaf.e.dtype), leaf.cfg,
              leaf.g)

    def replace(p, x):
        return bad if p == path else x
    return jax.tree_util.tree_map_with_path(
        replace, masters, is_leaf=lambda x: isinstance(x, BFP))


def flip_mantissa_bits(masters, seed: int, n_flips: int = 8,
                       leaf_index: int = 0):
    """Flip ``n_flips`` seeded random bits in one master leaf's integer
    mantissas — the silent-corruption model (DRAM fault, torn write).
    Deterministic in ``seed``; returns a new masters tree."""
    leaves = _leaf_paths(masters)
    path, leaf = leaves[leaf_index % len(leaves)]
    m = np.array(leaf.m)
    rng = np.random.Philox(seed)
    gen = np.random.Generator(rng)
    flat = m.reshape(-1)
    idx = gen.integers(0, flat.size, size=n_flips)
    bits = gen.integers(0, 8 * m.dtype.itemsize - 1, size=n_flips)
    for i, b in zip(idx, bits):
        flat[i] = flat[i] ^ np.asarray(1 << int(b), m.dtype)
    bad = BFP(jax.numpy.asarray(m), leaf.e, leaf.cfg, leaf.g)

    def replace(p, x):
        return bad if p == path else x
    return jax.tree_util.tree_map_with_path(
        replace, masters, is_leaf=lambda x: isinstance(x, BFP))


def nan_carrier(masters, leaf_index: int = 0):
    """Poison one master leaf's float32 gradient carrier with NaN (only
    meaningful under ``policy.qweights`` where carriers exist); falls back
    to :func:`corrupt_master_exponent` when the leaf has no carrier."""
    leaves = _leaf_paths(masters)
    path, leaf = leaves[leaf_index % len(leaves)]
    if leaf.g is None:
        return corrupt_master_exponent(masters, leaf_index)
    bad = BFP(leaf.m, leaf.e, leaf.cfg,
              jax.numpy.full_like(leaf.g, jax.numpy.nan))

    def replace(p, x):
        return bad if p == path else x
    return jax.tree_util.tree_map_with_path(
        replace, masters, is_leaf=lambda x: isinstance(x, BFP))


# ---------------------------------------------------------------------------
# serving-fault injectors (consumed by launch.engine / tools/chaos_smoke.py)
# ---------------------------------------------------------------------------

def flip_pool_page_bits(pool, pid: int, seed: int,
                        n_flips: int = 8) -> None:
    """Flip ``n_flips`` seeded random mantissa bits inside physical page
    ``pid`` of a :class:`~repro.runtime.qpool.QPool` — the serving-side
    silent-corruption model (a DRAM fault in the block-paged qcache).

    The flips rewrite the page through ``QPool.xor_page`` and
    deliberately do NOT touch the recorded per-page checksum, so
    ``scan_integrity`` must catch the mismatch.  Deterministic in
    ``seed``."""
    role = pool._role.get(pid)
    store = pool._slots if role == "slot" else pool._paged
    if not store:
        store = pool._slots or pool._paged
    gen = np.random.Generator(np.random.Philox(seed))
    names = sorted(store)
    masks = {}
    for _ in range(n_flips):
        name = names[int(gen.integers(0, len(names)))]
        parts = store[name]
        # prefer mantissas; fall back to whatever integer part exists
        pname = "m" if "m" in parts else sorted(parts)[0]
        arr = parts[pname]
        flat = masks.setdefault((name, pname), np.zeros(
            int(np.prod(arr.shape[1:])), arr.dtype))
        i = int(gen.integers(0, flat.size))
        b = int(gen.integers(0, 8 * flat.dtype.itemsize - 1))
        flat[i] = flat[i] ^ np.asarray(1 << b, flat.dtype)
    for (name, pname), flat in masks.items():
        pool.xor_page(pid, name, pname, flat)


# lanes currently stalled by injection: the engine skips a stalled lane's
# decode entirely (it makes no progress, exactly like a hung device), so
# only the guard's stall watchdog can get it moving again.
_stalled_lanes: Set[int] = set()


def stall_lane(rid: int) -> None:
    """Stall sequence ``rid``: from now on the engine schedules no decode
    work for it.  Persists until :func:`clear_lane_stalls` — which the
    guard's recovery path calls for the lane it retries, standing in for
    tearing down and re-creating the lane's device work."""
    _stalled_lanes.add(rid)


def lane_stalled(rid: int) -> bool:
    return rid in _stalled_lanes


def clear_lane_stalls(rid: Optional[int] = None) -> None:
    if rid is None:
        _stalled_lanes.clear()
    else:
        _stalled_lanes.discard(rid)


@dataclasses.dataclass(frozen=True)
class ServingFaultPlan:
    """Declarative chaos schedule for one serving run, applied by
    ``tools/chaos_smoke.py --serving`` between ``Engine.step`` calls.

    ``corrupt_step``: flip mantissa bits in one of ``corrupt_rid``'s pool
    pages after that step.  ``stall_step``: stall ``stall_rid``'s lane.
    ``kernel_fail_step``: arm an any-path kernel failure (the dispatch
    ladder absorbs it at trace time).  ``crash_step``: snapshot + kill the
    engine after that step and restore into a fresh one."""

    corrupt_step: Optional[int] = None
    corrupt_rid: int = 0
    corrupt_seed: int = 0xDECAF
    stall_step: Optional[int] = None
    stall_rid: int = 0
    kernel_fail_step: Optional[int] = None
    crash_step: Optional[int] = None


# ---------------------------------------------------------------------------
# cluster-fault simulators
# ---------------------------------------------------------------------------

class SimClock:
    """Manually-advanced monotonic clock, injectable into ``Heartbeat``."""

    def __init__(self, start: float = 0.0):
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Declarative chaos schedule for one training run.

    ``nan_step``: corrupt the masters *after* committing that step (the
    guard trips on the next step's report).  ``kill_host_step``: stop
    beating ``kill_host`` from that step on.  ``kernel_fail_step``: arm
    one fused-kernel failure at that step."""

    nan_step: Optional[int] = None
    nan_leaf: int = 0
    kill_host_step: Optional[int] = None
    kill_host: int = 1
    kernel_fail_step: Optional[int] = None
    flip_step: Optional[int] = None
    flip_seed: int = 0xC0FFEE


class HostSim:
    """Scripted fleet: drives ``Heartbeat``/``StragglerMonitor`` without a
    real multi-host runtime.  Hosts beat every step unless dead; per-host
    step durations come from a fixed table (stragglers are just slow
    entries)."""

    def __init__(self, hosts: Sequence[int], clock: SimClock,
                 step_seconds: Optional[Dict[int, float]] = None):
        self.hosts = list(hosts)
        self.clock = clock
        self.durations = dict(step_seconds or {})
        self._dead: Set[int] = set()

    def kill(self, host: int) -> None:
        self._dead.add(host)

    def alive(self) -> List[int]:
        return [h for h in self.hosts if h not in self._dead]

    def tick(self, heartbeat, monitor=None,
             base_seconds: float = 1.0) -> None:
        """One step boundary: advance the clock by the slowest live host's
        step time, beat every live host, record durations."""
        durs = {h: self.durations.get(h, base_seconds) for h in self.alive()}
        self.clock.advance(max(durs.values(), default=base_seconds))
        for h, d in durs.items():
            heartbeat.beat(h)
            if monitor is not None:
                monitor.record(h, d)
