"""Jaxpr introspection: count representation-mapping ops in a traced step.

The qflow dataflow (docs/DATAFLOW.md) claims to remove redundant
quantize passes between layers.  This module makes that claim measurable:
:func:`count_quantize_ops` traces a function and walks its jaxpr —
recursing through jit / scan / while / cond / remat / custom_vjp call
primitives — counting every call of the named quantization routines
(``core.bfp.quantize``; ``fx_quantize`` and the norm layers route through
it too, so one number covers GEMM and norm quantization alike).

Counts are *execution-weighted*: an op inside a ``lax.scan`` body counts
once per trip (``length`` param), so a quantize hoisted out of the KV-chunk
scan or the layer scan shows up as the multiple it actually saves.  Ops on
the cotangent side of ``jax.grad`` and inside ``jax.checkpoint`` replays
are included — the number is "quantize executions per step", not "call
sites in source".

Used by ``benchmarks/op_microbench.py`` to emit ``BENCH_dataflow.json``
and by the qflow tests to assert the reduction.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = ["count_quantize_ops", "count_weight_quantize_ops",
           "count_cache_quantize_ops", "count_named_calls",
           "health_summary",
           "QUANTIZE_NAMES", "WEIGHT_QUANTIZE_NAMES", "CACHE_QUANTIZE_NAMES"]

# jit call names of the quantization entry points (jitted functions keep their
# Python function name as the jaxpr call name).  Weight-operand
# quantizations route through the separately-named ``quantize_weight``
# wrapper (core.bfp) — same mapping, distinct jaxpr name — so the
# persistent-weight-currency claim ("0 per-GEMM weight quantizes with
# policy.qweights on") is countable.  ``quantize_weight`` calls
# ``quantize`` internally, so counting QUANTIZE_NAMES alone still yields
# the historical all-quantizes total (the walker recurses through the
# un-counted outer call).
QUANTIZE_NAMES = ("quantize",)
WEIGHT_QUANTIZE_NAMES = ("quantize_weight",)
# Cache-row quantizations (the append-time mapping of the decode cache
# currency, ``policy.qcache``) route through ``quantize_cache`` — same
# mapping, distinct jaxpr name — so "the cache is quantized exactly once
# per appended row" is countable per decode step.
CACHE_QUANTIZE_NAMES = ("quantize_cache",)


def _jaxprs_of(eqn) -> Iterable[tuple]:
    """Yield (sub_jaxpr, trip_multiplier) for every jaxpr-valued param."""
    length = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
    for v in eqn.params.values():
        for w in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(w, ClosedJaxpr):
                yield w.jaxpr, length
            elif isinstance(w, Jaxpr):
                yield w, length


def _walk(jaxpr, names, mult: int, counts: Dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name", "") if eqn.primitive.name == "jit" else ""
        if name in names:
            counts[name] = counts.get(name, 0) + mult
            continue                      # a counted call is a leaf
        for sub, length in _jaxprs_of(eqn):
            _walk(sub, names, mult * length, counts)


def count_named_calls(fn: Callable, *args, names=QUANTIZE_NAMES,
                      **kwargs) -> Dict[str, int]:
    """Trace ``fn(*args, **kwargs)`` and count named jit calls, weighted by
    scan trip counts.  Returns {name: executions} plus a "total" key."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: Dict[str, int] = {}
    _walk(jaxpr.jaxpr, tuple(names), 1, counts)
    counts["total"] = sum(counts.values())
    return counts


def count_quantize_ops(fn: Callable, *args, **kwargs) -> int:
    """Quantize executions per call of ``fn`` (see module docstring)."""
    return count_named_calls(fn, *args, names=QUANTIZE_NAMES, **kwargs)["total"]


def count_weight_quantize_ops(fn: Callable, *args, **kwargs) -> int:
    """Per-GEMM *weight* quantize executions per call of ``fn``: the
    quantizations the persistent weight currency (``policy.qweights``)
    eliminates.  Scan-trip-weighted like :func:`count_quantize_ops`."""
    return count_named_calls(fn, *args, names=WEIGHT_QUANTIZE_NAMES,
                             **kwargs)["total"]


def count_cache_quantize_ops(fn: Callable, *args, **kwargs) -> int:
    """Cache-row quantize executions per call of ``fn`` (the append-time
    mapping of ``policy.qcache`` — docs/SERVING.md): one per appended
    KV/state row per decode step, and exactly one per cache tensor at
    prefill.  Scan-trip-weighted like :func:`count_quantize_ops`."""
    return count_named_calls(fn, *args, names=CACHE_QUANTIZE_NAMES,
                             **kwargs)["total"]


def health_summary(report) -> Dict[str, float]:
    """Flatten a ``core.health`` :func:`~repro.core.health.health_report`
    into a plain ``{metric: python scalar}`` dict for telemetry lines and
    the supervisor's guard check (docs/ROBUSTNESS.md).  Group metrics are
    keyed ``<group>/<metric>``; tree-wide aggregates keep their names."""
    out: Dict[str, float] = {
        "max_sat8": float(report["max_sat8"]),
        "min_headroom_bits": int(report["min_headroom_bits"]),
        "nonfinite_grads": int(report["nonfinite_grads"]),
        "loss_finite": bool(report["loss_finite"]),
    }
    for g, metrics in sorted(report.get("groups", {}).items()):
        out[f"{g}/sat8"] = float(metrics["sat8"])
        out[f"{g}/headroom_bits"] = int(metrics["headroom_bits"])
        out[f"{g}/exp_top"] = int(metrics["exp_top"])
        out[f"{g}/nonfinite"] = int(metrics["nonfinite"])
    return out
