"""Shape-keyed dispatch between fused-Pallas, unfused-Pallas and jnp paths.

``core.qops`` routes every integer contraction (``qmatmul`` / ``qbmm``
forward and both Appendix-A.2 backward GEMMs) through :func:`plan_contract`,
which picks one of three execution paths.  Contractions come in five
operand kinds: ``qq`` (both operands quantized in-op), ``qi``/``iq`` (one
operand pre-quantized — a stored residual, a q-in BFP activation from the
qflow dataflow, or a persistent BFP weight against a fresh activation),
``ii`` (both pre-quantized residuals, the backward dW) and ``pp`` (the
fully-pre-quantized *forward*: a q-in activation against a derived /
load-time-quantized weight — the persistent weight currency of
docs/DATAFLOW.md §Weight currency, with its own autotune keys).  The
decode cache currency (``policy.qcache`` — docs/SERVING.md) reuses the
``qi``/``pp`` kinds for its cache-operand contractions, planned under the
ops ``qdecode_qk`` / ``qdecode_pv`` (decode-shaped contractions get their
own shape keys in the autotune cache; :func:`cache_operand_bytes` is the
matching traffic model behind the BENCH_dataflow decode rows).
Pre-quantized entry points skip the quantize stage for that operand:

  ``fused``    one ``pallas_call`` from ``kernels.fused_linear``: in-VMEM
               quantization feeding the MXU, no intermediate HBM round-trip.
  ``unfused``  ``bfp_quant`` kernel -> HBM int8 -> ``int8_matmul`` kernel
               (the pre-dispatch pipeline; kept as the fallback when the
               fused kernel's VMEM residency budget doesn't fit).
  ``jnp``      the pure-jnp emulation in ``core.qops`` — the bit-exact
               correctness oracle and the default on non-TPU backends.

Routing rules (see docs/KERNELS.md for the full table):

  * ``kernel_mode="jnp"`` or bits != 8 -> jnp (kernels are int8-only);
  * ``kernel_mode="auto"`` -> fused on TPU when feasible, jnp elsewhere
    (interpret-mode emulation is for validation, not speed);
  * ``kernel_mode="fused"``/``"unfused"`` force a kernel path (interpret
    mode off-TPU), degrading fused -> unfused -> jnp when shapes/VMEM
    disallow;
  * fused per-tensor needs K <= min(accum_chunk, int32-overflow bound);
    per-block contractions are fused-or-jnp (the unfused quantizer kernel
    only does per-row-strip scales, not per-K-block).

All three paths are *bit-identical* for per-tensor scale: they consume the
same `core.bfp.rounding_bits` draw, run the same threshold-compare rounding,
accumulate exactly in int32 and apply the same single f32 scale multiply.

The row-strip height ``bm`` of the fused kernel comes from the shape-keyed
autotune cache (``kernels.autotune``).  Decisions can be observed with
:func:`record_decisions` (used by the dispatch-introspection tests), and
:func:`bytes_moved` is the analytic HBM-traffic model behind the
``BENCH_kernels.json`` perf trail.

Planning picks the *intended* path; execution defends it.  A kernel launch
that fails — a compile/runtime error, a poisoned autotune entry, or an
armed ``runtime.fault_injection`` trip wire — degrades one rung down the
same ladder (fused -> unfused -> jnp) instead of aborting the job: the
failed fused block height is quarantined in the autotune cache, the
degraded Decision is recorded with the failure as its reason, and
:func:`fallback_counts` exposes the transition counters to the training
supervisor's telemetry (docs/ROBUSTNESS.md §Degradation ladder).  Because
all rungs are bit-identical, degradation changes cost, never results.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.bfp import (BFP, PER_TENSOR, QuantConfig, pow2, rounding_bits,
                        storage_dtype)
from ..core.bfp import quantize as bfp_quantize
from ..runtime import fault_injection as _fi
from ..runtime.sharding import current_mesh
from . import autotune, ref
from .bfp_quant import bfp_quantize_pallas
from .fused_linear import (fused_gemm_epi_pallas, fused_ii_pt_pallas,
                           fused_qi_pt_pallas, fused_qq_blk_pallas,
                           fused_qq_pt_pallas, gemm_epi_ref)
from .int8_matmul import int8_matmul_pallas
from .tile import round_up

__all__ = [
    "FUSED", "UNFUSED", "JNP", "Decision", "plan_contract",
    "plan_attention", "record_decisions", "contract_qq", "contract_qi",
    "contract_iq", "contract_ii", "contract_pp", "bytes_moved",
    "attention_bytes_moved", "attn_block_t", "cache_operand_bytes",
    "paged_gather_bytes", "plan_batched_decode",
    "speculative_verify_bytes_moved", "plan_speculative_verify",
    "fallback_counts", "reset_fallback_counts",
    "DEFAULT_VMEM_BUDGET",
    "plan_norm_gemm", "run_norm_gemm", "plan_epilogue", "contract_epi",
    "plan_decode_block", "run_decode_block", "norm_gemm_bytes_moved",
    "epilogue_bytes_moved", "decode_block_bytes_moved",
]

FUSED = "fused"
UNFUSED = "unfused"
JNP = "jnp"

# Residency budget for one fused-kernel instance.  Mosaic's default scoped
# VMEM limit on TPU v5e is 16 MiB; the estimates count double buffering
# and in-kernel temporaries, and the rest is headroom for the compiler.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

# GSPMD does not partition a Mosaic kernel: inside a jit over a multi-
# device mesh (bound by ``runtime.sharding.use_rules``) a kernel call fails
# to lower, so compiled plans there take the jnp path instead.
UNPARTITIONED = "Mosaic kernels are not partitioned over a multi-device mesh"


def _on_multi_device_mesh() -> bool:
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


_LANE = 128       # last-dim tile multiple
_INT8_SUBLANE = 32


@dataclasses.dataclass(frozen=True)
class Decision:
    """One routing decision, recorded per traced contraction."""

    op: str            # e.g. "qmatmul_fwd", "qmatmul_dx", "attn_fwd"
    path: str          # FUSED | UNFUSED | JNP
    reason: str
    m: int
    k: int
    n: int
    bm: int = 0        # fused row-strip height (0 when not fused)
    interpret: bool = False
    kind: str = "qq"   # operand kind: qq | qi | iq | ii | pp
    bt: int = 0        # fused-attention KV block size (attention ops only)
    atkey: str = ""    # autotune shape key (fused plans): quarantine target


_decision_log: Optional[List[Decision]] = None

# Degradation-ladder counters: {"fused->unfused": n, ...} — every kernel
# launch that failed (compile/runtime error or an armed fault injector) and
# was re-executed one rung down.  Observed by the supervisor's telemetry
# and the chaos harness (docs/ROBUSTNESS.md §Degradation ladder).
_fallback_counts: dict = {}


def fallback_counts() -> dict:
    """Snapshot of the degradation-ladder counters since the last reset."""
    return dict(_fallback_counts)


def reset_fallback_counts() -> None:
    _fallback_counts.clear()


# Ops administratively disabled by the serving guard's degradation ladder
# (docs/ROBUSTNESS.md §Serving resilience): a plan that would have been
# FUSED is issued as JNP with the OP_DISABLED reason instead, so retraces
# run the chain's bit-exact jnp mirror without ever launching the failing
# kernel again.  The reason lets the chain call sites distinguish "declined
# (use the per-op path)" from "disabled (stay on the chain, mirror rung)" —
# the mirror is bit-exact to the kernel, so outputs are unchanged; the
# per-op path is a different numerics contract.
OP_DISABLED = "op disabled by serving guard"
_disabled_ops: set = set()


def disable_op(op: str) -> None:
    """Administratively pin ``op`` (e.g. ``"qdecode_block"``) to its jnp
    mirror on every subsequent plan."""
    _disabled_ops.add(op)


def enable_ops() -> None:
    """Re-enable every administratively disabled op."""
    _disabled_ops.clear()


def disabled_ops() -> set:
    return set(_disabled_ops)


@contextlib.contextmanager
def record_decisions():
    """Collect every Decision planned while the context is open.

    Planning happens at trace time, so wrap the *first* call of a jitted
    function (cached retraces plan nothing).
    """
    global _decision_log
    prev = _decision_log
    _decision_log = log = []
    try:
        yield log
    finally:
        _decision_log = prev


def _record(d: Decision) -> Decision:
    if _decision_log is not None:
        _decision_log.append(d)
    return d


# ---------------------------------------------------------------------------
# degradation ladder: fused -> unfused -> jnp on kernel failure
# ---------------------------------------------------------------------------

def _degrade(dec: Decision, err: BaseException,
             cfg: Optional[QuantConfig]) -> Decision:
    """One rung down the ladder after a failed kernel launch.

    A failed *fused* launch quarantines its autotuned block height (the
    poisoned-cache-entry case: subsequent plans re-tune instead of raising
    on every call) and retries on the unfused pipeline when that pipeline
    can serve the operands — per-tensor scale AND (pre-quantized operands
    or a stochastic config; the standalone quantizer kernel is SR-only) —
    else drops straight to the jnp oracle.  A failed *unfused* launch drops
    to jnp.  All rungs are bit-identical (module docstring), so degrading
    changes cost, never results.  The degraded Decision is recorded like a
    planned one, with the failure in ``reason``.
    """
    if dec.path == FUSED:
        if dec.atkey and dec.bm:
            try:
                autotune.quarantine(dec.atkey, dec.bm)
            except OSError:
                pass                       # cache write failure is non-fatal
        per_tensor = cfg is None or cfg.block == PER_TENSOR
        # Cross-op chains (norm_gemm / *_epi / decode_block) have no unfused
        # middle pipeline: their terminal rung is the bit-exact jnp mirror.
        gemm_kind = dec.kind in ("qq", "qi", "iq", "ii", "pp")
        unfused_ok = gemm_kind and per_tensor and (
            dec.kind in ("ii", "pp") or (cfg is not None and cfg.stochastic))
        to = UNFUSED if unfused_ok else JNP
    else:
        to = JNP
    edge = f"{dec.path}->{to}"
    _fallback_counts[edge] = _fallback_counts.get(edge, 0) + 1
    reason = f"fallback from {dec.path}: {type(err).__name__}: {err}"
    return _record(dataclasses.replace(dec, path=to, reason=reason, bm=0))


def _with_ladder(dec: Decision, run_kernel, run_jnp,
                 cfg: Optional[QuantConfig] = None):
    """Execute ``run_kernel(dec)`` with fused->unfused->jnp degradation.

    ``run_kernel`` handles the FUSED and UNFUSED paths of one contraction;
    ``run_jnp(dec)`` is its bit-identical jnp mirror (the terminal rung —
    plain jnp ops cannot fail to compile).  The fault-injection trip wire
    (``runtime.fault_injection.maybe_fail_kernel``) fires here, exactly
    where a real Pallas failure would surface.
    """
    while dec.path != JNP:
        try:
            _fi.maybe_fail_kernel(dec.path)
            return run_kernel(dec)
        except Exception as err:           # compile/runtime/injected failure
            dec = _degrade(dec, err, cfg)
    return run_jnp(dec)


def _jnp_matmul(am: jnp.ndarray, bmant: jnp.ndarray, ea, eb,
                pa: int, pb: int) -> jnp.ndarray:
    """jnp mirror of :func:`_matmul_unfused`: int8 contraction-last
    mantissas, scalar per-tensor scales, exact int32 accumulation (the
    plan guarantees K fits one accumulator) and one f32 rescale — bit-
    identical to both kernel GEMMs."""
    sea = ea - 127 - 23 + (24 - pa)
    seb = eb - 127 - 23 + (24 - pb)
    acc = jnp.einsum("...mk,...nk->...mn", am.astype(jnp.int32),
                     bmant.astype(jnp.int32))
    return acc.astype(jnp.float32) * pow2(sea + seb)


def _jnp_block_matmul(am: jnp.ndarray, bmant: jnp.ndarray, ea, eb,
                      pa: int, pb: int, blk: int) -> jnp.ndarray:
    """jnp mirror of the fused per-block kernel (the batched twin of
    ``ref.bfp_block_matmul_ref``): per-K-block int32 partials rescaled and
    summed sequentially in block order — the kernel's exact combine order,
    so the fallback stays bit-strict."""
    sea = ea - 127 - 23 + (24 - pa)      # (..., M, K/blk)
    seb = eb - 127 - 23 + (24 - pb)      # (..., N, K/blk)
    nb = am.shape[-1] // blk
    acc = jnp.zeros(am.shape[:-2] + (am.shape[-2], bmant.shape[-2]),
                    jnp.float32)
    for i in range(nb):
        part = jnp.einsum("...mk,...nk->...mn",
                          am[..., i * blk:(i + 1) * blk].astype(jnp.int32),
                          bmant[..., i * blk:(i + 1) * blk].astype(jnp.int32))
        scale = pow2(sea[..., :, i:i + 1] + seb[..., i][..., None, :])
        acc = acc + part.astype(jnp.float32) * scale
    return acc


# ---------------------------------------------------------------------------
# geometry helpers
# ---------------------------------------------------------------------------

def _pad2(x: jnp.ndarray, rm: int, cm: int, value=0) -> jnp.ndarray:
    """Zero-pad the last two dims up to multiples (rm, cm); exact through
    quantize (0 -> mantissa 0) and GEMM (0 contributes nothing)."""
    pr = round_up(x.shape[-2], rm) - x.shape[-2]
    pc = round_up(x.shape[-1], cm) - x.shape[-1]
    if pr or pc:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)]
        x = jnp.pad(x, pad, constant_values=value)
    return x


def _vmem_bytes(kind: str, bm: int, k: int, n: int, nb: int) -> int:
    """Residency estimate for one fused-kernel instance, in bytes.

    Pallas double-buffers every blocked operand, the resident b-side blocks
    included (a constant index map still gets two buffers), so both the
    row strip and the resident blocks count twice.  On top come the
    in-kernel temporaries: the quantizer's f32-wide working copy of the
    largest tile it quantizes and the int32 accumulator of the strip.
    kind: "qq" f32 a+rand / f32 b+rand + both mantissa outputs;
          "qq_blk" adds the int32 exponent blocks;
          "qi" drops b's f32/rand (int8 resident); "ii" drops a's too.
    """
    y = 4 * bm * n
    acc = 4 * bm * n
    if kind in ("qq", "qq_blk"):
        a_strip = (4 + 4 + 1) * bm * k + y
        b_res = (4 + 4 + 1) * n * k
        tmp = 4 * max(bm, n) * k
        if kind == "qq_blk":
            a_strip += 4 * bm * nb
            b_res += 4 * n * nb
    elif kind == "qi":
        a_strip = (4 + 4 + 1) * bm * k + y
        b_res = 1 * n * k
        tmp = 4 * bm * k
    else:  # "ii" / "pp": both operands arrive as int8 mantissas
        a_strip = 1 * bm * k + y
        b_res = 1 * n * k
        tmp = 0
    return 2 * (a_strip + b_res) + tmp + acc


def bytes_moved(path: str, m: int, k: int, n: int, *, stochastic: bool = True,
                bm: int = 128, bn: int = 128, bk: int = 128,
                kind: str = "qq") -> int:
    """Analytic HBM traffic of one quantize+contract, in bytes.

    Counts, for a (M, K) x (N, K)^T -> (M, N) integer contraction:
    the shared-exponent scan (one f32 read of each *freshly quantized*
    operand — paid by every integer path), f32 + random-bit reads into the
    quantizer, int8 mantissa writes (the custom_vjp residuals), any
    intermediate HBM round-trip, the tiled GEMM's operand re-reads, and the
    f32 output write.  ``float`` is the plain f32 GEMM (no quantizer, f32
    tile re-reads).  The default (bm, bn, bk) matches the 128-tile geometry
    the unfused pipeline actually executes (_matmul_unfused and the
    microbenchmarks).

    ``kind`` states which operands arrive pre-quantized (the q-in paths of
    the qflow dataflow): "qq" both fresh, "iq" a pre-quantized, "qi" b
    pre-quantized, "ii"/"pp" both ("pp" is the *forward* fully-pre-
    quantized contraction of the persistent weight currency: a BFP
    activation against a derived BFP weight; "ii" the residual-vs-residual
    backward dW).  A pre-quantized operand pays one int8 read in place of
    the f32 scan + quantizer reads and writes no residual — the 4-9x
    per-operand traffic cut that makes BFP the cheaper inter-layer (and,
    with ``policy.qweights``, inter-*step*) currency.
    """
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    ni, nj = math.ceil(m / bm), math.ceil(n / bn)
    if path == "float":
        return f32 * (nj * m * k + ni * n * k + m * n)
    a_fresh = kind in ("qq", "qi")
    b_fresh = kind in ("qq", "iq")
    fresh = (m * k if a_fresh else 0) + (n * k if b_fresh else 0)
    pre = (m * k if not a_fresh else 0) + (n * k if not b_fresh else 0)
    scan = f32 * fresh
    quant_in = (f32 + r8) * fresh
    resid_out = i8 * fresh
    y_out = f32 * m * n
    if path == FUSED:
        # One pallas_call: a-strips fetched once, b resident — the quantizer
        # feeds the MXU through VMEM, nothing int8 round-trips HBM; a
        # pre-quantized operand is read once as int8.
        return scan + quant_in + resid_out + i8 * pre + y_out
    # Unfused: quantizer writes mantissas to HBM, the GEMM re-reads them
    # (pre-quantized mantissas included) once per output tile row/column;
    # jnp adds the elementwise emulation's extra f32 round-trips through
    # the ~6-op quantizer chain.
    gemm_reads = i8 * (nj * m * k + ni * n * k)
    unfused = scan + quant_in + resid_out + gemm_reads + y_out
    if path == UNFUSED:
        return unfused
    return unfused + 2 * f32 * fresh             # JNP emulation overhead


def cache_operand_bytes(n_rows: int, row: int, *, quantized: bool,
                        bits: int = 8, stochastic: bool = True,
                        rewritten: bool = False) -> int:
    """Analytic HBM bytes one decode step pays for ONE cache operand of
    ``n_rows`` rows x ``row`` elements (the decode-time twin of the
    weight-side column in :func:`bytes_moved` — see docs/SERVING.md).

    ``quantized=False`` is the float-cache pipeline: decode re-quantizes
    the whole cache operand inside attention every step — the f32 scan,
    the quantizer's f32 + random-bit reads and the int8 residual write
    (the same per-operand accounting as ``bytes_moved(kind="qq")``).
    ``quantized=True`` is the qcache currency: one ``bits``-wide mantissa
    read plus one int32 exponent read per cache row — no quantizer runs.
    ``rewritten=True`` models accumulator *state* leaves (RG-LRU h, RWKV6
    S) that are also written back every step: float pays a read+write
    round-trip, quantized pays the narrow mantissa/exponent write.
    """
    f32, r8 = 4, (4 if stochastic else 0)
    n = n_rows * row
    if quantized:
        # container bytes, not bits//8: sub-byte widths still store int8
        read = np.dtype(storage_dtype(bits)).itemsize * n + 4 * n_rows
        return 2 * read if rewritten else read
    if rewritten:
        return 2 * f32 * n                       # f32 read + f32 write
    return (f32 + f32 + r8 + 1) * n              # scan + quantize + residual


def paged_gather_bytes(n_blocks: int, page_rows: int, row: int, *,
                       bits: int = 8, rewritten: bool = False) -> int:
    """Analytic HBM bytes ONE paged cache operand costs a batched decode
    lane: the engine (launch/engine.py) walks the sequence's page table —
    one int32 page-id read per block — and streams each page's
    ``page_rows`` quantized rows into the contiguous layout the decode
    kernels consume.  The row payload is exactly
    :func:`cache_operand_bytes` of the gathered operand (paging relocates
    integer rows, it never requantizes), so the pool's whole overhead over
    a private contiguous cache is the page-table walk."""
    payload = cache_operand_bytes(n_blocks * page_rows, row, quantized=True,
                                  bits=bits, rewritten=rewritten)
    return payload + 4 * n_blocks


def plan_batched_decode(n_lanes: int, layout: dict, shapes: dict,
                        bits_for, *, page_rows: int = 16) -> dict:
    """Traffic plan for one engine decode iteration over ``n_lanes``
    gathered lanes (the continuous-batching hot path, docs/SERVING.md
    §Engine).  ``layout``/``shapes`` come from ``get_cache_layout`` and
    the batch-1 ``cache_template``; ``bits_for(kind, row)`` is
    ``policy.cache_cfg_for(...).bits``.  Weight mantissas are read once
    per iteration regardless of lane count — that amortization is the
    whole reason iteration-level batching moves tokens/s-per-step — so
    the per-lane cost is the paged cache traffic alone."""
    per_lane = 0
    for name, kind in layout.items():
        shape = shapes[name]
        rows = 1
        for dim in shape[:-1]:
            rows *= dim
        n_blocks = max(1, -(-rows // page_rows))
        per_lane += paged_gather_bytes(n_blocks, page_rows, shape[-1],
                                       bits=bits_for(kind, shape[-1]),
                                       rewritten=kind == "state")
    return {"n_lanes": n_lanes, "page_rows": page_rows,
            "cache_bytes_per_lane": per_lane,
            "cache_bytes_total": n_lanes * per_lane}


def speculative_verify_bytes_moved(k: int, *, weight_bytes: int,
                                   draft_weight_bytes: int,
                                   cache_bytes: int,
                                   draft_cache_bytes: int) -> int:
    """Analytic HBM bytes ONE speculative decode round moves
    (launch.speculative, docs/SERVING.md §Speculative decoding): ``k``
    draft steps each stream the truncated model's weights and its slice
    of the cache band, then the verify pass reads the TARGET's weights
    exactly once for the whole k+1-token block — a banded fused-attention
    prefill over the existing qcache rows, so the cache side pays the
    k+1 band reads but the weight side is amortized the same way
    iteration-level batching amortizes it across lanes.  Compare with
    ``(k + 1) * (weight_bytes + cache_bytes)``, which is what sequential
    decode pays for the same tokens when everything is accepted."""
    return (k * (draft_weight_bytes + draft_cache_bytes)
            + weight_bytes + (k + 1) * cache_bytes)


def plan_speculative_verify(k: int, draft_layers: int, n_layers: int, *,
                            weight_bytes: int, cache_bytes: int,
                            draft_weight_bytes: Optional[int] = None,
                            draft_cache_bytes: Optional[int] = None) -> dict:
    """Traffic plan for speculative decoding at draft depth ``k``
    (docs/SERVING.md §Speculative decoding).  ``weight_bytes`` /
    ``cache_bytes`` are the target's per-decode-step weight-operand and
    cache-operand HBM bytes; the draft twins default to the layer-count
    fraction of them (the truncated draft shares the embedding/head, a
    second-order term at serving widths).

    The plan prices one round against the sequential decode that emits
    the same tokens, and reports ``breakeven_accepted``: the fewest draft
    tokens a round must land for speculation to move fewer bytes per
    emitted token than plain decode.  The measured acceptance rate
    (``accepted_tokens_per_step`` in BENCH_serving.json) closes the loop:
    above breakeven, speculation wins on traffic; at full acceptance the
    per-token bytes drop by ``reduction_at_full_accept_pct``."""
    if not 1 <= draft_layers <= n_layers:
        raise ValueError(
            f"draft_layers must be in [1, {n_layers}], got {draft_layers}")
    if k < 1:
        raise ValueError(f"speculation depth k must be >= 1, got {k}")
    frac = draft_layers / n_layers
    dw = (int(weight_bytes * frac) if draft_weight_bytes is None
          else draft_weight_bytes)
    dc = (int(cache_bytes * frac) if draft_cache_bytes is None
          else draft_cache_bytes)
    round_bytes = speculative_verify_bytes_moved(
        k, weight_bytes=weight_bytes, draft_weight_bytes=dw,
        cache_bytes=cache_bytes, draft_cache_bytes=dc)
    seq_token = weight_bytes + cache_bytes
    seq_block = (k + 1) * seq_token
    # round_bytes <= (1 + a) * seq_token  <=>  a >= round/seq - 1
    breakeven = max(0, math.ceil(round_bytes / seq_token - 1))
    return {
        "k": k, "draft_layers": draft_layers, "n_layers": n_layers,
        "weight_bytes": weight_bytes, "cache_bytes": cache_bytes,
        "draft_weight_bytes": dw, "draft_cache_bytes": dc,
        "round_bytes": round_bytes,
        "sequential_bytes_per_token": seq_token,
        "sequential_block_bytes": seq_block,
        "breakeven_accepted": breakeven,
        "reduction_at_full_accept_pct": round(
            100.0 * (1 - round_bytes / seq_block), 2),
    }


# ---------------------------------------------------------------------------
# fused attention: geometry, residency, traffic model, planning
# ---------------------------------------------------------------------------

def attn_block_t(t: int) -> int:
    """KV block size ``bt`` of the fused attention kernels for band length
    ``t``: a lane multiple, small enough to keep several online-softmax
    steps per band (the in-register tile is (bq, bt)).  ``bt`` is part of
    the fused path's numerics (the per-row shared exponent of ``p`` spans
    one block), so it is a pure function of the static shape — forward,
    backward and the jnp mirrors all derive the same value."""
    if t <= 1024:
        return 128
    if t <= 4096:
        return 256
    return 512


def _attn_vmem_bytes(op: str, bq: int, gs: int, t: int, d: int, bt: int,
                     stochastic: bool) -> int:
    """Residency estimate for one fused-attention kernel instance.

    ``attn_fwd``: one (bq, D) query strip + its (bq, T) p-rounding bits
    double-buffered, K/V mantissas resident, ~6 f32 (bq, bt) score-chain
    tiles in registers/VMEM.  ``attn_bwd``: Q-side (qm, gm, stats, dq)
    resident, (bt, D) K/V strips + (GS, bt) rand strips double-buffered,
    (GS, bt) score-chain tiles.  ``attn_decode``: everything resident,
    one program, (GS, T) score tiles.
    """
    r8 = 4 if stochastic else 0
    if op == "attn_bwd":
        resident = 2 * gs * d + 3 * 4 * gs + 4 * gs * d
        strip = 2 * bt * d + 2 * r8 * gs * bt + 2 * 4 * bt * d
        tiles = 6 * 4 * gs * bt
        return resident + 2 * strip + tiles
    if op == "attn_decode":
        return (gs * d + 2 * t * d + 2 * 4 * t + r8 * gs * t
                + 4 * gs * d + 6 * 4 * gs * t)
    strip = bq * d + r8 * bq * t + 4 * bq * d + 2 * 4 * bq
    return 2 * strip + 2 * t * d + 6 * 4 * bq * bt


def attention_bytes_moved(path: str, gs: int, t: int, d: int, *,
                          chunk: int = 1024, stochastic: bool = True,
                          op: str = "attn_fwd") -> int:
    """Analytic HBM traffic of one attention forward, per (batch·KV-head)
    slice: grouped queries (GS, D) against a band of T KV rows.

    ``path="scan"`` (any non-fused spelling) is the ``lax.scan`` pipeline
    of ``models.attention``: per KV chunk, the two separately-dispatched
    integer GEMMs (QKᵀ fully-pre-quantized, PV quantize-p-fused — each at
    the fused *GEMM* path's own best cost), PLUS the inter-GEMM round
    trips the flash fusion deletes: the masked scores re-read by the
    softmax, the float probabilities written for the PV quantizer, and
    the online-softmax carry (m, l, acc) re-read + re-written every chunk.
    ``path="fused"`` is one kernel: the query strip and K/V mantissas are
    each fetched exactly once, the p rounding bits stream once, and only
    the output + two row-stat vectors are written — scores and
    probabilities never touch HBM.

    ``op="attn_decode"`` swaps operand costs for the qcache decode shapes:
    the cache mantissas pay one int8 read + one int32 exponent read per
    row on both paths (the qcache contract), so the fused win there is
    exactly the deleted score/probability round-trips and the second
    kernel launch's operand re-reads.
    """
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    fused_like = path == FUSED
    if op == "attn_decode":
        exp_rows = 2 * 4 * t
        if fused_like:
            return (i8 * gs * d + 2 * i8 * t * d + exp_rows + r8 * gs * t
                    + f32 * gs * d)
        qk = bytes_moved(FUSED, gs, d, t, stochastic=stochastic, kind="pp")
        pv = bytes_moved(FUSED, gs, t, d, stochastic=stochastic, kind="qi")
        return qk + pv + exp_rows + 2 * f32 * gs * t
    if fused_like:
        return (i8 * gs * d + 2 * i8 * t * d + r8 * gs * t
                + f32 * gs * d + 2 * f32 * gs)
    c = min(chunk, t)
    nc = math.ceil(t / c)
    per_chunk = (bytes_moved(FUSED, gs, d, c, stochastic=stochastic,
                             kind="pp")
                 + bytes_moved(FUSED, gs, c, d, stochastic=stochastic,
                               kind="qi")
                 + 2 * f32 * gs * c                  # sck re-read, p write
                 + 2 * f32 * (gs * d + 2 * gs))      # m/l/acc carry
    return nc * per_chunk


def _make_attn_bench(gs: int, t: int, d: int, cfg: QuantConfig, s: int,
                     bt: int, interpret: bool):
    """bench(bq) -> µs over synthetic int8 operands (attention autotune)."""
    from .fused_attention import fused_attn_fwd_pallas

    def bench(bq: int) -> float:
        rng = np.random.RandomState(0)
        gsp = round_up(max(gs, 1), bq)
        tp = round_up(t, bt)
        dp = round_up(d, _LANE)
        qm = jnp.asarray(rng.randint(-127, 128, (gsp, dp), np.int8))
        km = jnp.asarray(rng.randint(-127, 128, (tp, dp), np.int8))
        vm = jnp.asarray(rng.randint(-127, 128, (tp, dp), np.int8))
        rp = (jnp.asarray(rng.randint(0, 2 ** 32, (gsp, tp), np.uint32))
              if cfg.stochastic else None)
        e = jnp.int32(130)

        def fn():
            return jax.block_until_ready(fused_attn_fwd_pallas(
                qm, km, vm, rp, e, e, e, jnp.int32(0), jnp.int32(t),
                p=cfg.p, s=s, bq=bq, bt=bt, causal=True, window=0,
                stochastic=cfg.stochastic, interpret=interpret))

        return autotune.time_call_us(fn)

    return bench


def plan_attention(op: str, gs: int, t: int, d: int, cfg: QuantConfig, *,
                   s: int, kind: str = "pp", kernel_mode: str = "auto",
                   backend: Optional[str] = None,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET,
                   autotune_measure: bool = False) -> Decision:
    """Choose the execution path for one fused-attention op.

    ``gs`` = grouped query rows (g·S per KV head), ``t`` = KV band length,
    ``d`` = head dim, ``s`` = per-group query length (GQA row layout).
    ``op`` ∈ {"attn_fwd", "attn_bwd", "attn_decode"}; ``kind`` states the
    query operand ("pp": pre-quantized q-in mantissas, "qi": fresh float
    quantized before the kernel).  FUSED means the flash-style Pallas
    kernel of ``kernels.fused_attention``; JNP means the caller keeps the
    established ``lax.scan``-of-GEMMs path (there is no unfused middle
    pipeline for attention).  Decision.bm carries the autotuned query
    row-strip ``bq``, Decision.bt the KV block size.
    """
    backend = backend or jax.default_backend()
    interpret = backend != "tpu"

    def decide(path, reason, bm=0, bt=0, atkey=""):
        return _record(Decision(op, path, reason, gs, d, t, bm, interpret,
                                kind, bt, atkey=atkey))

    if kernel_mode not in ("auto", "fused", "unfused", "jnp"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    if kernel_mode == "jnp":
        return decide(JNP, "kernel_mode=jnp")
    if kernel_mode == "unfused":
        return decide(JNP, "attention has no unfused pipeline")
    if cfg.bits != 8:
        return decide(JNP, f"bits={cfg.bits} (kernels are int8-only)")
    if cfg.block != PER_TENSOR:
        return decide(JNP, "fused attention is per-tensor only")
    if kernel_mode == "auto" and interpret:
        return decide(JNP, f"auto keeps the scan path on backend={backend}")
    if not interpret and _on_multi_device_mesh():
        return decide(JNP, UNPARTITIONED)
    bt = attn_block_t(t)
    tp = round_up(t, bt)
    dp = round_up(d, _LANE)
    if op in ("attn_bwd", "attn_decode"):
        gsp = round_up(gs, _INT8_SUBLANE)
        if _attn_vmem_bytes(op, 0, gsp, tp, dp, bt,
                            cfg.stochastic) <= vmem_budget:
            return decide(FUSED, "fused attention fits VMEM budget", bt=bt)
        return decide(JNP, f"no residency fits vmem_budget={vmem_budget}")

    def fits(bq):
        return _attn_vmem_bytes(op, bq, round_up(gs, bq), tp, dp, bt,
                                cfg.stochastic) <= vmem_budget

    key = autotune.shape_key(f"attn_{kind}", gs, d, t, cfg.bits, 0, backend)
    measure = ((autotune_measure or autotune.autotune_enabled_by_env())
               and backend == jax.default_backend())
    bench = (_make_attn_bench(gs, t, d, cfg, s, bt, interpret)
             if measure else None)
    bq = autotune.select_bm(key, gs, fits, measure=measure, bench=bench)
    if bq:
        return decide(FUSED, "fused attention fits VMEM budget", bq, bt,
                      atkey=key)
    return decide(JNP, f"no bq candidate fits vmem_budget={vmem_budget}")


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def plan_contract(op: str, m: int, k: int, n: int, cfg: QuantConfig, *,
                  kind: str = "qq", cfg2: Optional[QuantConfig] = None,
                  kernel_mode: str = "auto", accum_chunk: int = 65536,
                  backend: Optional[str] = None,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  autotune_measure: bool = False) -> Decision:
    """Choose the execution path for one (M, K) x (N, K)^T contraction.

    ``cfg`` is the quantization config of the freshly-quantized operand(s);
    ``cfg2`` (if given) the config of a pre-quantized operand — a stored
    residual (``qi``/``ii``) or a q-in activation flowing between layers
    (``iq``: the a side arrives as int8 mantissas + scale and the in-kernel
    quantize stage is skipped for it).  Called at trace time with static
    shapes.
    """
    backend = backend or jax.default_backend()
    interpret = backend != "tpu"

    def decide(path, reason, bm=0, atkey=""):
        return _record(Decision(op, path, reason, m, k, n, bm, interpret,
                                kind, atkey=atkey))

    if kernel_mode not in ("auto", "fused", "unfused", "jnp"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    if kernel_mode == "jnp":
        return decide(JNP, "kernel_mode=jnp")
    bits = {cfg.bits} | ({cfg2.bits} if cfg2 is not None else set())
    if bits != {8}:
        return decide(JNP, f"bits={sorted(bits)} (kernels are int8-only)")
    if cfg2 is not None and cfg2.block != PER_TENSOR:
        # qi/ii/pp reuse pre-quantized mantissas against a *scalar*
        # exponent; a per-block pre-quantized operand has no kernel path.
        return decide(JNP, "per-block residual operands have no kernel path")
    if kind == "pp" and cfg.block != PER_TENSOR:
        return decide(JNP, "pp needs per-tensor scales on both operands")
    if kernel_mode == "auto" and interpret:
        return decide(JNP, f"auto keeps the jnp oracle on backend={backend}")
    if not interpret and _on_multi_device_mesh():
        return decide(JNP, UNPARTITIONED)
    if cfg.block == PER_TENSOR and k > accum_chunk:
        # The jnp path emulates periodic hardware accumulator flushes by
        # chunking K; neither kernel path reproduces that flush.
        return decide(JNP, f"K={k} > accum_chunk={accum_chunk} "
                           "(flush emulation stays on jnp)")
    if cfg.block == PER_TENSOR and k * 127 * 127 >= (1 << 31):
        return decide(JNP, f"K={k} overflows the int32 accumulator")

    blk = cfg.block
    kp = round_up(k, _LANE if blk == PER_TENSOR else (_LANE * blk) // math.gcd(_LANE, blk))
    np_ = round_up(n, _LANE)
    nb = 0 if blk == PER_TENSOR else kp // blk
    vkind = "qq_blk" if (kind == "qq" and blk != PER_TENSOR) else kind

    # -- fused feasibility ---------------------------------------------------
    # "iq" runs the qi kernel with the operand roles swapped: the row strip
    # walks the freshly-quantized side (N rows) while the pre-quantized int8
    # mantissas (M rows) stay resident.
    strip_rows = n if kind == "iq" else m
    res_cols = round_up(m, _LANE) if kind == "iq" else np_
    vmem_kind = "qi" if vkind == "iq" else vkind
    fused_block = None
    if kernel_mode in ("auto", "fused"):
        if blk != PER_TENSOR and kind != "qq":
            fused_block = (0, "per-block residuals require the qq variant")
        else:
            def fits(bm):
                return _vmem_bytes(vmem_kind, bm, kp, res_cols, nb) <= vmem_budget
            key = autotune.shape_key(vkind, m, k, n, cfg.bits, blk, backend)
            # Measure only when the requested backend IS the local one:
            # interpret-mode timings must never be persisted under a TPU key.
            measure = ((autotune_measure or autotune.autotune_enabled_by_env())
                       and backend == jax.default_backend())
            if not measure:
                bench = None
            elif kind == "iq":
                bench = _make_bench("qi", n, k, m, cfg, interpret)
            elif kind == "pp":
                # same kernel as ii, but timed (and cached) under its own
                # forward-shaped key: the weight side is N-major resident.
                bench = _make_bench("ii", m, k, n, cfg, interpret)
            else:
                bench = _make_bench(vkind, m, k, n, cfg, interpret)
            bench_jnp = (_make_bench_jnp(vkind, m, k, n, cfg)
                         if measure else None)
            bm = autotune.select_bm(key, strip_rows, fits, measure=measure,
                                    bench=bench, bench_jnp=bench_jnp)
            if bm == autotune.JNP_FALLBACK:
                return decide(JNP, "autotune: jnp mirror measured faster",
                              atkey=key)
            if bm:
                return decide(FUSED, "fused pipeline fits VMEM budget", bm,
                              atkey=key)
            fused_block = (0, f"no bm candidate fits vmem_budget={vmem_budget}")

    # -- unfused fallback ----------------------------------------------------
    if blk == PER_TENSOR:
        if kind not in ("ii", "pp") and not cfg.stochastic:
            # the standalone quantizer kernel only implements the
            # threshold-compare *stochastic* circuit; nearest rounding is
            # fused-or-jnp (the fused kernel handles both).
            return decide(JNP, "unfused quantizer kernel is SR-only")
        why = ("kernel_mode=unfused" if kernel_mode == "unfused"
               else f"fused infeasible: {fused_block[1]}")
        return decide(UNFUSED, why)
    return decide(JNP, "per-block scale has no unfused kernel path"
                  if fused_block is None else
                  f"fused infeasible: {fused_block[1]} (per-block -> jnp)")


def _make_bench(vkind: str, m: int, k: int, n: int, cfg: QuantConfig,
                interpret: bool):
    """Build a bench(bm) -> µs callable over synthetic operands (autotune)."""
    import numpy as np

    def bench(bm: int) -> float:
        rng = np.random.RandomState(0)
        mp = round_up(max(m, 1), bm)
        blk = cfg.block
        kp = round_up(k, _LANE if blk == PER_TENSOR
                       else (_LANE * blk) // math.gcd(_LANE, blk))
        np_ = round_up(n, _LANE)
        a = jnp.asarray(rng.randn(mp, kp).astype(np.float32))
        b = jnp.asarray(rng.randn(np_, kp).astype(np.float32))
        ra = jnp.asarray(rng.randint(0, 2**32, (mp, kp), np.uint32))
        rb = jnp.asarray(rng.randint(0, 2**32, (np_, kp), np.uint32))
        if vkind == "qq_blk":
            ea = ref.max_biased_exp_blocks_ref(a, blk)
            eb = ref.max_biased_exp_blocks_ref(b, blk)
            fn = lambda: jax.block_until_ready(fused_qq_blk_pallas(
                a, ra, ea, b, rb, eb, p=cfg.p, blk=blk, bm=bm,
                interpret=interpret))
        else:
            ea = ref.max_biased_exp_ref(a)
            eb = ref.max_biased_exp_ref(b)
            if vkind == "qq":
                fn = lambda: jax.block_until_ready(fused_qq_pt_pallas(
                    a, ra, b, rb, ea, eb, p=cfg.p, bm=bm, interpret=interpret))
            elif vkind == "qi":
                bm8 = jnp.asarray(rng.randint(-127, 128, (np_, kp), np.int8))
                fn = lambda: jax.block_until_ready(fused_qi_pt_pallas(
                    a, ra, bm8, ea, eb, pa=cfg.p, pb=cfg.p, bm=bm,
                    interpret=interpret))
            else:
                a8 = jnp.asarray(rng.randint(-127, 128, (mp, kp), np.int8))
                bm8 = jnp.asarray(rng.randint(-127, 128, (np_, kp), np.int8))
                fn = lambda: jax.block_until_ready(fused_ii_pt_pallas(
                    a8, bm8, ea, eb, pa=cfg.p, pb=cfg.p, bm=bm,
                    interpret=interpret))
        return autotune.time_call_us(fn)

    return bench


def _make_bench_jnp(vkind: str, m: int, k: int, n: int, cfg: QuantConfig):
    """Build a bench() -> µs callable over the bit-identical jnp mirror of
    the same contraction, for :func:`autotune.select_bm`'s measured
    jnp-fallback decision (the pre-quantized small shapes where XLA's dot
    beats the kernel's strip launches)."""
    import numpy as np

    def bench_jnp() -> float:
        rng = np.random.RandomState(0)
        key = jax.random.key(0)
        if vkind in ("ii", "pp"):
            a8 = jnp.asarray(rng.randint(-127, 128, (m, k), np.int8))
            b8 = jnp.asarray(rng.randint(-127, 128, (n, k), np.int8))
            run = jax.jit(lambda a, b: _jnp_matmul(a, b, 130, 130,
                                                   cfg.p, cfg.p))
            fn = lambda: jax.block_until_ready(run(a8, b8))
        else:
            a = jnp.asarray(rng.randn(m, k).astype(np.float32))
            b = jnp.asarray(rng.randn(n, k).astype(np.float32))
            ka, kb = jax.random.split(key)
            if vkind == "qq_blk":
                def run(a, b):
                    aq = bfp_quantize(a, cfg, ka)
                    bq = bfp_quantize(b, cfg, kb)
                    return _jnp_block_matmul(aq.m, bq.m, aq.e, bq.e,
                                             cfg.p, cfg.p, cfg.block)
            elif vkind == "qq":
                def run(a, b):
                    aq = bfp_quantize(a, cfg, ka)
                    bq = bfp_quantize(b, cfg, kb)
                    return _jnp_matmul(aq.m, bq.m, aq.e, bq.e, cfg.p, cfg.p)
            else:                                   # qi / iq: one fresh side
                b8 = jnp.asarray(rng.randint(-127, 128, (n, k), np.int8))

                def run(a, b):
                    aq = bfp_quantize(a, cfg, ka)
                    return _jnp_matmul(aq.m, b, aq.e, 130, cfg.p, cfg.p)

                b = b8
            run = jax.jit(run)
            fn = lambda: jax.block_until_ready(run(a, b))
        return autotune.time_call_us(fn)

    return bench_jnp


# ---------------------------------------------------------------------------
# execution: quantize-and-contract entry points (contraction-last layout)
# ---------------------------------------------------------------------------

def _batched_call(one, arrays, nbatch, crops):
    """Flatten leading batch dims, run the 2-D kernel wrapper (lax.map when
    batched), crop the padding, restore batch dims.

    ``crops`` is one (rows, cols) pair per kernel output; returns a list of
    outputs in kernel order.
    """
    lead = arrays[0].shape[:nbatch]
    flat = tuple(x.reshape((-1,) + x.shape[nbatch:]) if nbatch else x
                 for x in arrays)
    outs = one(flat) if nbatch == 0 else lax.map(one, flat)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    res = []
    for o, (r, c) in zip(outs, crops):
        o = o[..., :r, :c]
        if nbatch:
            o = o.reshape(lead + o.shape[1:])
        res.append(o)
    return res


def contract_qq(a: jnp.ndarray, b: jnp.ndarray, cfg: QuantConfig,
                ka: jax.Array, kb: jax.Array, dec: Decision,
                nbatch: int = 0,
                want_residuals: bool = True) -> Tuple[jnp.ndarray, BFP, BFP]:
    """Quantize both contraction-last operands and contract on kernels.

    a (*B, M, K) f32, b (*B, N, K) f32 -> (y (*B, M, N) f32, aq, bq) with
    the BFP residuals bit-identical to ``core.bfp.quantize(_, cfg, key)``.
    ``want_residuals=False`` (the backward requantization path) returns
    (y, None, None) and keeps all mantissas in VMEM — no int8 HBM writes.
    Non-stochastic configs stream no random bits at all.
    """
    m, k = a.shape[-2], a.shape[-1]
    n = b.shape[-2]
    sr = cfg.stochastic
    ra = rounding_bits(ka, a.shape, cfg.rng) if sr else None
    rb = rounding_bits(kb, b.shape, cfg.rng) if sr else None

    if cfg.block == PER_TENSOR:
        ea = ref.max_biased_exp_ref(a)    # global max: padding-independent
        eb = ref.max_biased_exp_ref(b)

        def run_kernel(d):
            if d.path == UNFUSED:
                # plan_contract only routes stochastic configs here (the
                # standalone quantizer kernel is SR-only).
                am, bmant = (_quantize_rows(a, ra, ea, d.interpret),
                             _quantize_rows(b, rb, eb, d.interpret))
                y = _matmul_unfused(am, bmant, ea, eb, cfg.p, cfg.p,
                                    d.interpret, nbatch)
                return y, BFP(am, ea.astype(jnp.int32), cfg), \
                    BFP(bmant, eb.astype(jnp.int32), cfg)
            arrays = [_pad2(a, d.bm, _LANE)] + \
                ([_pad2(ra, d.bm, _LANE)] if sr else []) + \
                [_pad2(b, _LANE, _LANE)] + \
                ([_pad2(rb, _LANE, _LANE)] if sr else [])

            def one(args):
                if sr:
                    a2, ra2, b2, rb2 = args
                else:
                    (a2, b2), ra2, rb2 = args, None, None
                return fused_qq_pt_pallas(a2, ra2, b2, rb2, ea, eb, p=cfg.p,
                                          bm=d.bm, stochastic=sr,
                                          interpret=d.interpret,
                                          emit_residuals=want_residuals)

            if not want_residuals:
                y, = _batched_call(one, arrays, nbatch, [(m, n)])
                return y, None, None
            y, am, bmant = _batched_call(one, arrays, nbatch,
                                         [(m, n), (m, k), (n, k)])
            return y, BFP(am, ea.astype(jnp.int32), cfg), \
                BFP(bmant, eb.astype(jnp.int32), cfg)

        def run_jnp(d):
            aq = bfp_quantize(a, cfg, ka)
            bq = bfp_quantize(b, cfg, kb)
            y = _jnp_matmul(aq.m, bq.m, aq.e, bq.e, cfg.p, cfg.p)
            if not want_residuals:
                return y, None, None
            return y, aq, bq

        return _with_ladder(dec, run_kernel, run_jnp, cfg)

    # ---- per-block (along K) fused path ------------------------------------
    blk = cfg.block
    ea = ref.max_biased_exp_blocks_ref(a, blk)    # (*B, M, K/blk)
    eb = ref.max_biased_exp_blocks_ref(b, blk)
    kmult = (_LANE * blk) // math.gcd(_LANE, blk)
    nbp = round_up(k, kmult) // blk
    # Padded blocks/rows get biased exponent 1: their (zero) mantissas scale
    # to exactly 0, so the padding is invisible in the f32 combine.

    def pad_e(e, rm):
        e = _pad2(e, rm, 1, value=1)
        return jnp.pad(e, [(0, 0)] * (e.ndim - 1) + [(0, nbp - e.shape[-1])],
                       constant_values=1)

    def run_kernel(d):
        arrays = [_pad2(a, d.bm, kmult)] + \
            ([_pad2(ra, d.bm, kmult)] if sr else []) + \
            [pad_e(ea, d.bm), _pad2(b, _LANE, kmult)] + \
            ([_pad2(rb, _LANE, kmult)] if sr else []) + \
            [pad_e(eb, _LANE)]

        def one(args):
            if sr:
                a2, ra2, ea2, b2, rb2, eb2 = args
            else:
                (a2, ea2, b2, eb2), ra2, rb2 = args, None, None
            return fused_qq_blk_pallas(a2, ra2, ea2, b2, rb2, eb2, p=cfg.p,
                                       blk=blk, bm=d.bm, stochastic=sr,
                                       interpret=d.interpret,
                                       emit_residuals=want_residuals)

        if not want_residuals:
            y, = _batched_call(one, arrays, nbatch, [(m, n)])
            return y, None, None
        y, am, bmant = _batched_call(one, arrays, nbatch,
                                     [(m, n), (m, k), (n, k)])
        return y, BFP(am, ea.astype(jnp.int32), cfg), \
            BFP(bmant, eb.astype(jnp.int32), cfg)

    def run_jnp(d):
        # per-block has no unfused rung: _degrade routes straight here
        # (cfg.block != PER_TENSOR fails its per-tensor predicate).
        aq = bfp_quantize(a, cfg, ka)
        bq = bfp_quantize(b, cfg, kb)
        y = _jnp_block_matmul(aq.m, bq.m, aq.e, bq.e, cfg.p, cfg.p, blk)
        if not want_residuals:
            return y, None, None
        return y, aq, bq

    return _with_ladder(dec, run_kernel, run_jnp, cfg)


def contract_qi(a: jnp.ndarray, bq: BFP, cfg: QuantConfig, ka: jax.Array,
                dec: Decision, nbatch: int = 0) -> Tuple[jnp.ndarray, BFP]:
    """Quantize ``a`` fused into the GEMM against residual mantissas ``bq``.

    a (*B, M, K) f32, bq.m (*B, N, K) int8 (per-tensor scale) ->
    (y (*B, M, N) f32, aq).  The backward ``dX = Ĝ Ŵᵀ`` path.
    """
    assert bq.cfg.block == PER_TENSOR
    m, k = a.shape[-2], a.shape[-1]
    n = bq.m.shape[-2]
    sr = cfg.stochastic
    ea = ref.max_biased_exp_ref(a)
    ra = rounding_bits(ka, a.shape, cfg.rng) if sr else None

    def run_kernel(d):
        if d.path == UNFUSED:
            am = _quantize_rows(a, ra, ea, d.interpret)
            y = _matmul_unfused(am, bq.m, ea, bq.e, cfg.p, bq.cfg.p,
                                d.interpret, nbatch)
            return y, BFP(am, ea.astype(jnp.int32), cfg)
        arrays = [_pad2(a, d.bm, _LANE)] + \
            ([_pad2(ra, d.bm, _LANE)] if sr else []) + \
            [_pad2(bq.m, _LANE, _LANE)]

        def one(args):
            if sr:
                a2, ra2, b2 = args
            else:
                (a2, b2), ra2 = args, None
            return fused_qi_pt_pallas(a2, ra2, b2, ea, bq.e, pa=cfg.p,
                                      pb=bq.cfg.p, bm=d.bm, stochastic=sr,
                                      interpret=d.interpret)

        y, am = _batched_call(one, arrays, nbatch, [(m, n), (m, k)])
        return y, BFP(am, ea.astype(jnp.int32), cfg)

    def run_jnp(d):
        aq = bfp_quantize(a, cfg, ka)
        y = _jnp_matmul(aq.m, bq.m, aq.e, bq.e, cfg.p, bq.cfg.p)
        return y, aq

    return _with_ladder(dec, run_kernel, run_jnp, cfg)


def contract_iq(aq: BFP, b: jnp.ndarray, cfg: QuantConfig, kb: jax.Array,
                dec: Decision, nbatch: int = 0) -> Tuple[jnp.ndarray, BFP]:
    """Contract pre-quantized mantissas ``aq`` against freshly-quantized ``b``.

    aq.m (*B, M, K) int8 (per-tensor scale), b (*B, N, K) f32 ->
    (y (*B, M, N) f32, bq).  The q-in forward path: an activation that
    already flows as BFP skips the in-kernel quantize stage entirely —
    kernel-wise this is the qi kernel with the operand roles swapped (the
    row strip walks the fresh side, the int8 mantissas stay resident, and
    the tile output is transposed back).
    """
    assert aq.cfg.block == PER_TENSOR
    m, k = aq.m.shape[-2], aq.m.shape[-1]
    n = b.shape[-2]
    sr = cfg.stochastic
    eb = ref.max_biased_exp_ref(b)
    rb = rounding_bits(kb, b.shape, cfg.rng) if sr else None

    def run_kernel(d):
        if d.path == UNFUSED:
            bmant = _quantize_rows(b, rb, eb, d.interpret)
            y = _matmul_unfused(aq.m, bmant, aq.e, eb, aq.cfg.p, cfg.p,
                                d.interpret, nbatch)
            return y, BFP(bmant, eb.astype(jnp.int32), cfg)
        arrays = [_pad2(b, d.bm, _LANE)] + \
            ([_pad2(rb, d.bm, _LANE)] if sr else []) + \
            [_pad2(aq.m, _LANE, _LANE)]

        def one(args):
            if sr:
                b2, rb2, a2 = args
            else:
                (b2, a2), rb2 = args, None
            yt, bm8 = fused_qi_pt_pallas(b2, rb2, a2, eb, aq.e, pa=cfg.p,
                                         pb=aq.cfg.p, bm=d.bm, stochastic=sr,
                                         interpret=d.interpret)
            return jnp.swapaxes(yt, -1, -2), bm8

        y, bmant = _batched_call(one, arrays, nbatch, [(m, n), (n, k)])
        return y, BFP(bmant, eb.astype(jnp.int32), cfg)

    def run_jnp(d):
        bq = bfp_quantize(b, cfg, kb)
        y = _jnp_matmul(aq.m, bq.m, aq.e, bq.e, aq.cfg.p, cfg.p)
        return y, bq

    return _with_ladder(dec, run_kernel, run_jnp, cfg)


def contract_ii(aq: BFP, bq: BFP, dec: Decision,
                nbatch: int = 0) -> jnp.ndarray:
    """Contract two residual mantissa tensors (per-tensor scale).

    aq.m (*B, M, K) int8, bq.m (*B, N, K) int8 -> y (*B, M, N) f32.
    The backward ``dW = X̂ᵀ Ĝ`` path — a pure int8 GEMM on kernels.
    """
    assert aq.cfg.block == PER_TENSOR and bq.cfg.block == PER_TENSOR
    m, k = aq.m.shape[-2], aq.m.shape[-1]
    n = bq.m.shape[-2]

    def run_kernel(d):
        if d.path == UNFUSED:
            return _matmul_unfused(aq.m, bq.m, aq.e, bq.e, aq.cfg.p,
                                   bq.cfg.p, d.interpret, nbatch)
        arrays = [_pad2(aq.m, d.bm, _LANE), _pad2(bq.m, _LANE, _LANE)]

        def one(args):
            a2, b2 = args
            return fused_ii_pt_pallas(a2, b2, aq.e, bq.e, pa=aq.cfg.p,
                                      pb=bq.cfg.p, bm=d.bm,
                                      interpret=d.interpret)

        y, = _batched_call(one, arrays, nbatch, [(m, n)])
        return y

    def run_jnp(d):
        return _jnp_matmul(aq.m, bq.m, aq.e, bq.e, aq.cfg.p, bq.cfg.p)

    return _with_ladder(dec, run_kernel, run_jnp)


def contract_pp(aq: BFP, bq: BFP, dec: Decision,
                nbatch: int = 0) -> jnp.ndarray:
    """Fully-pre-quantized *forward* contraction (persistent weight currency).

    aq.m (*B, M, K) int8 (a q-in activation), bq.m (*B, N, K) int8 (a
    derived / load-time-quantized weight) -> y (*B, M, N) f32.  No
    quantization stage runs and no random bits are streamed — a pure
    int8 x int8 -> int32 GEMM plus one f32 exponent-add rescale.  Kernel-
    wise this is the ii pipeline, but planned under its own ``pp``
    autotune keys (forward shapes, weight resident) by ``plan_contract``.
    """
    return contract_ii(aq, bq, dec, nbatch=nbatch)


# ---------------------------------------------------------------------------
# unfused building blocks (quantizer kernel -> HBM int8 -> GEMM kernel)
# ---------------------------------------------------------------------------

def _quantize_rows(x: jnp.ndarray, rand: jnp.ndarray, e: jnp.ndarray,
                   interpret: bool) -> jnp.ndarray:
    """Per-tensor quantization through the bfp_quant Pallas kernel.

    Handles any leading batch dims by flattening rows; bit-identical to
    ``core.bfp.quantize`` for the same random bits (the kernel implements
    stochastic rounding only — plan_contract never routes nearest-rounding
    configs here).
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = rand.reshape(-1, shape[-1])
    m = x2.shape[0]
    xp = _pad2(x2, 8, _LANE)
    rp = _pad2(r2, 8, _LANE)
    e_rows = jnp.pad(jnp.broadcast_to(e, (m,)), (0, xp.shape[0] - m),
                     constant_values=1)[:, None].astype(jnp.int32)
    mant = bfp_quantize_pallas(xp, rp, e_rows, block_rows=8,
                               interpret=interpret)
    return mant[:m, :shape[-1]].reshape(shape)


def _matmul_unfused(am: jnp.ndarray, bmant: jnp.ndarray, ea, eb,
                    pa: int, pb: int, interpret: bool,
                    nbatch: int = 0) -> jnp.ndarray:
    """int8 GEMM kernel on contraction-last mantissas with scalar scales."""
    sea = ea - 127 - 23 + (24 - pa)
    seb = eb - 127 - 23 + (24 - pb)
    scale = pow2(sea + seb)
    m, k = am.shape[-2], am.shape[-1]
    n = bmant.shape[-2]
    tile = _INT8_SUBLANE * 4  # 128: safe bm/bn/bk for the MXU kernel
    arrays = [_pad2(am, tile, tile), _pad2(bmant, tile, tile)]

    def one(args):
        a2, b2 = args
        return int8_matmul_pallas(a2, jnp.swapaxes(b2, -1, -2), scale,
                                  bm=tile, bn=tile, bk=tile,
                                  interpret=interpret)

    y, = _batched_call(one, arrays, nbatch, [(m, n)])
    return y


# ---------------------------------------------------------------------------
# cross-op fusion: norm->quantize->GEMM, GEMM epilogues, decode megakernel
# (docs/KERNELS.md §Cross-op fusion)
# ---------------------------------------------------------------------------
#
# Three chain ops extend the per-contraction dispatch above.  They share its
# machinery — shape-keyed autotune, VMEM residency predicates, the
# degradation ladder, Decision records — but their ladder is two-runged:
# there is no unfused middle pipeline, so a failed chain kernel degrades
# straight to the bit-exact jnp mirror built from the same block-core
# functions (``kernels.fused_chain`` / the ``gemm_epi_ref`` mirror).
#
# Numerics contract: the *epilogue* chain is bit-identical to the unfused
# composition (same f32 ops, same out-quantize under the q-out key-folding
# contract), so routing it is numerically invisible.  ``norm_gemm`` and
# ``decode_block`` define their own fx-lite per-row datapath (the PR-5
# fused-attention precedent): fused-vs-unfused may deviate, fused-vs-mirror
# must not — which is why planning JNP at trace time means "caller keeps
# the established unfused seam", while a *runtime* degrade inside the
# runner lands on the mirror and changes cost, never results.


def _norm_gemm_vmem_bytes(bm: int, kp: int, n: int, stochastic: bool,
                          emit_residuals: bool) -> int:
    """Residency estimate for one fused norm->quantize->GEMM instance: the
    f32 x strip + its two rounding-bit strips (double-buffered), the
    resident int8 weight mantissas + per-column exponents, the f32 output
    strip and the int8/meta residual strips."""
    r8 = 4 if stochastic else 0
    strip = (4 + 2 * r8) * bm * kp + 4 * bm * n
    if emit_residuals:
        strip += 2 * bm * kp + 4 * bm * _LANE
    resident = 1 * n * kp + 4 * n + 2 * 4 * kp
    return 2 * strip + resident


def _epi_vmem_bytes(kind: str, bm: int, kp: int, np_: int, n_out: int,
                    stochastic: bool, bias: bool, act: bool,
                    out_q: bool) -> int:
    """Residency estimate for one GEMM+epilogue instance: the base GEMM
    kind's footprint plus the bias row, the out-quantize rounding-bit
    strip and the pre-activation residual strip."""
    r8 = 4 if stochastic else 0
    extra = (4 * np_ if bias else 0)
    if out_q:
        extra += 2 * r8 * bm * n_out
    if act:
        extra += 2 * 4 * bm * np_
    return _vmem_bytes(kind, bm, kp, np_, 0) + extra


def _decode_block_vmem_bytes(b: int, d: int, n_ff: int, t: int, hq: int,
                             hkv: int, dh: int) -> int:
    """Residency estimate for one whole-block decode instance (grid=(1,),
    so each block is held once, not double-buffered): every weight
    mantissa, the qcache band and a few f32 working tiles the width of the
    widest intermediate.  It stays just above what Mosaic allocates (for
    a v5e: 18.83 MiB for a 19.01 MiB estimate at d 896, d_ff 6144)."""
    w_i8 = d * (hq + 2 * hkv) * dh + hq * dh * d + 2 * d * n_ff + n_ff * d
    w_exp = 4 * ((hq + 2 * hkv) * dh + d + 2 * n_ff + d + 2 * d)
    cache = b * hkv * t * (2 * 1 * dh + 2 * 4)
    widest = max((hq + 2 * hkv) * dh, 2 * n_ff, d)
    work = 6 * 4 * b * widest + 4 * b * hq * t
    return w_i8 + w_exp + cache + work


def plan_norm_gemm(op: str, m: int, k: int, n: int, cfg: QuantConfig, *,
                   kernel_mode: str = "auto", backend: Optional[str] = None,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET,
                   emit_residuals: bool = True,
                   autotune_measure: bool = False) -> Decision:
    """Choose the execution path for one fused norm->quantize->GEMM.

    ``m`` rows of width ``k`` (the normalized axis), projected to ``n``
    outputs.  FUSED runs ``kernels.fused_chain.fused_norm_gemm_pallas``;
    JNP means the caller keeps the established unfused seam (fx qnorm ->
    quantize -> dispatched GEMM) — the chain defines its own numerics, so
    only a *runtime* degrade lands on the bit-exact mirror.
    """
    backend = backend or jax.default_backend()
    interpret = backend != "tpu"

    def decide(path, reason, bm=0, atkey=""):
        return _record(Decision(op, path, reason, m, k, n, bm, interpret,
                                "norm_gemm", atkey=atkey))

    if kernel_mode not in ("auto", "fused", "unfused", "jnp"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    if kernel_mode == "jnp":
        return decide(JNP, "kernel_mode=jnp")
    if kernel_mode == "unfused":
        return decide(JNP, "chain ops have no unfused pipeline")
    if cfg.bits != 8:
        return decide(JNP, f"bits={cfg.bits} (kernels are int8-only)")
    if kernel_mode == "auto" and interpret:
        return decide(JNP, f"auto keeps the unfused seam on backend={backend}")
    if not interpret and _on_multi_device_mesh():
        return decide(JNP, UNPARTITIONED)
    kp = round_up(k, _LANE)
    np_ = round_up(n, _LANE)

    def fits(bm):
        return _norm_gemm_vmem_bytes(bm, kp, np_, cfg.stochastic,
                                     emit_residuals) <= vmem_budget

    key = autotune.shape_key("norm_gemm", m, k, n, cfg.bits, 0, backend)
    measure = ((autotune_measure or autotune.autotune_enabled_by_env())
               and backend == jax.default_backend())
    bench = (_make_norm_gemm_bench(m, k, n, cfg, interpret)
             if measure else None)
    if op in _disabled_ops:
        return decide(JNP, OP_DISABLED)
    bm = autotune.select_bm(key, m, fits, measure=measure, bench=bench)
    if bm == autotune.JNP_FALLBACK:
        return decide(JNP, "autotune: jnp mirror measured faster", atkey=key)
    if bm:
        return decide(FUSED, "fused chain fits VMEM budget", bm, atkey=key)
    return decide(JNP, f"no bm candidate fits vmem_budget={vmem_budget}")


def _make_norm_gemm_bench(m: int, k: int, n: int, cfg: QuantConfig,
                          interpret: bool):
    """bench(bm) -> µs over synthetic operands (norm_gemm autotune)."""
    from .fused_chain import fused_norm_gemm_pallas

    def bench(bm: int) -> float:
        rng = np.random.RandomState(0)
        mp = round_up(max(m, 1), bm)
        kp = round_up(k, _LANE)
        np_ = round_up(n, _LANE)
        x = jnp.asarray(rng.randn(mp, kp).astype(np.float32))
        rin = jnp.asarray(rng.randint(0, 2 ** 32, (mp, kp), np.uint32))
        rout = jnp.asarray(rng.randint(0, 2 ** 32, (mp, kp), np.uint32))
        gm = jnp.asarray(rng.randint(1 << 14, 1 << 15, (1, kp), np.int32))
        wm = jnp.asarray(rng.randint(-127, 128, (np_, kp), np.int8))
        se_w = jnp.full((1, np_), -7, jnp.int32)
        if not cfg.stochastic:
            rin = rout = None

        def fn():
            return jax.block_until_ready(fused_norm_gemm_pallas(
                x, rin, rout, gm, -15, None, 0, wm, se_w, n=k, p=cfg.p,
                bm=bm, stochastic=cfg.stochastic, interpret=interpret,
                emit_residuals=True))

        return autotune.time_call_us(fn)

    return bench


def run_norm_gemm(x, rand_in, rand_out, gm, se_g, beta_m, se_b, w_m, se_w,
                  dec: Decision, *, n: int, p: int = 7, eps_m: int = 1,
                  eps_e: int = -32, center: bool = False,
                  stochastic: bool = True, nbatch: int = 0,
                  want_residuals: bool = True):
    """Execute a FUSED-planned norm->quantize->GEMM with mirror degrade.

    x (*B, M, K) f32 (K = true width ``n``), rand_in/rand_out (*B, M, Kp)
    uint32 drawn at the lane-padded width (None when deterministic), gamma
    and optional beta as (1, Kp) int32 fx mantissas, weight mantissas
    (N, Kp) int8 with (1, N) int32 per-column exponents.  Returns
    ``[y (*B, M, N)]`` or ``[y, xq, meta, c]`` with per-row residuals.
    """
    from . import fused_chain as fc

    m, k = x.shape[-2], x.shape[-1]
    kp = round_up(k, _LANE)
    nn = w_m.shape[0]
    np_ = round_up(nn, _LANE)
    xp = _pad2(x, 1, kp)
    gm_p = _pad2(gm, 1, kp)
    beta_p = None if beta_m is None else _pad2(beta_m, 1, kp)
    wm_p = _pad2(w_m, np_, kp)
    sw_p = _pad2(se_w, 1, np_)
    kw = dict(n=n, p=p, eps_m=eps_m, eps_e=eps_e, center=center)
    crops = [(m, nn)] + ([(m, kp), (m, 128), (m, kp)] if want_residuals
                         else [])

    def run_kernel(d):
        arrays = [_pad2(xp, d.bm, kp)] + \
            ([_pad2(rand_in, d.bm, kp), _pad2(rand_out, d.bm, kp)]
             if stochastic else [])

        def one(args):
            if stochastic:
                x2, rin2, rout2 = args
            else:
                (x2,), rin2, rout2 = args, None, None
            return fc.fused_norm_gemm_pallas(
                x2, rin2, rout2, gm_p, se_g, beta_p, se_b, wm_p, sw_p,
                bm=d.bm, stochastic=stochastic, interpret=d.interpret,
                emit_residuals=want_residuals, **kw)

        return _batched_call(one, arrays, nbatch, crops)

    def run_jnp(d):
        arrays = [xp] + ([rand_in, rand_out] if stochastic else [])

        def one(args):
            if stochastic:
                x2, rin2, rout2 = args
            else:
                (x2,), rin2, rout2 = args, None, None
            return fc.norm_gemm_ref(x2, rin2, rout2, gm_p, se_g, beta_p,
                                    se_b, wm_p, sw_p,
                                    emit_residuals=want_residuals, **kw)

        return _batched_call(one, arrays, nbatch, crops)

    return _with_ladder(dec, run_kernel, run_jnp)


def plan_epilogue(op: str, m: int, k: int, n: int, cfg: QuantConfig, *,
                  kind: str = "qq", cfg2: Optional[QuantConfig] = None,
                  act: Optional[str] = None, bias: bool = False,
                  out_q: bool = False, kernel_mode: str = "auto",
                  accum_chunk: int = 65536,
                  backend: Optional[str] = None,
                  vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  autotune_measure: bool = False) -> Decision:
    """Choose the execution path for one GEMM + bias/act/out-quantize chain.

    Same gates as :func:`plan_contract` (int8-only, per-tensor-only,
    accumulator bounds) plus glu alignment; autotuned under its own
    ``<kind>_epi`` shape keys.  JNP keeps the unfused composition — which
    is bit-identical to the fused chain, so this plan only moves cost.
    """
    backend = backend or jax.default_backend()
    interpret = backend != "tpu"
    ekind = f"{kind}_epi"

    def decide(path, reason, bm=0, atkey=""):
        return _record(Decision(op, path, reason, m, k, n, bm, interpret,
                                ekind, atkey=atkey))

    if kernel_mode not in ("auto", "fused", "unfused", "jnp"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    if kernel_mode == "jnp":
        return decide(JNP, "kernel_mode=jnp")
    if kernel_mode == "unfused":
        return decide(JNP, "chain ops have no unfused pipeline")
    bits = {cfg.bits} | ({cfg2.bits} if cfg2 is not None else set())
    if bits != {8}:
        return decide(JNP, f"bits={sorted(bits)} (kernels are int8-only)")
    if cfg.block != PER_TENSOR or (cfg2 is not None
                                   and cfg2.block != PER_TENSOR):
        return decide(JNP, "epilogue chains are per-tensor only")
    if kernel_mode == "auto" and interpret:
        return decide(JNP, f"auto keeps the jnp oracle on backend={backend}")
    if not interpret and _on_multi_device_mesh():
        return decide(JNP, UNPARTITIONED)
    if k > accum_chunk:
        return decide(JNP, f"K={k} > accum_chunk={accum_chunk} "
                           "(flush emulation stays on jnp)")
    if k * 127 * 127 >= (1 << 31):
        return decide(JNP, f"K={k} overflows the int32 accumulator")
    glu = (act or "").endswith("_glu")
    if glu and (n % (2 * _LANE) or n % 2):
        return decide(JNP, "glu halves must be lane-aligned")
    kp = round_up(k, _LANE)
    np_ = round_up(n, _LANE)
    n_out = n // 2 if glu else np_
    base = "qq" if kind == "qq" else ("qi" if kind == "qi" else "ii")

    def fits(bm):
        return _epi_vmem_bytes(base, bm, kp, np_, n_out, cfg.stochastic,
                               bias, act is not None, out_q) <= vmem_budget

    key = autotune.shape_key(ekind, m, k, n, cfg.bits, 0, backend)
    measure = ((autotune_measure or autotune.autotune_enabled_by_env())
               and backend == jax.default_backend())
    bench = (_make_epi_bench(kind, m, k, n, cfg, act, bias, out_q, interpret)
             if measure else None)
    if op in _disabled_ops:
        return decide(JNP, OP_DISABLED)
    bm = autotune.select_bm(key, m, fits, measure=measure, bench=bench)
    if bm == autotune.JNP_FALLBACK:
        return decide(JNP, "autotune: jnp mirror measured faster", atkey=key)
    if bm:
        return decide(FUSED, "fused chain fits VMEM budget", bm, atkey=key)
    return decide(JNP, f"no bm candidate fits vmem_budget={vmem_budget}")


def _make_epi_bench(kind: str, m: int, k: int, n: int, cfg: QuantConfig,
                    act, bias: bool, out_q: bool, interpret: bool):
    """bench(bm) -> µs over synthetic operands (epilogue autotune)."""
    from .fused_linear import fused_gemm_epi_pallas

    def bench(bm: int) -> float:
        rng = np.random.RandomState(0)
        mp = round_up(max(m, 1), bm)
        kp = round_up(k, _LANE)
        np_ = round_up(n, _LANE)
        n_out = n // 2 if (act or "").endswith("_glu") else np_
        sr = cfg.stochastic
        if kind == "ii":
            a = jnp.asarray(rng.randint(-127, 128, (mp, kp), np.int8))
            ra = None
        else:
            a = jnp.asarray(rng.randn(mp, kp).astype(np.float32))
            ra = (jnp.asarray(rng.randint(0, 2 ** 32, (mp, kp), np.uint32))
                  if sr else None)
        if kind == "qq":
            b = jnp.asarray(rng.randn(np_, kp).astype(np.float32))
            rb = (jnp.asarray(rng.randint(0, 2 ** 32, (np_, kp), np.uint32))
                  if sr else None)
        else:
            b = jnp.asarray(rng.randint(-127, 128, (np_, kp), np.int8))
            rb = None
        bias_row = (jnp.asarray(rng.randn(1, np_).astype(np.float32))
                    if bias else None)
        rq = (jnp.asarray(rng.randint(0, 2 ** 32, (mp, n_out), np.uint32))
              if (out_q and sr) else None)
        e = jnp.int32(130)

        def fn():
            return jax.block_until_ready(fused_gemm_epi_pallas(
                a, ra, b, rb, bias_row, rq, e, e, kind=kind, p=cfg.p,
                bm=bm, stochastic=sr, act=act, out_q=out_q,
                interpret=interpret))

        return autotune.time_call_us(fn)

    return bench


def contract_epi(a, b, dec: Decision, *, cfg: Optional[QuantConfig] = None,
                 ka=None, kb=None, bias=None, act: Optional[str] = None,
                 qcfg: Optional[QuantConfig] = None, kq=None,
                 nbatch: int = 0, want_residuals: bool = True):
    """GEMM with the fused bias/activation/out-quantize epilogue.

    Operand roles follow ``dec.kind`` (``qq_epi`` / ``qi_epi`` / ``ii_epi``
    / ``pp_epi``): ``qq`` takes a, b f32 quantized in-op under ``cfg`` with
    keys ``ka``/``kb``; ``qi`` takes a f32 + b :class:`BFP`; ``ii``/``pp``
    take both as :class:`BFP`.  ``qcfg``/``kq`` (per-tensor) switch on the
    fused out-quantize — bit-identical to quantizing the unfused f32
    output with the same key (the q-out key-folding contract).

    Returns ``(out, aq, bq, ylin)``: ``out`` f32 or a :class:`BFP` when
    ``qcfg`` is given; ``aq``/``bq`` the in-op quantize residuals (None
    when that side was pre-quantized or residuals are off); ``ylin`` the
    pre-activation f32 (None unless ``act`` and residuals).
    """
    kind = dec.kind.split("_")[0]
    kind_k = "ii" if kind == "pp" else kind
    out_q = qcfg is not None
    if kind in ("ii", "pp"):
        a_arr, ea, pa_ = a.m, a.e, a.cfg.p
    else:
        a_arr, pa_ = a, cfg.p
        ea = ref.max_biased_exp_ref(a)
    if kind == "qq":
        b_arr, pb_ = b, cfg.p
        eb = ref.max_biased_exp_ref(b)
    else:
        b_arr, eb, pb_ = b.m, b.e, b.cfg.p
    # One stochastic flag drives both the in-op and the out-op quantize
    # (the kernel streams one rand array per role); mixed SR/nearest
    # configs have no fused path and must be planned JNP by the caller.
    if cfg is not None and out_q:
        assert qcfg.stochastic == cfg.stochastic, (cfg, qcfg)
    sr = (cfg.stochastic if cfg is not None
          else (out_q and qcfg.stochastic))
    m, k = a_arr.shape[-2], a_arr.shape[-1]
    n = b_arr.shape[-2]
    glu = (act or "").endswith("_glu")
    n_out = n // 2 if glu else n
    assert nbatch == 0 or not out_q, \
        "fused out-quantize is 2-D only (per-tensor e spans the whole call)"
    ra = (rounding_bits(ka, a_arr.shape, cfg.rng)
          if (kind != "ii" and kind != "pp" and sr) else None)
    rb = (rounding_bits(kb, b_arr.shape, cfg.rng)
          if (kind == "qq" and sr) else None)
    rq = (rounding_bits(kq, a_arr.shape[:-2] + (m, n_out), qcfg.rng)
          if (out_q and qcfg.stochastic) else None)
    qp = qcfg.p if out_q else 7

    def outs_spec():
        crops = [(m, n_out)]
        if out_q:
            crops.append((1, 128))
        if kind_k != "ii" and want_residuals:
            crops.append((m, k))
        if kind == "qq" and want_residuals:
            crops.append((n, k))
        if act is not None and want_residuals:
            crops.append((m, n))
        return crops

    def package(outs, d):
        it = iter(outs)
        y = next(it)
        if out_q:
            emeta = next(it)
            e_out = emeta[..., 0, 0].astype(jnp.int32)
            out = BFP(y, e_out, qcfg)
        else:
            out = y
        aq = bq = ylin = None
        if kind_k != "ii" and want_residuals:
            aq = BFP(next(it), jnp.asarray(ea, jnp.int32), cfg)
        if kind == "qq" and want_residuals:
            bq = BFP(next(it), jnp.asarray(eb, jnp.int32), cfg)
        if act is not None and want_residuals:
            ylin = next(it)
        return out, aq, bq, ylin

    def run_kernel(d):
        pad_rows = d.bm
        arrays = [_pad2(a_arr, pad_rows, _LANE)]
        if ra is not None:
            arrays.append(_pad2(ra, pad_rows, _LANE))
        arrays.append(_pad2(b_arr, _LANE, _LANE))
        if rb is not None:
            arrays.append(_pad2(rb, _LANE, _LANE))
        if bias is not None:
            arrays.append(_pad2(bias, 1, _LANE))
        if rq is not None:
            arrays.append(_pad2(rq, pad_rows, _LANE))
        emit = want_residuals

        def one(args):
            it = iter(args)
            a2 = next(it)
            ra2 = next(it) if ra is not None else None
            b2 = next(it)
            rb2 = next(it) if rb is not None else None
            bias2 = next(it) if bias is not None else None
            rq2 = next(it) if rq is not None else None
            return fused_gemm_epi_pallas(
                a2, ra2, b2, rb2, bias2, rq2, ea, eb, kind=kind_k,
                pa=pa_, pb=pb_, bm=d.bm, stochastic=sr, act=act,
                out_q=out_q, qp=qp, m_true=m, emit_residuals=emit,
                interpret=d.interpret)

        outs = _batched_call(one, arrays, nbatch, outs_spec())
        return package(outs, d)

    def run_jnp(d):
        arrays = [a_arr]
        if ra is not None:
            arrays.append(ra)
        arrays.append(b_arr)
        if rb is not None:
            arrays.append(rb)
        if bias is not None:
            arrays.append(bias)
        if rq is not None:
            arrays.append(rq)

        def one(args):
            it = iter(args)
            a2 = next(it)
            ra2 = next(it) if ra is not None else None
            b2 = next(it)
            rb2 = next(it) if rb is not None else None
            bias2 = next(it) if bias is not None else None
            rq2 = next(it) if rq is not None else None
            return gemm_epi_ref(
                a2, ra2, b2, rb2, bias2, rq2, ea, eb, kind=kind_k,
                pa=pa_, pb=pb_, stochastic=sr, act=act, out_q=out_q,
                qp=qp, m_true=None, emit_residuals=want_residuals)

        outs = _batched_call(one, arrays, nbatch, outs_spec())
        return package(outs, d)

    return _with_ladder(dec, run_kernel, run_jnp, cfg)


def plan_decode_block(op: str, b: int, d: int, n_ff: int, t: int, hq: int,
                      hkv: int, dh: int, cfg: QuantConfig, *,
                      kernel_mode: str = "auto",
                      backend: Optional[str] = None,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET) -> Decision:
    """Choose the execution path for one whole-block decode megakernel.

    One ``pallas_call`` per layer, grid=(1,): everything must be resident,
    so the only knob is the residency predicate (no autotuned strip).
    JNP keeps the established per-op decode path.
    """
    backend = backend or jax.default_backend()
    interpret = backend != "tpu"

    def decide(path, reason):
        return _record(Decision(op, path, reason, b, d, n_ff, 0, interpret,
                                "decode_block", bt=t))

    if kernel_mode not in ("auto", "fused", "unfused", "jnp"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    if kernel_mode == "jnp":
        return decide(JNP, "kernel_mode=jnp")
    if kernel_mode == "unfused":
        return decide(JNP, "chain ops have no unfused pipeline")
    if cfg.bits != 8:
        return decide(JNP, f"bits={cfg.bits} (kernels are int8-only)")
    if kernel_mode == "auto" and interpret:
        return decide(JNP, f"auto keeps the per-op path on backend={backend}")
    if not interpret and _on_multi_device_mesh():
        return decide(JNP, UNPARTITIONED)
    if _decode_block_vmem_bytes(b, d, n_ff, t, hq, hkv, dh) > vmem_budget:
        return decide(JNP, f"no residency fits vmem_budget={vmem_budget}")
    if op in _disabled_ops:
        return decide(JNP, OP_DISABLED)
    return decide(FUSED, "decode block fits VMEM budget")


def run_decode_block(x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu, wd_m, se_d,
                     g1m, g2m, km, ke, vm, ve, cossin, pos, dec: Decision, *,
                     n_d: int, n_ff: int, hq: int, hkv: int, dh: int,
                     p: int = 7, window: int = 0, eps_m: int = 1,
                     eps_e: int = -32, se_g1: int = 0, se_g2: int = 0):
    """Execute a FUSED-planned decode block with mirror degrade.

    Deterministic and gradient-free; returns (x_out, k_new, ek_new, v_new,
    ev_new) — the fresh cache rows are the caller's to append (they equal
    ``quantize_cache`` rows bit-exactly)."""
    from . import fused_chain as fc

    kw = dict(n_d=n_d, n_ff=n_ff, hq=hq, hkv=hkv, dh=dh, p=p, window=window,
              eps_m=eps_m, eps_e=eps_e, se_g1=se_g1, se_g2=se_g2)
    args = (x, wqkv_m, se_qkv, wo_m, se_o, wgu_m, se_gu, wd_m, se_d,
            g1m, g2m, km, ke, vm, ve, cossin, pos)

    def run_kernel(d):
        return fc.fused_decode_block_pallas(*args, interpret=d.interpret,
                                            **kw)

    def run_jnp(d):
        return fc.decode_block_ref(*args, **kw)

    return _with_ladder(dec, run_kernel, run_jnp)


# ---------------------------------------------------------------------------
# cross-op chains: analytic traffic models (BENCH_kernels fused-chain rows)
# ---------------------------------------------------------------------------

def norm_gemm_bytes_moved(path: str, m: int, k: int, n: int, *,
                          stochastic: bool = True,
                          center: bool = False) -> int:
    """Analytic HBM traffic of one norm->quantize->GEMM chain, in bytes.

    ``fused``: x read once in f32 with its two rounding-bit strips, weight
    mantissas + per-column exponents read once, the f32 output written
    once, plus the per-row backward residuals (xq, c mantissas and the
    meta row).  Anything else is the unfused composition: the fx-norm
    pipeline's f32 read + write of the activation (the HBM round-trip the
    fusion deletes), then the dispatched GEMM at its own best (fused) cost
    with a fresh a-side quantize (``kind="qi"``: the weight is the
    pre-quantized operand)."""
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    resid = 2 * i8 * m * k + 4 * m * 1
    if path == FUSED:
        return (f32 * m * k + 2 * r8 * m * k + i8 * n * k + 4 * n
                + f32 * m * n + resid)
    norm_io = 2 * f32 * m * k + r8 * m * k
    gemm = bytes_moved(FUSED, m, k, n, stochastic=stochastic, kind="qi")
    return norm_io + gemm


def epilogue_bytes_moved(path: str, m: int, k: int, n: int, *,
                         stochastic: bool = True, kind: str = "qq",
                         bias: bool = False, act: bool = False,
                         out_q: bool = False) -> int:
    """Analytic HBM traffic of one GEMM+bias/act/out-quantize chain.

    ``fused``: the fused GEMM's own traffic, with the f32 output write
    replaced by the int8 mantissa write (+ rounding bits in) when
    ``out_q``, plus the bias row and the pre-activation residual strip.
    Anything else adds the round-trips the fusion deletes: the f32 output
    re-read by the bias/act stage, its f32 re-write, and the out-quantize
    scan + quantizer reads + int8 write of ``core.qops._quantize_out``."""
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    base = bytes_moved(FUSED, m, k, n, stochastic=stochastic, kind=kind)
    n_out = n // 2 if act == "glu" else n
    extra = (f32 * n if bias else 0) + (f32 * m * n if act else 0)
    if path == FUSED:
        if out_q:
            base = base - f32 * m * n + r8 * m * n_out + i8 * m * n_out + 512
        return base + extra
    seams = 0
    if bias or act:
        seams += 2 * f32 * m * n                  # y re-read + re-write
    if out_q:
        seams += 2 * f32 * m * n_out + r8 * m * n_out + i8 * m * n_out
    return base + extra + seams


def decode_block_bytes_moved(path: str, b: int, d: int, n_ff: int, t: int,
                             hq: int, hkv: int, dh: int, *,
                             stochastic: bool = False) -> int:
    """Analytic HBM traffic of one decoder layer's decode step.

    ``fused``: every weight mantissa and qcache row read exactly once, the
    f32 activation in and out, the fresh quantized k/v rows written.
    Anything else is the per-op composition: the same weight and cache
    reads, plus the inter-op f32 round-trips (norm in/out twice, the QKV /
    attention / out-proj / gate-up / activation / down seams) and each
    GEMM's own quantize-stage traffic."""
    f32, i8 = 4, 1
    n_qkv = (hq + 2 * hkv) * dh
    weights = (i8 * (d * n_qkv + hq * dh * d + 2 * d * n_ff + n_ff * d)
               + 4 * (n_qkv + d + 2 * n_ff + d))
    cache = 2 * (i8 * b * hkv * t * dh + 4 * b * hkv * t)
    fresh_rows = 2 * (i8 * b * hkv * dh + 4 * b * hkv)
    io = 2 * f32 * b * d
    if path == FUSED:
        return weights + cache + fresh_rows + io
    # per-op composition: every seam round-trips f32 through HBM
    seams = f32 * b * (2 * 2 * d          # two norms: in + out
                       + 2 * n_qkv        # qkv out + attention in
                       + 2 * hq * dh      # attention out + out-proj in
                       + 2 * d            # out-proj out + residual
                       + 2 * 2 * n_ff     # gate|up out + act in/out
                       + 2 * n_ff         # down in
                       + 2 * d)           # down out + residual
    quant = 5 * (f32 + f32 + i8) * b * d  # five per-row activation quantizes
    return weights + cache + fresh_rows + io + seams + quant
