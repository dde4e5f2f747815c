"""Batched serving driver: prefill a prompt batch, decode greedily.

Exercises the integer inference pipeline (int8 matmuls everywhere,
KV/state caches per family) and reports prefill + per-token decode
latency and tokens/s.  With ``qweights`` (the default for the int8
policy) the model's GEMM weights are quantized exactly ONCE at load —
the Jacob-et-al. deployment contract — so prefill and decode run fully
pre-quantized contractions (dispatch kinds ``pp``/``qi``) and never
touch a float32 weight; ``--per-call-weights`` restores the legacy
quantize-per-GEMM path for comparison.  ``--qcache`` completes the
currency trilogy at decode time: prefill writes int8 cache rows exactly
once, decode appends one quantized row per step, and attention consumes
the mantissas directly (docs/SERVING.md) — the analytic report then
shows the per-decode-step cache-operand traffic cut next to the weight
one.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..core.health import bfp_tree_stats
from ..core.policy import FLOAT32, PAPER_INT8
from ..kernels import dispatch
from ..models import (get_cache_layout, get_cache_page_spec,
                      get_draft_support, get_model)
from .compile_cache import enable_compile_cache
from .steps import (cache_template, make_decode_step, make_prefill_step,
                    quantize_serving_params)

POLICIES = {"int8": PAPER_INT8, "float32": FLOAT32}


class ServeConfigError(ValueError):
    """A serving request that can never run (unknown arch, contradictory
    flags).  ``main`` turns it into a clean non-zero exit — no traceback."""

# Attention KV leaves are *consumed by integer GEMMs* each decode step (the
# float pipeline re-quantizes them in-op; qcache reads mantissas); every
# other cache leaf is a register/state read+written elementwise.
_KV_LEAVES = ("k", "v", "xk", "xv")


def _dense_gemm_shapes(cfg, m: int):
    """(M, K, N) of every per-layer weight GEMM + the lm head, for the
    analytic traffic model.  Only valid for the dense-FFN transformer
    families ("dense", and "vlm" whose patch frontend is an external
    stub); MoE expert GEMMs have a different shape set."""
    d, hq, hkv, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                          cfg.d_ff)
    per_layer = [(m, d, hq * hd), (m, d, hkv * hd), (m, d, hkv * hd),
                 (m, hq * hd, d), (m, d, ff), (m, d, ff), (m, ff, d)]
    return per_layer * cfg.n_layers + [(m, d, cfg.vocab)]


def weight_traffic_report(cfg, batch: int, prompt_len: int) -> dict:
    """Analytic HBM traffic of the model's weight GEMMs, per prefill call
    and per decode step: weights quantized per call (kind "qq") vs
    quantized once at load (kind "qi"), using the fused-path bytes-moved
    model of ``kernels.dispatch`` (whole-GEMM totals: activation reads
    and the output write are included and identical on both sides).
    ``weight_side`` isolates the weight-operand component alone — the
    bytes the persistent currency actually removes: f32 scan + quantizer
    f32/rand reads + int8 residual write vs one int8 mantissa read
    (M-independent, so one row covers both phases)."""
    out = {}
    for phase, m in (("prefill", batch * prompt_len), ("decode", batch)):
        per_call = sum(dispatch.bytes_moved(dispatch.FUSED, m, k, n, kind="qq")
                       for _, k, n in _dense_gemm_shapes(cfg, m))
        pre_q = sum(dispatch.bytes_moved(dispatch.FUSED, m, k, n, kind="qi")
                    for _, k, n in _dense_gemm_shapes(cfg, m))
        out[phase] = {"per_call_weight_quant_bytes": per_call,
                      "load_time_quantized_bytes": pre_q,
                      "reduction_pct": round(100.0 * (1 - pre_q / per_call), 2)}
    f32, r8, i8 = 4, 4, 1
    wk = sum(n * k for _, k, n in _dense_gemm_shapes(cfg, 1))
    out["weight_side"] = {
        "per_call_weight_quant_bytes": (f32 + f32 + r8 + i8) * wk,
        "load_time_quantized_bytes": i8 * wk,
        "reduction_pct": round(100.0 * (1 - i8 / (f32 + f32 + r8 + i8)), 2)}
    return out


def cache_traffic_report(cfg, policy, batch: int, prompt_len: int,
                         max_len: int, page_size: Optional[int] = None) -> dict:
    """Analytic per-decode-step HBM traffic of the CACHE operands
    (docs/SERVING.md): float caches (decode re-quantizes the whole K/V
    operand inside attention each step, and reads/writes f32 recurrent
    state) vs the qcache currency (one int8/int16 mantissa read + one
    int32 exponent read per row).  Windowed archs only touch the attention
    band, and is modeled so.  ``gemm`` rows additionally give the
    whole-contraction comparison of the two decode attention GEMMs through
    the ``bytes_moved`` kinds they actually plan (``qq`` fresh vs ``qi``
    pre-quantized cache operand).  With a ``page_size`` the report adds an
    ``engine`` row: the same cache operands served per-lane through the
    block-paged pool (``plan_batched_decode``) — the pool's only overhead
    over a private contiguous cache is the page-table walk."""
    layout = get_cache_layout(cfg)
    tmpl = cache_template(cfg, batch, max_len, src_len=prompt_len)
    f_total = q_total = 0
    for name, kind in layout.items():
        shape = tuple(tmpl[name].shape)
        if name in ("k", "v") and cfg.local_window:
            shape = shape[:-2] + (min(cfg.local_window, max_len), shape[-1])
        rows = 1
        for dim in shape[:-1]:
            rows *= dim
        rewritten = name not in _KV_LEAVES
        bits = policy.cache_cfg_for(kind, shape[-1]).bits
        f_total += dispatch.cache_operand_bytes(rows, shape[-1],
                                                quantized=False,
                                                rewritten=rewritten)
        q_total += dispatch.cache_operand_bytes(rows, shape[-1],
                                                quantized=True, bits=bits,
                                                rewritten=rewritten)
    out = {"cache_side": {
        "float_cache_bytes": f_total, "qcache_bytes": q_total,
        "reduction_pct": round(100.0 * (1 - q_total / f_total), 2)}}
    if cfg.family in ("dense", "vlm", "moe"):
        g = cfg.n_heads // cfg.n_kv_heads
        n_bh = batch * cfg.n_kv_heads * cfg.n_layers
        whole = {}
        for label, quant_kind in (("float_cache_bytes", "qq"),
                                  ("qcache_bytes", "qi")):
            qk = dispatch.bytes_moved(dispatch.FUSED, g, cfg.hd, max_len,
                                      kind=quant_kind)
            pv = dispatch.bytes_moved(dispatch.FUSED, g, max_len, cfg.hd,
                                      kind=quant_kind)
            whole[label] = n_bh * (qk + pv)
        whole["reduction_pct"] = round(
            100.0 * (1 - whole["qcache_bytes"] / whole["float_cache_bytes"]), 2)
        out["gemm"] = whole
    if page_size:
        tmpl1 = cache_template(cfg, 1, max_len, src_len=prompt_len,
                               policy=policy)
        shapes = {}
        for name in layout:
            leaf = tmpl1[name]
            shapes[name] = tuple(leaf.m.shape if hasattr(leaf, "m")
                                 else leaf.shape)
        bits_for = lambda kind, row: policy.cache_cfg_for(kind, row).bits
        plan = dispatch.plan_batched_decode(batch, layout, shapes, bits_for,
                                            page_rows=page_size)
        contiguous = 0
        for name, kind in layout.items():
            rows = 1
            for dim in shapes[name][:-1]:
                rows *= dim
            contiguous += dispatch.cache_operand_bytes(
                rows, shapes[name][-1], quantized=True,
                bits=bits_for(kind, shapes[name][-1]),
                rewritten=kind == "state")
        plan["contiguous_bytes_per_lane"] = contiguous
        plan["page_table_overhead_pct"] = round(
            100.0 * (plan["cache_bytes_per_lane"] / max(contiguous, 1) - 1), 2)
        out["engine"] = plan
    return out


def speculative_traffic_report(cfg, policy, k: int, draft_layers: int,
                               max_len: int) -> dict:
    """Analytic HBM traffic of one speculative decode round vs the
    sequential steps it replaces (docs/SERVING.md §Speculative decoding):
    per-step weight-operand and cache-operand bytes for the target and
    its ``draft_layers``-deep truncation feed
    ``dispatch.plan_speculative_verify``, which prices the k draft steps
    + one verify pass and reports the acceptance break-even.  The
    ``decision`` row is the ``plan_attention`` Decision the deployment
    target (backend="tpu") would record for the banded (k+1)-row verify
    over the existing qcache rows — the fused-attention prefill shape of
    the verify pass."""
    from ..core.bfp import PER_TENSOR, QuantConfig

    i8 = 1

    def per_step(c):
        wk = sum(n * kk for _, kk, n in _dense_gemm_shapes(c, 1))
        cache = 0
        layout = get_cache_layout(c)
        tmpl = cache_template(c, 1, max_len, src_len=max_len)
        for name, kind in layout.items():
            shape = tuple(tmpl[name].shape)
            rows = 1
            for dim in shape[:-1]:
                rows *= dim
            cache += dispatch.cache_operand_bytes(
                rows, shape[-1], quantized=True,
                bits=policy.cache_cfg_for(kind, shape[-1]).bits,
                rewritten=name not in _KV_LEAVES)
        return i8 * wk, cache

    wb, cb = per_step(cfg)
    dwb, dcb = per_step(dataclasses.replace(cfg, n_layers=draft_layers))
    plan = dispatch.plan_speculative_verify(
        k, draft_layers, cfg.n_layers, weight_bytes=wb, cache_bytes=cb,
        draft_weight_bytes=dwb, draft_cache_bytes=dcb)
    g = cfg.n_heads // cfg.n_kv_heads
    cfg8 = QuantConfig(policy.fwd_bits, PER_TENSOR, policy.stochastic,
                       policy.rng)
    band = dispatch.plan_attention(
        "attn_fwd", g * (k + 1), max_len, cfg.hd, cfg8, s=k + 1, kind="pp",
        backend="tpu", kernel_mode=policy.kernel_mode)
    plan["decision"] = {"op": band.op, "kind": band.kind, "path": band.path,
                        "bq": band.bm, "bt": band.bt, "reason": band.reason}
    return plan


def attention_traffic_report(cfg, policy, batch: int, prompt_len: int,
                             max_len: int) -> dict:
    """Analytic HBM traffic of the attention contractions themselves — the
    op family the fused flash kernel owns (docs/KERNELS.md §Fused
    attention).  Per phase: the ``lax.scan`` pipeline (two dispatched
    GEMMs per KV chunk plus the score/probability round-trips) vs the
    fused one-kernel pass, summed over batch · KV-heads · layers, plus the
    Decision ``plan_attention`` would record for the deployment target
    (backend="tpu") — op, kind, path and the (bq, bt) tile geometry."""
    from ..core.bfp import PER_TENSOR, QuantConfig

    g = cfg.n_heads // cfg.n_kv_heads
    n_bh = batch * cfg.n_kv_heads * cfg.n_layers
    cfg8 = QuantConfig(policy.fwd_bits, PER_TENSOR, policy.stochastic,
                       policy.rng)
    out = {}
    # the fused *prefill* needs the qflow quantize-once operands (the
    # models' _fused_attn_eligible gate); fused decode takes a fresh
    # float query too (kind "qi"), so only prefill is qflow-conditioned.
    phases = (
        ("prefill", "attn_fwd", "pp", g * prompt_len, prompt_len,
         prompt_len, policy.qflow),
        ("decode", "attn_decode", "pp" if policy.qflow else "qi", g,
         min(cfg.local_window, max_len) if cfg.local_window else max_len,
         1, True),
    )
    chunk = cfg.attn_chunk or 1024
    for phase, op, kind, gs, t, s, eligible in phases:
        scan_b = n_bh * dispatch.attention_bytes_moved(
            "scan", gs, t, cfg.hd, chunk=chunk, op=op)
        fused_b = n_bh * dispatch.attention_bytes_moved(
            dispatch.FUSED, gs, t, cfg.hd, chunk=chunk, op=op)
        if eligible:
            plan = dispatch.plan_attention(op, gs, t, cfg.hd, cfg8, s=s,
                                           kind=kind, backend="tpu",
                                           kernel_mode=policy.kernel_mode)
            decision = {"op": plan.op, "kind": plan.kind,
                        "path": plan.path, "bq": plan.bm, "bt": plan.bt,
                        "reason": plan.reason}
        else:
            decision = {"op": op, "kind": kind, "path": "scan",
                        "bq": 0, "bt": 0,
                        "reason": "fused prefill needs policy.qflow "
                                  "(quantize-once Q/K/V operands)"}
        out[phase] = {
            "scan_bytes": scan_b, "fused_bytes": fused_b,
            "reduction_pct": round(100.0 * (1 - fused_b / scan_b), 2),
            "decision": decision}
    return out


def chain_traffic_report(cfg, policy, batch: int, prompt_len: int,
                         max_len: int) -> dict:
    """Analytic HBM traffic of the cross-op fused chains (docs/KERNELS.md
    §Cross-op fusion) vs the op-by-op compositions they replace, summed
    over layers.  ``norm_gemm`` is the pre-norm -> merged-QKV projection
    seam per prefill call; ``gemm_epilogue`` the up-projection ->
    activation (-> out-quantize under qflow) seam; ``decode_block`` one
    whole decoder layer's decode step — norm -> QKV -> decode attention
    -> out-proj -> MLP as a single kernel over the qcache rows.  Only the
    dense-FFN shape set is modeled (same caveat as the weight report)."""
    d, hq, hkv, dh, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                          cfg.d_ff)
    m = batch * prompt_len
    n_qkv = (hq + 2 * hkv) * dh
    t = min(cfg.local_window, max_len) if cfg.local_window else max_len
    rows = (
        ("norm_gemm",
         dispatch.norm_gemm_bytes_moved(dispatch.FUSED, m, d, n_qkv),
         dispatch.norm_gemm_bytes_moved(dispatch.UNFUSED, m, d, n_qkv)),
        ("gemm_epilogue",
         dispatch.epilogue_bytes_moved(dispatch.FUSED, m, d, ff, act=True,
                                       out_q=policy.qflow),
         dispatch.epilogue_bytes_moved(dispatch.UNFUSED, m, d, ff, act=True,
                                       out_q=policy.qflow)),
        ("decode_block",
         dispatch.decode_block_bytes_moved(dispatch.FUSED, batch, d, ff, t,
                                           hq, hkv, dh),
         dispatch.decode_block_bytes_moved(dispatch.UNFUSED, batch, d, ff, t,
                                           hq, hkv, dh)),
    )
    out = {}
    for op, fused_b, unfused_b in rows:
        fused_b *= cfg.n_layers
        unfused_b *= cfg.n_layers
        out[op] = {
            "unfused_bytes": unfused_b, "fused_bytes": fused_b,
            "reduction_pct": round(100.0 * (1 - fused_b / unfused_b), 2)}
    return out


def validate_request(arch: str, policy_name: str, *, batch: int = 1,
                     prompt_len: int = 1, gen: int = 1, qcache: bool = False,
                     health: bool = False, engine: bool = False,
                     page_size: int = 16, n_pages: int = 64,
                     speculate: int = 0, draft_layers: int = 0,
                     smoke: bool = True) -> None:
    """Reject impossible serving requests up front with a message that
    names the fix, instead of a traceback from deep inside model import
    or jit trace (docs/ROBUSTNESS.md §Serving).  With ``engine`` the pool
    geometry is checked too: a zero-page pool, a non-positive page size,
    or a page size that doesn't divide the cache length / attention window
    can never serve a single request."""
    if arch not in ARCH_IDS:
        raise ServeConfigError(
            f"unknown arch {arch!r}; known archs: {', '.join(ARCH_IDS)}")
    if policy_name not in POLICIES:
        raise ServeConfigError(
            f"unknown policy {policy_name!r}; known: {', '.join(POLICIES)}")
    if batch < 1 or prompt_len < 1 or gen < 1:
        raise ServeConfigError(
            f"batch/prompt-len/gen must all be >= 1, got "
            f"batch={batch} prompt_len={prompt_len} gen={gen}")
    if not POLICIES[policy_name].enabled:
        if qcache:
            raise ServeConfigError(
                "--qcache quantizes decode caches, which needs an integer "
                "policy; drop --qcache or use --policy int8")
        if health:
            raise ServeConfigError(
                "--health reports quantized-leaf saturation, which needs "
                "an integer policy; drop --health or use --policy int8")
    if engine:
        if not (POLICIES[policy_name].enabled and qcache):
            raise ServeConfigError(
                "--engine serves through the block-paged qcache pool, "
                "which needs quantized caches; add --qcache with "
                "--policy int8")
        if page_size < 1:
            raise ServeConfigError(
                f"--page-size must be >= 1 cache row, got {page_size}")
        if n_pages < 1:
            raise ServeConfigError(
                f"a zero-page pool cannot admit anything: "
                f"--n-pages {n_pages} must be >= 1")
        max_len = prompt_len + gen
        if max_len % page_size != 0:
            raise ServeConfigError(
                f"--page-size {page_size} must divide prompt_len + gen = "
                f"{max_len}: gathered caches must reproduce the contiguous "
                f"max_len layout exactly (stochastic rounding bits are "
                f"position-dependent); pick a page size dividing {max_len}")
        cfg = get_smoke_config(arch) if smoke else get_config(arch)
        if cfg.local_window and cfg.local_window % page_size != 0:
            raise ServeConfigError(
                f"--page-size {page_size} must divide {arch}'s attention "
                f"window {cfg.local_window} so a window never straddles a "
                f"part-page")
        spec = get_cache_page_spec(cfg)
        need = (-(-prompt_len // page_size)
                if any(s.seq_axis is not None for s in spec.values()) else 0)
        need += 1 if any(s.seq_axis is None for s in spec.values()) else 0
        if n_pages < need:
            raise ServeConfigError(
                f"--n-pages {n_pages} cannot hold even one "
                f"{prompt_len}-token prompt at --page-size {page_size} "
                f"({need} pages needed)")
    if speculate < 0:
        raise ServeConfigError(
            f"--speculate is a draft depth (tokens proposed per round), "
            f"must be >= 0, got {speculate}")
    if speculate > 0:
        if not engine:
            raise ServeConfigError(
                "--speculate runs inside the continuous-batching engine's "
                "decode loop; add --engine")
        cfg = get_smoke_config(arch) if smoke else get_config(arch)
        ok, why = get_draft_support(cfg)
        if not ok:
            raise ServeConfigError(
                f"--speculate is unsupported for {arch} "
                f"(family {cfg.family!r}): {why}")
        if draft_layers and not 1 <= draft_layers <= cfg.n_layers:
            raise ServeConfigError(
                f"--draft-layers must be in [1, {cfg.n_layers}] for {arch} "
                f"({cfg.n_layers} layers), got {draft_layers}")


def serve_engine(arch: str, *, smoke: bool = True, batch: int = 4,
                 prompt_len: int = 32, gen: int = 16,
                 policy_name: str = "int8", seed: int = 0, page_size: int = 16,
                 n_pages: int = 64, max_batch: int = 4, speculate: int = 0,
                 draft_layers: int = 0, guard: bool = False,
                 quiet: bool = False):
    """Route a smoke request set — ``batch`` concurrent streams with the
    same prompt randomness ``serve`` would draw — through the
    continuous-batching engine (launch/engine.py) and report the
    simulated-step serving metrics next to the analytic engine traffic
    row.  Streams get staggered arrivals and per-stream key chains, so
    this exercises admission, iteration-level batching and the pool.
    ``speculate`` > 0 arms truncated-draft speculative decoding
    (``draft_layers`` defaults to all-but-one layer); tokens are bitwise
    identical either way — speculation moves steps, never results.
    ``guard`` attaches an :class:`~repro.launch.engine_guard.EngineGuard`
    (docs/ROBUSTNESS.md §Serving resilience): pool page checksums, stall
    watchdogs, and the serving degradation ladder — also bitwise, the
    guard moves scheduling and cost, never numerics."""
    from .engine import Engine, EngineConfig, Request
    from .engine_guard import EngineGuard
    validate_request(arch, policy_name, batch=batch, prompt_len=prompt_len,
                     gen=gen, qcache=True, engine=True, page_size=page_size,
                     n_pages=n_pages, speculate=speculate,
                     draft_layers=draft_layers, smoke=smoke)
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    policy = dataclasses.replace(POLICIES[policy_name], qweights=True,
                                 qcache=True)
    if speculate > 0 and draft_layers == 0:
        draft_layers = max(1, cfg.n_layers - 1)
    key = jax.random.key(seed)
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 1), (batch, prompt_len), 0, cfg.vocab),
        np.int32)
    max_len = prompt_len + gen
    eng = Engine(cfg, policy, EngineConfig(
        max_len=max_len, page_size=page_size, n_pages=n_pages,
        max_batch=max_batch, seed=seed, speculate=speculate,
        draft_layers=draft_layers), src_len=prompt_len,
        guard=EngineGuard() if guard else None)
    reqs = [Request(rid=i, prompt=prompts[i], gen=gen, arrival_step=i,
                    seed=seed + i) for i in range(batch)]
    results = eng.run(reqs)
    stats = eng.stats()
    stats["cache_traffic"] = cache_traffic_report(
        cfg, policy, batch, prompt_len, max_len, page_size=page_size)
    if speculate > 0 and cfg.family in ("dense", "vlm"):
        stats["spec_traffic"] = speculative_traffic_report(
            cfg, policy, speculate, draft_layers, max_len)
    if not quiet:
        print(f"arch={cfg.name} engine: {batch} streams, max_batch="
              f"{max_batch}, pool {n_pages} pages x {page_size} rows")
        print(f"{stats['tokens']} tokens in {stats['steps']} steps "
              f"({stats['tokens_per_step']:.2f} tokens/step), TTFT p50 "
              f"{stats['ttft_p50_steps']:.0f} / p99 "
              f"{stats['ttft_p99_steps']:.0f} steps, "
              f"{stats['n_preemptions']} preemptions")
        if speculate > 0:
            print(f"speculative: k={speculate} draft_layers={draft_layers}"
                  f"/{cfg.n_layers}, {stats['spec_rounds']} rounds, "
                  f"acceptance length "
                  f"{stats['accepted_tokens_per_step']:.2f} tokens/round "
                  f"({stats['accepted_drafts_per_round']:.2f} drafts), "
                  f"{stats['spec_rejections']} rejections")
            st = stats.get("spec_traffic")
            if st:
                d = st["decision"]
                print(f"speculative round traffic: "
                      f"{st['round_bytes'] / 1e6:.3f} MB vs sequential "
                      f"{st['sequential_block_bytes'] / 1e6:.3f} MB for "
                      f"k+1 tokens (break-even {st['breakeven_accepted']} "
                      f"accepted; -{st['reduction_at_full_accept_pct']}% "
                      f"at full accept)  [{d['op']}/{d['kind']} -> "
                      f"{d['path']} bq={d['bq']} bt={d['bt']}]")
        pool = stats["pool"]
        print(f"pool: peak {pool['peak_live']}/{pool['n_pages']} pages, "
              f"allocs {pool['page_allocs']} = frees {pool['page_frees']} "
              f"+ live {pool['live_pages']} (balanced={pool['balanced']})")
        if guard:
            g = stats["guard"]
            print(f"guard: {g['events']} events {g['event_counts']}, "
                  f"{stats['n_retries']} lane retries, "
                  f"{stats['n_shed']} streams shed, eff_max_batch "
                  f"{g['eff_max_batch']}")
        eng_row = stats["cache_traffic"]["engine"]
        print(f"engine cache traffic/lane: contiguous "
              f"{eng_row['contiguous_bytes_per_lane'] / 1e6:.3f} MB -> "
              f"paged {eng_row['cache_bytes_per_lane'] / 1e6:.3f} MB "
              f"(page-table overhead "
              f"+{eng_row['page_table_overhead_pct']}%)")
    toks = np.stack([results[i] for i in range(batch)])
    return toks, stats


def serve(arch: str, *, smoke: bool = True, batch: int = 4, prompt_len: int = 32,
          gen: int = 16, policy_name: str = "int8", seed: int = 0,
          qweights: bool = True, qcache: bool = False, health: bool = False,
          quiet: bool = False):
    validate_request(arch, policy_name, batch=batch, prompt_len=prompt_len,
                     gen=gen, qcache=qcache, health=health)
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    policy = POLICIES[policy_name]
    if qweights and policy.enabled:
        policy = dataclasses.replace(policy, qweights=True)
    if qcache and policy.enabled:
        # quantized caches: prefill writes int8 rows once, decode appends
        # one quantized row per step and attention consumes the mantissas.
        policy = dataclasses.replace(policy, qcache=True)
    mod = get_model(cfg)
    key = jax.random.key(seed)
    params = mod.init_params(key, cfg)
    if policy.qweights_on:
        # quantize-once serving: after this line no float32 weight exists
        # on the prefill/decode path (weight_mask-declared leaves).
        params = quantize_serving_params(params, cfg, policy,
                                         jax.random.fold_in(key, 0x9E))
    max_len = prompt_len + gen

    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab)
    pf_batch = {"tokens": prompts}
    if cfg.family == "audio":
        pf_batch["src_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (batch, prompt_len, cfg.d_model)) * 0.02
    if cfg.family == "vlm":
        pf_batch["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (batch, cfg.patch_positions, cfg.d_model)) * 0.02

    prefill_fn = jax.jit(make_prefill_step(cfg, policy, max_len))
    decode_fn = jax.jit(make_decode_step(cfg, policy))

    t0 = time.time()
    cache, logits = prefill_fn(params, pf_batch, jax.random.fold_in(key, 3))
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(gen - 1):
        logits, cache = decode_fn(params, cache, tok, jnp.int32(prompt_len + i),
                                  jax.random.fold_in(key, 10 + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    tok.block_until_ready()
    t_decode = time.time() - t0

    toks_per_s = batch * (gen - 1) / max(t_decode, 1e-9)
    stats = {"prefill_s": t_prefill, "decode_s": t_decode,
             "tok_per_s": toks_per_s, "qweights": policy.qweights_on,
             "qcache": policy.qcache_on}
    # the analytic comparison only describes integer-pipeline runs and the
    # dense-FFN GEMM set (vlm's patch frontend is an external stub; MoE
    # expert GEMMs have a different shape set)
    if policy.enabled and cfg.family in ("dense", "vlm"):
        stats["weight_traffic"] = weight_traffic_report(cfg, batch, prompt_len)
    if policy.enabled:
        stats["cache_traffic"] = cache_traffic_report(cfg, policy, batch,
                                                      prompt_len, max_len)
    if policy.enabled and cfg.family in ("dense", "vlm", "moe"):
        stats["attn_traffic"] = attention_traffic_report(
            cfg, policy, batch, prompt_len, max_len)
    if policy.enabled and cfg.family in ("dense", "vlm"):
        stats["chain_traffic"] = chain_traffic_report(cfg, policy, batch,
                                                      prompt_len, max_len)
    if health:
        # per-leaf saturation/exponent stats of every quantized artifact
        # actually serving: the load-time weights and the decode-time cache
        stats["health"] = {}
        if policy.qweights_on:
            stats["health"]["weights"] = bfp_tree_stats(params)
        if policy.qcache_on:
            stats["health"]["qcache"] = bfp_tree_stats(cache)
    if not quiet:
        print(f"arch={cfg.name} policy={policy_name} batch={batch} "
              f"qweights={policy.qweights_on} qcache={policy.qcache_on}")
        print(f"prefill: {prompt_len} toks x {batch} in {t_prefill:.3f}s")
        print(f"decode: {gen - 1} steps in {t_decode:.3f}s  "
              f"({toks_per_s:.1f} tok/s, {t_decode / max(gen - 1, 1) * 1e3:.1f} ms/step)")
        wt = stats.get("weight_traffic")
        if wt:
            for phase, r in wt.items():
                what = ("weight-operand traffic per model pass"
                        if phase == "weight_side"
                        else f"{phase} GEMM traffic (whole)")
                print(f"{what}: per-call weight quant "
                      f"{r['per_call_weight_quant_bytes'] / 1e6:.2f} MB -> "
                      f"load-time quantized "
                      f"{r['load_time_quantized_bytes'] / 1e6:.2f} MB "
                      f"(-{r['reduction_pct']}%)")
        ct = stats.get("cache_traffic")
        if ct:
            for phase, r in ct.items():
                what = ("cache-operand traffic per decode step"
                        if phase == "cache_side"
                        else "decode attention GEMM traffic (whole)")
                print(f"{what}: float cache "
                      f"{r['float_cache_bytes'] / 1e6:.2f} MB -> qcache "
                      f"{r['qcache_bytes'] / 1e6:.2f} MB "
                      f"(-{r['reduction_pct']}%)")
        at = stats.get("attn_traffic")
        if at:
            for phase, r in at.items():
                d = r["decision"]
                print(f"attention {phase} traffic: scan "
                      f"{r['scan_bytes'] / 1e6:.2f} MB -> fused "
                      f"{r['fused_bytes'] / 1e6:.2f} MB "
                      f"(-{r['reduction_pct']}%)  "
                      f"[{d['op']}/{d['kind']} -> {d['path']} "
                      f"bq={d['bq']} bt={d['bt']}]")
        cht = stats.get("chain_traffic")
        if cht:
            for op, r in cht.items():
                per = ("per decode step" if op == "decode_block"
                       else "per prefill call")
                print(f"fused-chain {op} traffic {per}: unfused "
                      f"{r['unfused_bytes'] / 1e6:.2f} MB -> fused "
                      f"{r['fused_bytes'] / 1e6:.2f} MB "
                      f"(-{r['reduction_pct']}%)")
        for section, leaves in stats.get("health", {}).items():
            if not leaves:
                print(f"health {section}: no quantized leaves")
                continue
            worst = max(leaves, key=lambda k: leaves[k]["sat_rate"])
            mean_sat = sum(v["sat_rate"] for v in leaves.values()) / len(leaves)
            exp_lo = min(v["exp_min"] for v in leaves.values())
            exp_hi = max(v["exp_max"] for v in leaves.values())
            print(f"health {section}: {len(leaves)} quantized leaves, "
                  f"mean sat {mean_sat:.4f}, exp range [{exp_lo}, {exp_hi}], "
                  f"worst {worst} sat {leaves[worst]['sat_rate']:.4f}")
    return np.stack(out_tokens, axis=1), stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2_0_5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--policy", default="int8", choices=list(POLICIES))
    ap.add_argument("--per-call-weights", dest="qweights",
                    action="store_false", default=True,
                    help="legacy path: re-quantize f32 weights inside every "
                         "GEMM instead of once at model load")
    ap.add_argument("--qcache", action="store_true", default=False,
                    help="quantized decode caches: int8 KV/state rows "
                         "written once at append time, consumed directly "
                         "by decode attention (docs/SERVING.md)")
    ap.add_argument("--health", action="store_true", default=False,
                    help="print per-artifact saturation/exponent stats of "
                         "the quantized serving weights and qcache "
                         "(docs/ROBUSTNESS.md); needs --policy int8")
    ap.add_argument("--engine", action="store_true", default=False,
                    help="route the request set through the "
                         "continuous-batching engine over the block-paged "
                         "qcache pool (docs/SERVING.md §Engine): --batch "
                         "becomes N concurrent streams with staggered "
                         "arrivals; implies --qcache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache rows per pool page (--engine); must divide "
                         "prompt_len + gen and any attention window")
    ap.add_argument("--n-pages", type=int, default=64,
                    help="physical pages in the qcache pool (--engine)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode lanes per engine iteration (--engine)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="draft tokens per speculative round (--engine); "
                         "0 disables; output stays bitwise identical")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="layers in the truncated self-draft (--speculate); "
                         "0 means all but the last layer")
    ap.add_argument("--guard", action="store_true", default=False,
                    help="attach the serving guard (--engine): pool page "
                         "checksums, deadline watchdogs, lane recovery, "
                         "and the degradation ladder "
                         "(docs/ROBUSTNESS.md §Serving resilience); "
                         "output stays bitwise identical")
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        if (args.speculate or args.draft_layers) and not args.engine:
            raise ServeConfigError(
                "--speculate runs inside the continuous-batching engine's "
                "decode loop; add --engine")
        if args.guard and not args.engine:
            raise ServeConfigError(
                "--guard watches the continuous-batching engine; "
                "add --engine")
        if args.engine:
            serve_engine(args.arch, smoke=args.smoke, batch=args.batch,
                         prompt_len=args.prompt_len, gen=args.gen,
                         policy_name=args.policy, page_size=args.page_size,
                         n_pages=args.n_pages, max_batch=args.max_batch,
                         speculate=args.speculate,
                         draft_layers=args.draft_layers, guard=args.guard)
        else:
            serve(args.arch, smoke=args.smoke, batch=args.batch,
                  prompt_len=args.prompt_len, gen=args.gen,
                  policy_name=args.policy, qweights=args.qweights,
                  qcache=args.qcache, health=args.health)
    except ServeConfigError as err:
        ap.exit(2, f"error: {err}\n")


if __name__ == "__main__":
    main()
