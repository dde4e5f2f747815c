#!/usr/bin/env python3
"""Bring-up smoke run of the integer pipeline on one TPU chip.

Drives the main path once through the entry points a user calls, at the
full published width of qwen2-0.5b (24 layers, d_model 896, 14/2 heads,
d_ff 4864, vocab 151936), with random weights made from a seed:

  train   ``repro.launch.train.train`` takes a few int8 train steps (the
          default paper policy) at batch 4 x seq 512; every loss must be
          finite, and the first-batch loss must lie within 1% of the
          float32 policy's loss on the same batch and initial weights.
  serve   ``repro.launch.serve.serve_engine`` answers 4 requests through
          ``Engine`` over the qcache page pool with 4 decode lanes; each
          request's tokens must equal, bitwise, the same request served
          alone (max_batch 1): scheduling moves cost, never bits.
  routes  around the first call of each phase the kernel router's plans
          are recorded: every GEMM and attention op must plan a compiled
          Pallas kernel (FUSED or UNFUSED, not interpret mode); an op may
          plan the jnp path only for a shape the kernels do not take, and
          is then listed.  No kernel may have fallen back to jnp through
          an exception, and the serving guard stays off.

``--four-chips`` runs only the four-chip comparison: the same full-width
int8 train step on a 2x2 (data, model) mesh over four chips and on one
chip; their step-1 losses must agree to 1e-4 relative.  On the mesh the
GEMMs run the jnp path, bit-identical to the kernels: GSPMD cannot
partition a Mosaic kernel.

Every phase that fails exits non-zero.  There is no CPU fallback: without
a TPU the script exits non-zero and prints no result.  On success the
last line of stdout is one JSON object naming the device.  Numbers printed
on earlier lines are a smoke run's, not a benchmark's.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2_0_5b"
BATCH, SEQ, TRAIN_STEPS = 4, 512, 3
N_REQUESTS, PROMPT_LEN, GEN = 4, 32, 16
LOSS_BAND = 0.01          # int8 vs float32 first-batch loss, relative
MESH_TOL = 1e-4           # 2x2 mesh vs one chip step-1 loss, relative
# jnp plans that are a property of the shape, not of the backend or of
# the VMEM residency model (a block that does not fit plans UNFUSED)
_SHAPE_REASONS = ("accum_chunk", "overflows the int32 accumulator")


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
        super().__init__(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def check_routes(phase: str, decisions, sharded: bool = False) -> None:
    """``sharded``: the step runs over a multi-device mesh, where compiled
    kernels plan the jnp path (GSPMD cannot partition a Mosaic kernel)."""
    from repro.kernels import dispatch
    check(len(decisions) > 0, f"{phase}: no kernel routing was planned")
    allowed = _SHAPE_REASONS + ((dispatch.UNPARTITIONED,) if sharded else ())
    kernels = 0
    for d in decisions:
        what = f"{d.op}/{d.kind} {d.m}x{d.k}x{d.n}"
        if d.path in (dispatch.FUSED, dispatch.UNFUSED):
            check(not d.interpret, f"{phase}: {what} plans interpret mode")
            kernels += 1
        else:
            check(any(r in d.reason for r in allowed),
                  f"{phase}: {what} plans jnp: {d.reason}")
            if d.reason != dispatch.UNPARTITIONED:
                log(f"[{phase}] jnp by shape: {what}: {d.reason}")
    counts = {}
    for d in decisions:
        counts[(d.op, d.path)] = counts.get((d.op, d.path), 0) + 1
    log(f"[{phase}] routes: " + ", ".join(
        f"{op}:{path}x{n}" for (op, path), n in sorted(counts.items())))
    check(kernels > 0 or sharded, f"{phase}: no op planned a Pallas kernel")
    check(not dispatch.fallback_counts(),
          f"{phase}: kernel fallbacks {dispatch.fallback_counts()}")


def train_phase(steps: int, policy: str, mesh_shape=(1, 1)):
    """Returns (losses, step seconds, decisions) of one train() call."""
    import jax
    from repro.kernels import dispatch
    from repro.launch.train import train
    with dispatch.record_decisions() as decisions:
        losses, state = train(ARCH, smoke=False, steps=steps, batch=BATCH,
                              seq=SEQ, policy_name=policy, quiet=True,
                              mesh_shape=mesh_shape)
    jax.block_until_ready(state)
    del state
    return losses, list(train.last_step_seconds), decisions


def report_steps(tag: str, losses, seconds) -> None:
    for i, (loss, s) in enumerate(zip(losses, seconds)):
        log(f"[{tag}] step {i} loss {loss!r} wall_s {s!r}")
    if len(seconds) > 1:
        steady = statistics.median(seconds[1:])
        log(f"[{tag}] first step (compile + run) {seconds[0]!r} s, steady "
            f"step {steady!r} s, compile ~{seconds[0] - steady!r} s")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip(dev) -> None:
    import numpy as np
    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.launch.serve import serve_engine

    # -- train: int8 steps, then the float32 reference on the same batch --
    t0 = time.time()
    losses, seconds, decisions = train_phase(TRAIN_STEPS, "int8")
    report_steps("train int8", losses, seconds)
    check(all(np.isfinite(losses)), f"non-finite int8 loss: {losses}")
    check_routes("train", decisions)
    log(f"[train int8] peak_bytes_in_use {peak_bytes(dev)}")
    f32, f32_seconds, _ = train_phase(1, "float32")
    report_steps("train float32", f32, f32_seconds)
    gap = abs(losses[0] - f32[0]) / abs(f32[0])
    log(f"[train] first-batch loss int8 {losses[0]!r} float32 {f32[0]!r} "
        f"relative gap {gap!r}")
    check(gap <= LOSS_BAND, f"int8 loss off float32 by {gap} > {LOSS_BAND}")
    log(f"[train] phase wall_s {time.time() - t0!r}")

    # -- serve: batched engine vs each request served alone --------------
    t0 = time.time()
    kw = dict(smoke=False, batch=N_REQUESTS, prompt_len=PROMPT_LEN, gen=GEN,
              quiet=True)
    with dispatch.record_decisions() as decisions:
        toks, stats = serve_engine(ARCH, max_batch=N_REQUESTS, **kw)
    check_routes("serve", decisions)
    log(f"[serve] batched: {stats['tokens']} tokens in {stats['steps']} "
        f"scheduler steps, wall_s {time.time() - t0!r}")
    t1 = time.time()
    alone, _ = serve_engine(ARCH, max_batch=1, **kw)
    log(f"[serve] one at a time: wall_s {time.time() - t1!r}")
    vocab = get_config(ARCH).vocab
    check(toks.shape == (N_REQUESTS, GEN), f"token shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < vocab)).all()), "token id out of range")
    check(stats["pool"]["balanced"], f"page pool unbalanced: {stats['pool']}")
    for i in range(N_REQUESTS):
        log(f"[serve] request {i}: {toks[i].tolist()}")
        check(np.array_equal(toks[i], alone[i]),
              f"request {i}: batched tokens differ from served alone: "
              f"{toks[i].tolist()} vs {alone[i].tolist()}")
    check(not dispatch.fallback_counts(),
          f"kernel fallbacks {dispatch.fallback_counts()}")
    log(f"[serve] peak_bytes_in_use {peak_bytes(dev)}")


def four_chips(devices) -> None:
    import numpy as np
    check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
                             f"{len(devices)}")
    mesh_l, mesh_s, decisions = train_phase(1, "int8", mesh_shape=(2, 2))
    report_steps("train int8 2x2", mesh_l, mesh_s)
    check_routes("train 2x2", decisions, sharded=True)
    one_l, one_s, _ = train_phase(1, "int8")
    report_steps("train int8 1x1", one_l, one_s)
    check(bool(np.isfinite(mesh_l + one_l).all()), "non-finite loss")
    gap = abs(mesh_l[0] - one_l[0]) / abs(one_l[0])
    log(f"[train] step-1 loss 2x2 {mesh_l[0]!r} one chip {one_l[0]!r} "
        f"relative gap {gap!r}")
    check(gap <= MESH_TOL, f"2x2 mesh loss off one chip by {gap} > "
                           f"{MESH_TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2-mesh vs one-chip train step")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repository's src/repro is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # routing comes from committed code alone: never read an autotune
    # cache from outside the checkout, never measure.
    os.environ.pop("REPRO_KERNEL_AUTOTUNE", None)
    at_cache = os.path.join(ROOT, ".autotune", "chip_smoke.json")
    if os.path.exists(at_cache):
        os.remove(at_cache)
    os.environ["REPRO_KERNEL_AUTOTUNE_CACHE"] = at_cache

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {dev.platform}); there is no "
              "CPU fallback", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}, compile "
        f"cache {cache} ({warm} entries at start)")

    t0 = time.time()
    if args.four_chips:
        four_chips(devices)
    else:
        one_chip(dev)
    log(f"total wall_s {time.time() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
