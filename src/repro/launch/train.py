"""End-to-end training driver: data -> integer train step -> checkpoints.

Runs the paper's full integer pipeline (int8 fwd/bwd, int16 SGD) or the
float baseline on any zoo arch (full or smoke config), on whatever mesh
the local devices allow, with checkpoint/resume and per-step telemetry
feeding the straggler monitor.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
        --steps 50 --batch 8 --seq 64 --policy int8 --ckpt-dir /tmp/ckpt

``--mesh DATA,MODEL`` trains over a (data, model) mesh of that many local
devices (default 1,1): the state is placed with the production sharding
rules, the batch split over ``data``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..core import integer_sgd_init
from ..core.policy import FLOAT32, PAPER_INT8, NumericPolicy
from ..data import SyntheticLM
from ..introspect import health_summary
from ..models import get_model
from ..optim import sgd_init, wsd_schedule
from ..runtime import fault_injection as finj
from ..runtime.fault_tolerance import StragglerMonitor
from ..runtime.sharding import DEFAULT_RULES, use_rules
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh, parse_mesh_shape
from .steps import (TrainHyper, make_float_train_step, make_train_step,
                    params_shardings, state_shardings)
from .supervisor import GuardConfig, TrainSupervisor

POLICIES = {"int8": PAPER_INT8, "float32": FLOAT32,
            "int8_block": NumericPolicy(block=128),
            "int8_qflow": NumericPolicy(qflow=True),
            "int8_qweights": NumericPolicy(qweights=True),
            "int8_qfull": NumericPolicy(qflow=True, qweights=True),
            "int4": NumericPolicy(fwd_bits=4, bwd_bits=4)}


def _apply_state_faults(fault_plan, state, step: int, quiet: bool,
                        done: set):
    """Chaos-harness injection point: corrupt the *committed* state after
    ``step`` (the supervisor's snapshot/checkpoint of this step is clean,
    so a rollback restores an uncorrupted state and the retry replays the
    same data bit-identically — docs/ROBUSTNESS.md §Chaos harness).  Each
    fault fires exactly once (``done`` ledger): it models a transient
    upset, so a post-rollback replay of the same step stays clean."""
    if (fault_plan.nan_step is not None and step == fault_plan.nan_step
            and "nan" not in done):
        done.add("nan")
        if not quiet:
            print(f"[chaos] step {step}: corrupting master exponent")
        state = state._replace(masters=finj.corrupt_master_exponent(
            state.masters, fault_plan.nan_leaf))
    if (fault_plan.flip_step is not None and step == fault_plan.flip_step
            and "flip" not in done):
        done.add("flip")
        if not quiet:
            print(f"[chaos] step {step}: flipping master mantissa bits")
        state = state._replace(masters=finj.flip_mantissa_bits(
            state.masters, fault_plan.flip_seed))
    return state


def train(arch: str, *, smoke: bool = True, steps: int = 50, batch: int = 8,
          seq: int = 64, policy_name: str = "int8", lr: float = 0.05,
          microbatch: int = 1, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 25, log_every: int = 10, seed: int = 0,
          momentum: float = 0.9, weight_decay: float = 0.0,
          use_wsd: bool = False, quiet: bool = False, qflow: bool = False,
          qweights: bool = False, health: bool = False,
          guard: Optional[GuardConfig] = None, fault_plan=None,
          sim_hosts: int = 1, supervisor: Optional[TrainSupervisor] = None,
          mesh_shape: Tuple[int, int] = (1, 1)):
    """Train loop.  ``health=True`` computes the per-step numeric-health
    report and runs it through a :class:`TrainSupervisor` — tripped guards
    roll the run back to the last committed state with bounded retries
    (docs/ROBUSTNESS.md).  ``fault_plan`` (a ``runtime.fault_injection.
    FaultPlan``) is the chaos harness's injection schedule: state
    corruption after a chosen committed step and/or a simulated dead host
    driving the Heartbeat -> re-mesh -> restore path.  Returns
    ``(losses, state)``; with a supervisor attached, its ``events`` list
    is the recovery telemetry.  ``mesh_shape`` = (data, model) devices;
    each step's wall time (first one includes compilation) is kept in
    ``train.last_step_seconds``."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    policy = POLICIES[policy_name]
    if qflow and policy.enabled:
        policy = dataclasses.replace(policy, qflow=True)
    if qweights and policy.enabled:
        policy = dataclasses.replace(policy, qweights=True)
    use_health = (health or fault_plan is not None) and policy.enabled
    if use_health:
        policy = dataclasses.replace(policy, health=True)
    mod = get_model(cfg)
    key = jax.random.key(seed)

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed)
    schedule = (lambda s: wsd_schedule(s, lr, steps // 10, steps // 2,
                                       steps // 3)) if use_wsd else None
    hyper = TrainHyper(lr=lr, momentum=momentum, weight_decay=weight_decay,
                       microbatch=microbatch, schedule=schedule)

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0

    # Supervisor + (simulated) fleet.  A fault plan swaps the wall clock
    # for the injectable SimClock and stands up a scripted HostSim fleet,
    # so host death and straggling are deterministic and unit-testable on
    # one real process.
    sup, host_sim, monitor = supervisor, None, None
    if use_health and sup is None:
        hosts = list(range(max(1, sim_hosts)))
        if fault_plan is not None and len(hosts) > 1:
            clock = finj.SimClock()
            host_sim = finj.HostSim(hosts, clock)
            sup = TrainSupervisor(mgr, guard or GuardConfig(), hosts=hosts,
                                  clock=clock, heartbeat_timeout_s=2.5,
                                  quiet=quiet)
        else:
            sup = TrainSupervisor(mgr, guard or GuardConfig(), hosts=hosts,
                                  quiet=quiet)
    if sup is None:
        monitor = StragglerMonitor([0])

    if policy.enabled:
        state = integer_sgd_init(mod.init_params(key, cfg), policy, key=key)
        step_fn = jax.jit(make_train_step(cfg, policy, hyper))
    else:
        params = mod.init_params(key, cfg)
        state = (params, sgd_init(params))
        raw = make_float_train_step(cfg, hyper)
        step_fn = jax.jit(lambda s, b, k: raw(s, b, k))

    if mgr and mgr.latest_step() is not None:
        start_step, state = mgr.restore_latest(state)
        if not quiet:
            print(f"resumed from step {start_step}")

    # a concrete (possibly 1x1) mesh: logical_constraint needs one to turn
    # PartitionSpecs into NamedShardings (bare specs require a mesh context
    # manager, which jitted step functions don't have).  State and batch
    # are placed on it even at 1x1: the step's outputs carry the mesh in
    # their types, and inputs without it would compile the step twice.
    mesh = make_local_mesh(*mesh_shape)
    if policy.enabled:
        state = jax.device_put(
            state, state_shardings(cfg, policy, mesh, DEFAULT_RULES))
    else:
        psh = params_shardings(cfg, mesh, DEFAULT_RULES)
        state = jax.device_put(state, (psh, type(state[1])(
            psh, NamedSharding(mesh, P()))))
    batch_sh = NamedSharding(mesh, DEFAULT_RULES.spec(("batch",)))

    losses = []
    step_seconds = []
    faults_done: set = set()
    with use_rules(DEFAULT_RULES, mesh):
        step = start_step
        while step < steps:
            t0 = time.time()
            hb = ds.batch_for_step(step)
            batch_j = jax.device_put(
                {k: jnp.asarray(v) for k, v in hb.items()}, batch_sh)
            if cfg.family == "vlm":
                batch_j["patch_embeds"] = jax.random.normal(
                    jax.random.fold_in(key, step),
                    (batch, cfg.patch_positions, cfg.d_model)) * 0.02
            if cfg.family == "audio":
                batch_j["src_embeds"] = jax.random.normal(
                    jax.random.fold_in(key, step), (batch, seq, cfg.d_model)) * 0.02
            out = step_fn(state, batch_j, jax.random.fold_in(key, step))
            if use_health:
                new_state, loss = out[0], out[1]
                summary = health_summary(jax.device_get(out[2]))
            else:
                new_state, loss = out
                summary = None
            dt = time.time() - t0

            # liveness + step timing at the boundary (real or simulated)
            if sup is not None:
                if host_sim is not None:
                    if (fault_plan is not None
                            and fault_plan.kill_host_step is not None
                            and step >= fault_plan.kill_host_step):
                        host_sim.kill(fault_plan.kill_host)
                    host_sim.tick(sup.heartbeat, sup.monitor)
                else:
                    sup.heartbeat.beat(0)
                    sup.monitor.record(0, dt)
            else:
                monitor.record(0, dt)

            # guard check: a tripped step is discarded, never committed
            if sup is not None and summary is not None:
                trips = sup.check(step, summary)
                if trips:
                    step, state, offset = sup.rollback(step, state, trips,
                                                       summary)
                    if offset:
                        ds = dataclasses.replace(ds, seed=seed + offset)
                    del losses[max(step - start_step, 0):]
                    del step_seconds[max(step - start_step, 0):]
                    continue

            state = new_state
            losses.append(float(loss))
            step_seconds.append(time.time() - t0)
            if sup is not None:
                sup.commit(step, state)
            if mgr and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, state)

            # chaos injection AFTER commit: the snapshot stays clean
            if fault_plan is not None and policy.enabled:
                state = _apply_state_faults(fault_plan, state, step, quiet,
                                            faults_done)

            # dead host -> re-mesh + restore at the step boundary
            if sup is not None:
                plan = sup.poll_cluster(step)
                if plan is not None:
                    restore_step, state = sup.apply_remesh(plan, state)
                    if not quiet:
                        print(f"re-meshed to {plan.mesh_shape}, resuming "
                              f"from step {restore_step}")
                    if restore_step is not None and restore_step != step + 1:
                        del losses[max(restore_step - start_step, 0):]
                        del step_seconds[max(restore_step - start_step, 0):]
                        step = restore_step
                        continue

            if not quiet and (step % log_every == 0 or step == steps - 1):
                print(f"step {step:5d} loss {float(loss):.4f} "
                      f"({time.time() - t0:.2f}s)")
            step += 1
    if mgr:
        # settle in-flight async saves first: the loop may already have
        # written step ``steps`` ((steps-1)+1 boundary), and a second
        # concurrent save of the same step would race it on the tmp dir
        mgr.wait()
        if mgr.latest_step() != steps:
            mgr.save(steps, state)
            mgr.wait()
    if sup is not None:
        train.last_supervisor = sup
    train.last_step_seconds = step_seconds
    return losses, state


# telemetry handles of the most recent train() call: its supervisor, for
# callers that don't construct their own (tools/chaos_smoke.py), and the
# wall seconds of each committed step (chip_smoke.py).
train.last_supervisor = None
train.last_step_seconds = []


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2_0_5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--policy", default="int8", choices=list(POLICIES))
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--wsd", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=parse_mesh_shape, default=(1, 1),
                    metavar="DATA,MODEL",
                    help="train over a (data, model) mesh of local devices")
    ap.add_argument("--qflow", action="store_true",
                    help="quantized activations as the inter-layer currency "
                         "(docs/DATAFLOW.md); no-op for --policy float32")
    ap.add_argument("--qweights", action="store_true",
                    help="quantized weights as the persistent currency: "
                         "int8 forward weights derived from the int16 "
                         "masters once per step (docs/DATAFLOW.md); no-op "
                         "for --policy float32")
    ap.add_argument("--health", action="store_true",
                    help="per-step numeric-health report + supervisor: "
                         "tripped guards (NaN carrier, master headroom, "
                         "saturation spike) roll back to the last committed "
                         "checkpoint (docs/ROBUSTNESS.md); no-op for "
                         "--policy float32")
    args = ap.parse_args()
    enable_compile_cache()
    losses, _ = train(args.arch, smoke=args.smoke, steps=args.steps,
                      batch=args.batch, seq=args.seq, policy_name=args.policy,
                      lr=args.lr, microbatch=args.microbatch,
                      ckpt_dir=args.ckpt_dir, use_wsd=args.wsd, seed=args.seed,
                      qflow=args.qflow, qweights=args.qweights,
                      health=args.health, mesh_shape=args.mesh)
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
