#!/usr/bin/env python3
"""Readings that set a cell's limits and rates, on the chip, in one process.

    python3 bench/study.py train --config <config> --traffic <mix> \
        --seeds 1,2,... [--kernel-mode jnp] [--witness] [--policy int4]
    python3 bench/study.py noise --config <config> --traffic <mix> \
        --seeds 1 --keys 4 [--layers 1]
    python3 bench/study.py serve --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds 15
    python3 bench/study.py sweep --workload <cell> --rates 1,2,4 \
        --seconds 30 --seed 5 [--max-batch 32]

``train``: the compared numbers of the program as the cell runs it, one
line per seed, each leaf's norms with them; ``--kernel-mode jnp`` runs
every GEMM on the router's jnp path, ``--witness`` adds the norms of the
program's own float32 gradient, ``--policy int4`` puts the control (the
program's int4 path) in the int8 step's place.

``serve``: ``token_gap`` of the program on each seed, each seed's window
at the cell's own rate; and of the control: at each position of the same
prompts and served tokens, the gap of the token that the program's int4
path, teacher-forced, puts first.

``noise``: the program's int8 gradient against the reference's, leaf by
leaf: one key's, and the mean over several keys.

``sweep``: the cell's traffic at each rate in turn (the compiled
programs shared): requests offered and finished, the tails, tokens per
second, the backlog when the window closed and the device's peak memory;
``--max-batch`` serves with that many lanes, the page pool sized to
hold them all.  The benchmark's own runs
run none of this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# the precision below the int8 that the configurations state
CONTROL_POLICY = "int4"


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _float32_grad_norms(cell, run):
    """Per-leaf norms of the program's float32 gradient at the start
    weights on batch 0: a witness beside the reference."""
    import jax
    import jax.numpy as jnp
    from repro.core.policy import FLOAT32
    from repro.models import transformer
    conf, arch, cfg = cell.config, run.p.arch, run.p.cfg
    b = {k: jnp.asarray(v) for k, v in run.ds.batch_for_step(0).items()}
    grads = jax.jit(jax.grad(lambda p: transformer.loss_fn(
        p, b, run.key, FLOAT32, cfg)))(
        jax.jit(lambda k: arch.init_weights(k, conf))(run.key))
    return [float(x) for x in arch.leaf_norms(grads)]


def study_noise(cell, args) -> None:
    """The program's int8 gradient beside the reference's at the cell's
    batch: per leaf, the norm ratio and cosine of one key's gradient, and
    the norm ratio and cosine of the mean over ``--keys`` keys (unbiased
    rounding noise shrinks in the mean; a bias does not).  With
    ``--layers`` the configuration is cut to that depth."""
    import jax
    import jax.numpy as jnp
    from repro.launch.train import POLICIES
    from repro.models import transformer
    from bench import model, train_cell
    from bench.traffic import SyntheticLM
    conf = dict(cell.config)
    if args.layers:
        conf["num_hidden_layers"] = args.layers
    arch = train_cell.training_arch(conf, cell.root)
    cfg = arch.arch_config(conf)
    policy = POLICIES[cell.traffic["policy"]]
    tr = cell.traffic
    for seed in args.seeds:
        key = model.seed_key(seed)
        b = {k: jnp.asarray(v) for k, v in SyntheticLM(
            cfg.vocab, tr["seq"], tr["batch"], seed=seed).batch_for_step(0)
            .items()}
        params = jax.jit(lambda k: arch.init_weights(k, conf))(key)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(jax.grad(lambda p: arch.loss(p, b, conf)))(params)
        grad = jax.jit(jax.grad(lambda p, k: transformer.loss_fn(
            p, b, k, policy, cfg)))
        one = grad(params, jax.random.fold_in(key, 1))
        total = one
        for i in range(1, args.keys):
            total = jax.tree_util.tree_map(
                jnp.add, total, grad(params, jax.random.fold_in(key, 1 + i)))
        rows = {}
        for name, r, g1, gs in zip(model.leaf_names(ref),
                                   jax.tree_util.tree_leaves(ref),
                                   jax.tree_util.tree_leaves(one),
                                   jax.tree_util.tree_leaves(total)):
            gm = gs / args.keys
            nr = jnp.linalg.norm(r)
            rows[name] = [float(jnp.linalg.norm(g1) / nr),
                          float(jnp.vdot(g1, r) / jnp.linalg.norm(g1) / nr),
                          float(jnp.linalg.norm(gm) / nr),
                          float(jnp.vdot(gm, r) / jnp.linalg.norm(gm) / nr)]
        _emit(kind="noise", seed=seed, layers=conf["num_hidden_layers"],
              keys=args.keys, leaves=rows)
        del ref, one, total, params
        gc.collect()


def study_train(cell, args) -> None:
    from bench import train_cell
    progs = train_cell.Programs(cell, policy_name=args.policy,
                                kernel_mode=args.kernel_mode)
    for seed in args.seeds:
        run = train_cell.TrainRun(cell, seed, progs)
        t0 = time.perf_counter()
        run.setup()
        run.free()
        ref = run.reference()
        nums = train_cell.compare(run.readings(), ref, progs.names)
        _emit(kind=args.policy or "program", seed=seed, losses=run.losses,
              ref_losses=ref["losses"], wall_s=time.perf_counter() - t0,
              **nums)
        if args.witness:
            _emit(kind="float32_program_grad", seed=seed,
                  norms=dict(zip(progs.names,
                                 _float32_grad_norms(cell, run))))
        gc.collect()


def _control_pick(run, policy_name: str):
    """The token the program's lower-precision path puts first at each
    position of a prompt and its served tokens (teacher-forced)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.train import POLICIES
    from repro.models import transformer
    pol = dataclasses.replace(POLICIES[policy_name], qweights=True,
                              qcache=True)
    cfg = run.cfg
    params = run.arch.serving_loader(run.conf, cfg, pol)(run.key)
    length = int(run.mix["max_len"])

    @jax.jit
    def logits(p, toks, key):
        h, _, _ = transformer.forward_hidden(p, toks, key, pol, cfg)
        return transformer._lm_logits(p, h, jax.random.fold_in(key, 0xF2),
                                      pol, cfg)[0]

    def pick(prompt, toks):
        seq = np.zeros(length, np.int32)
        seq[:len(prompt) + len(toks) - 1] = np.concatenate(
            [prompt, toks[:-1]])
        lg = np.asarray(logits(params, jnp.asarray(seq)[None],
                               jax.random.fold_in(run.key, 3)))
        rows = lg[len(prompt) - 1:len(prompt) - 1 + len(toks)]
        return rows.argmax(axis=-1)

    return pick


def study_serve(cell, args) -> None:
    from bench import serve_cell
    prev = None
    for seed in args.seeds:
        run = serve_cell.ServeRun(cell, seed, share=prev)
        run.setup()
        w = run.window(args.seconds)
        run.free()
        nums = run.check()
        _emit(kind="program", seed=seed, window={
            k: w[k] for k in ("attempted", "finished", "tokens",
                              "ttft_p90_ms", "itl_p95_ms")}, **nums)
        if seed in args.control_seeds:
            pick = _control_pick(run, CONTROL_POLICY)
            gap, n = serve_cell.token_gaps(run.arch, run.conf, run.key,
                                           run.sequences(),
                                           int(run.mix["max_len"]), pick)
            _emit(kind="control", seed=seed, token_gap=gap, tokens=n)
        prev = run
        gc.collect()


def study_sweep(cell, args) -> None:
    import jax
    from bench import serve_cell
    prev = None
    for rate in args.rates:
        run = serve_cell.ServeRun(cell, args.seed, rate=rate, share=prev)
        run.setup()
        eng = run.engine
        w = run.window(args.seconds)
        backlog = len(eng._pending) + len(eng._waiting) + len(eng._preempted)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:cell.chips])
        _emit(kind="sweep", rate=rate, lanes=run.ecfg.max_batch,
              backlog=backlog, running=len(eng._running),
              peak_bytes_in_use=peak, **w)
        run.free()
        prev = run
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("train", "noise", "serve", "sweep"))
    ap.add_argument("--workload", default="",
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--config", default="",
                    help="with --traffic, a cell by its files instead")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[],
                    help="serve: seeds whose samples the control reads")
    ap.add_argument("--rates", type=lambda t: [float(x) for x in t.split(",")],
                    default=[])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--kernel-mode", default="",
                    help="train: run the program with this router mode")
    ap.add_argument("--layers", type=int, default=0,
                    help="noise: cut the configuration to this depth")
    ap.add_argument("--keys", type=int, default=4,
                    help="noise: keys whose gradients are averaged")
    ap.add_argument("--policy", default="",
                    help="train: run this policy of the program instead")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="serve, sweep: lanes, instead of the mix's")
    ap.add_argument("--witness", action="store_true",
                    help="train: also the program's float32 gradient norms")
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as bench_run, spec
    bench_run._setup_env()
    cell = (spec.load_cell(args.workload) if args.workload
            else spec.cell_from_files(args.config, args.traffic))
    if args.max_batch:
        cell.traffic = dict(cell.traffic, max_batch=args.max_batch)
    import jax
    bench_run.check_platform(jax.devices(), cell.chips)
    bench_run._enable_cache()
    {"train": study_train, "noise": study_noise, "serve": study_serve,
     "sweep": study_sweep}[args.mode](cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
