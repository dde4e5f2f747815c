"""Training cells: the program's integer train step, timed and checked.

The configuration's architecture (``bench/archs/<model_type>.py``) gives
the start weights and the reference; it has to have the training hooks.
Set-up builds one object, the jitted train step of
``launch/steps.make_train_step`` with its state from
``core.integer_sgd_init``, placed on the 1x1 mesh as ``launch/train.train``
places it, and drives it through steps 0, 1 and 2 by the window's own
call and feed.  The window goes on with the same object from step 3.
Once the window has closed, the plain reference follows steps 0 to 2 from
the same seed and the two are compared:

  loss_gap    the largest relative gap of the three steps' losses;
  grad_gap    the worst leaf's gap between the norms of the first
              gradient (the program's, read from its momentum after one
              step, where v = 0.9 * 0 + g);
  change_gap  the worst leaf's gap between the norms of the weights'
              change over the three steps (masters after step 2 minus the
              masters it started from).

A leaf's gap is |program - reference| over the larger of the reference's
norm of that leaf and of the median leaf.  Leaves whose reference
gradient is under a thousandth of the median leaf's (a key bias under
softmax) are left out of both gaps.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import model, spec
from .traffic import SyntheticLM

CHECK_STEPS = 3
QUIET_LEAF = 1e-3
# what an architecture gives the training runner besides serving's parts
TRAIN_HOOKS = ("init_weights", "loss", "sgd_readings", "leaf_norms")


def training_arch(conf: dict, root=spec.ROOT):
    """The configuration's architecture, which must have the training
    hooks."""
    arch = spec.arch(conf["model_type"], root)
    missing = [h for h in TRAIN_HOOKS if not hasattr(arch, h)]
    if missing:
        raise NotImplementedError(
            f"{conf['name']}: {arch.__file__} has no {', '.join(missing)}; "
            f"the training runner needs {', '.join(TRAIN_HOOKS)}")
    return arch


def make_step(cfg, policy, hyper) -> Callable:
    """The program's train step; a test swaps in a broken one."""
    from repro.launch.steps import make_train_step
    return make_train_step(cfg, policy, hyper)


def _is_bfp(x) -> bool:
    from repro.core.bfp import BFP
    return isinstance(x, BFP)


def _bfp_norms(tree) -> jnp.ndarray:
    from repro.core.bfp import dequantize
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=_is_bfp)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(dequantize(x))))
                      for x in leaves])


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """(worst gap, its leaf index) over the kept leaves."""
    base = np.maximum(ref, np.median(ref))
    gaps = np.where(keep, np.abs(prog - ref) / base, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare(prog: dict, ref: dict, names: List[str]) -> Dict[str, dict]:
    """The numbers that decide ``correct``, from the program's and the
    reference's readings (losses, first-gradient and change norms)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gr = np.asarray(ref["grad"], np.float64)
    keep = gr >= QUIET_LEAF * np.median(gr)
    g_gap, gi = leaf_gaps(np.asarray(prog["grad"], np.float64), gr, keep)
    c_gap, ci = leaf_gaps(np.asarray(prog["change"], np.float64),
                          np.asarray(ref["change"], np.float64), keep)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not (np.all(np.isfinite(lp)) and np.isfinite(g_gap)
            and np.isfinite(c_gap)):
        loss_gap = g_gap = c_gap = math.inf
    return {"loss_gap": {"value": loss_gap},
            "grad_gap": {"value": g_gap, "leaf": names[gi]},
            "change_gap": {"value": c_gap, "leaf": names[ci]},
            "left_out": [n for n, k in zip(names, keep) if not k],
            "leaves": {n: [float(a), float(b), float(c), float(d)]
                       for n, a, b, c, d in zip(
                           names, prog["grad"], gr, prog["change"],
                           ref["change"])}}


class Programs:
    """The compiled parts of a training cell under one policy: the train
    step, the start state from a key, and the check's norm programs.
    Runs of several seeds in one process can share them.  ``kernel_mode``
    (a study's second witness) replaces the policy's router mode."""

    def __init__(self, cell, policy_name: str = "", kernel_mode: str = ""):
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import TrainHyper, state_shardings
        from repro.launch.train import POLICIES
        from repro.runtime.sharding import DEFAULT_RULES
        from jax.sharding import NamedSharding
        tr = cell.traffic
        self.conf = conf = cell.config
        self.arch = arch = training_arch(conf, cell.root)
        self.cfg = arch.arch_config(conf)
        policy = POLICIES[policy_name or tr["policy"]]
        if kernel_mode:
            policy = dataclasses.replace(policy, kernel_mode=kernel_mode)
        self.policy = policy
        self.lr, self.momentum = float(tr["lr"]), float(tr["momentum"])
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        self.mesh = make_local_mesh(1, 1)
        self.rules = DEFAULT_RULES
        self.batch_sh = NamedSharding(self.mesh,
                                      DEFAULT_RULES.spec(("batch",)))
        hyper = TrainHyper(lr=self.lr, momentum=self.momentum)
        self.step_fn = jax.jit(make_step(self.cfg, policy, hyper))

        def start(k):
            from repro.core import integer_sgd_init
            return integer_sgd_init(arch.init_weights(k, conf), policy,
                                    key=k)

        self.start = jax.jit(start, out_shardings=state_shardings(
            self.cfg, policy, self.mesh, DEFAULT_RULES))
        self.norms = jax.jit(_bfp_norms)
        self.change = jax.jit(lambda m, k: _bfp_norms_diff(
            m, start(k).masters))
        self.names = model.leaf_names(
            jax.eval_shape(lambda k: arch.init_weights(k, conf),
                           jax.random.key(0)))


class TrainRun:
    """One training cell's run: ``setup``, ``window``, ``check``."""

    def __init__(self, cell, seed: int, programs: Programs = None):
        self.p = programs or Programs(cell)
        self.cell, self.seed = cell, seed
        self.conf = cell.config
        self.batch, self.seq = self.p.batch, self.p.seq
        self.ds = SyntheticLM(vocab=self.p.cfg.vocab, seq_len=self.seq,
                              batch=self.batch, seed=seed)
        self.key = model.seed_key(seed)
        self.losses: List[float] = []
        self.window_losses: List[float] = []
        self.decisions = []
        self.step = 0
        self.state = None

    def _one_step(self) -> float:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.batch"):
            hb = self.ds.batch_for_step(self.step)
            bj = jax.device_put({k: jnp.asarray(v) for k, v in hb.items()},
                                self.p.batch_sh)
        with TraceAnnotation("bench.step"):
            self.state, loss = self.p.step_fn(
                self.state, bj, jax.random.fold_in(self.key, self.step))
        with TraceAnnotation("bench.loss_fetch"):
            val = float(loss)
        self.step += 1
        return val

    def setup(self) -> None:
        """Weights from the seed, then steps 0..2 (the first compiles),
        keeping what the check reads."""
        from repro.kernels import dispatch
        from repro.runtime.sharding import use_rules
        with use_rules(self.p.rules, self.p.mesh):
            self.state = self.p.start(self.key)
            with dispatch.record_decisions() as dec:
                self.losses.append(self._one_step())
            self.decisions = list(dec)
            self.grad = np.asarray(self.p.norms(self.state.momentum))
            while self.step < CHECK_STEPS:
                self.losses.append(self._one_step())
            self.change = np.asarray(self.p.change(self.state.masters,
                                                  self.key))
            jax.block_until_ready(self.state)

    def window(self, seconds: float, tick=None) -> dict:
        """Steps from step 3 on until ``seconds`` have passed; the window
        ends when the last step's state is ready.  ``tick``, where given,
        is called with the seconds elapsed between steps."""
        from repro.runtime.sharding import use_rules
        n0 = self.step
        with use_rules(self.p.rules, self.p.mesh):
            t0 = time.perf_counter()
            while True:
                if tick:
                    tick(time.perf_counter() - t0)
                self.window_losses.append(self._one_step())
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(self.state)
            elapsed = time.perf_counter() - t0
        steps = self.step - n0
        tokens = steps * self.batch * self.seq
        return {"steps": steps, "attempted": steps, "elapsed_s": elapsed,
                "train_tokens_per_s": tokens / elapsed,
                "failed": int(sum(not math.isfinite(x)
                                  for x in self.window_losses))}

    def record(self) -> dict:
        """What the per-layer readers may read besides the trace."""
        return {"batch": self.batch, "seq": self.seq,
                "decisions": [dict(op=d.op, path=d.path, kind=d.kind, m=d.m,
                                   k=d.k, n=d.n) for d in self.decisions]}

    def free(self) -> None:
        self.state = None

    def reference(self) -> dict:
        """The reference's readings of steps 0..2."""
        batches = [self.ds.batch_for_step(i) for i in range(CHECK_STEPS)]
        conf, arch = self.conf, self.p.arch
        losses, g0, change = arch.sgd_readings(
            lambda k: arch.init_weights(k, conf), self.key, batches, conf,
            self.p.lr, self.p.momentum)
        return {"losses": losses, "grad": np.asarray(g0),
                "change": np.asarray(change)}

    def readings(self) -> dict:
        return {"losses": self.losses[:CHECK_STEPS], "grad": self.grad,
                "change": self.change}

    def check(self) -> Dict[str, dict]:
        """The reference follows steps 0..2; returns the compared numbers."""
        return compare(self.readings(), self.reference(), self.p.names)


def _bfp_norms_diff(a, b) -> jnp.ndarray:
    from repro.core.bfp import dequantize
    la = jax.tree_util.tree_leaves(a, is_leaf=_is_bfp)
    lb = jax.tree_util.tree_leaves(b, is_leaf=_is_bfp)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(dequantize(x)
                                                  - dequantize(y))))
                      for x, y in zip(la, lb)])
