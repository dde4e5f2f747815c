"""Serving cells: open-loop traffic through the program's ``Engine``.

The engine is built as ``launch/serve.serve_engine`` builds it (the int8
policy with quantize-once weights and the quantized cache, over
``runtime/qpool.QPool``), on weights the benchmark makes from the seed
by the serving loader of the configuration's architecture
(``bench/archs/<model_type>.py``).
Set-up serves one short request per prompt bucket of the mix, which
compiles the prefill program of each bucket (``Engine`` compiles one per
prompt length) and the batched decode program.

The window offers the mix's requests at their due times and calls
``Engine.step()`` while there is work, sleeping to the next due time when
there is none.  Each token is stamped when the step that emitted it
returns.  Time to first token runs from when the request was due; a
request due in the window that has no token when it closes counts at its
wait so far.

``correct``: once the window has closed, the engine steps on, untimed,
until ``check_requests`` requests have finished (for at most
``DRAIN_S``).  A sample of the finished requests drawn from the seed, the
one with the most output tokens always in it, goes through the
architecture's reference, a full forward over prompt and served tokens.
``token_gap`` is the widest gap by which a served token's reference logit
lies below the reference's best logit at its position.
"""

from __future__ import annotations

import dataclasses
import math
import time
import types
from typing import Dict, List

import jax
import numpy as np

from . import model, spec, traffic

WARM_RID = 1 << 30
# the longest the engine steps on after the window for the check's sample
DRAIN_S = 30.0


def make_engine(cfg, policy, ecfg, params, share=None):
    """The program's engine (with another's compiled programs where
    ``share`` is given); a test swaps in a broken one."""
    from repro.launch.engine import Engine
    return Engine(cfg, policy, ecfg, params=params, share_fns=share)


def serving_policy():
    from repro.launch.train import POLICIES
    return dataclasses.replace(POLICIES["int8"], qweights=True, qcache=True)


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class ServeRun:
    """One serving cell's run: ``setup``, ``window``, ``check``."""

    def __init__(self, cell, seed: int, rate: float = 0.0,
                 share: "ServeRun" = None):
        from repro.launch.engine import EngineConfig
        self.cell, self.seed = cell, seed
        self.conf, self.mix = cell.config, dict(cell.traffic)
        if rate:
            self.mix["rate_per_s"] = rate
        self.arch = spec.arch(self.conf["model_type"], cell.root)
        self.cfg = self.arch.arch_config(self.conf)
        self.policy = serving_policy()
        m = self.mix
        max_len = int(m["max_len"])
        page = int(m["page_size"])
        lanes = int(m["max_batch"])
        self.ecfg = EngineConfig(max_len=max_len, page_size=page,
                                 n_pages=lanes * (max_len // page) + 1,
                                 max_batch=lanes)
        self.key = model.seed_key(seed)
        self._load = share._load if share else self.arch.serving_loader(
            self.conf, self.cfg, self.policy)
        self.fns = share.fns if share else None
        self.engine = None
        self.arrivals: List[traffic.Arrival] = []
        self.due: Dict[int, float] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.step_ms: List[tuple] = []

    def _req_seed(self, rid: int) -> int:
        return (self.seed * 1_000_003 + rid) & 0x7FFFFFFF

    def _tokens(self, rid: int) -> int:
        eng = self.engine
        if rid in eng.results:
            return len(eng.results[rid])
        run = eng._running.get(rid)
        return len(run.tokens) if run is not None else 0

    def _busy(self) -> bool:
        e = self.engine
        return bool(e._pending or e._waiting or e._preempted or e._running)

    def setup(self) -> None:
        """Weights from the seed by the architecture's loader, then one
        request per prompt bucket (each compiles its prefill) through the
        batched decode program."""
        from repro.launch.engine import Request
        params = self._load(self.key)
        self.engine = make_engine(self.cfg, self.policy, self.ecfg, params,
                                  self.fns)
        e = self.engine
        self.fns = self.fns or types.SimpleNamespace(
            cfg=e.cfg, policy=e.policy, ecfg=e.ecfg, _prefill=e._prefill,
            _decode1=e._decode1, _decodeN=e._decodeN)
        rng = np.random.Generator(np.random.Philox(key=self.seed,
                                                   counter=[0, 0, 0, 9]))
        warm = [Request(rid=WARM_RID + i,
                        prompt=rng.integers(0, self.cfg.vocab, size=int(p))
                        .astype(np.int32), gen=2, arrival_step=0,
                        seed=self._req_seed(WARM_RID + i))
                for i, p in enumerate(self.mix["prompt_buckets"])]
        self.engine.run(warm)
        jax.block_until_ready(self.engine.params)

    def window(self, seconds: float, tick=None) -> dict:
        """Offer the schedule open-loop for ``seconds``; step while busy.
        Every request due in the window is submitted, the last ones when
        the step running at the window's close returns.  ``tick``, where
        given, is called with the seconds elapsed between steps."""
        from jax.profiler import TraceAnnotation
        from repro.launch.engine import Request
        eng = self.engine
        self.arrivals = traffic.schedule(self.mix, self.cfg.vocab, self.seed,
                                         seconds)
        late, i, n = [], 0, len(self.arrivals)
        seen: Dict[int, int] = {}
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if tick:
                tick(now)
            with TraceAnnotation("bench.submit"):
                while i < n and self.arrivals[i].due_s <= now:
                    a = self.arrivals[i]
                    eng.submit([Request(rid=a.rid, prompt=a.prompt,
                                        gen=a.gen,
                                        arrival_step=eng.clock,
                                        seed=self._req_seed(a.rid))])
                    self.due[a.rid] = a.due_s
                    self.stamps[a.rid] = []
                    seen[a.rid] = 0
                    late.append(now - a.due_s)
                    i += 1
            if now >= seconds:
                break
            if not self._busy():
                nxt = self.arrivals[i].due_s if i < n else seconds
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, seconds) - now))
                continue
            prefills = len(eng.ttft_steps)
            with TraceAnnotation("bench.engine_step"):
                s0 = time.perf_counter()
                eng.step()
                s1 = time.perf_counter()
            self.step_ms.append((len(eng.ttft_steps) > prefills,
                                 1e3 * (s1 - s0)))
            stamp = s1 - t0
            for rid in list(seen):
                k = self._tokens(rid)
                if k > seen[rid]:
                    self.stamps[rid].extend([stamp] * (k - seen[rid]))
                    seen[rid] = k
        end = time.perf_counter() - t0
        out = self._summary(end, late)
        out["drain_steps"], out["drain_s"] = self._drain()
        return out

    def _finished(self) -> int:
        return sum(r < WARM_RID for r in self.engine.results)

    def _drain(self):
        """After the close, with nothing timed, step on until the check
        has ``check_requests`` finished requests to sample from, or for
        at most ``DRAIN_S``: (steps, seconds)."""
        want = int(self.mix["check_requests"])
        steps, t0 = 0, time.perf_counter()
        while (self._finished() < want and self._busy()
               and time.perf_counter() - t0 < DRAIN_S):
            self.engine.step()
            steps += 1
        return steps, time.perf_counter() - t0

    def _summary(self, end: float, late: List[float]) -> dict:
        ttft, gaps, emitted, done = [], [], 0, 0
        for rid, due in self.due.items():
            st = [t for t in self.stamps[rid] if t <= end]
            ttft.append((st[0] if st else end) - due)
            gaps.extend(b - a for a, b in zip(st, st[1:]))
            emitted += len(st)
            done += rid in self.engine.results
        adm = [ms for a, ms in self.step_ms if a]
        dec = [ms for a, ms in self.step_ms if not a]
        out = {"attempted": len(self.due), "failed": len(self.engine.shed),
               "finished": done, "steps": len(self.step_ms),
               "elapsed_s": end, "tokens": emitted,
               "serve_tokens_per_s": emitted / end,
               "ttft_p50_ms": 1e3 * pct(ttft, 50) if ttft else math.nan,
               "ttft_p90_ms": 1e3 * pct(ttft, 90) if ttft else math.nan,
               "ttft_p95_ms": 1e3 * pct(ttft, 95) if ttft else math.nan,
               "itl_p50_ms": 1e3 * pct(gaps, 50) if gaps else math.nan,
               "itl_p95_ms": 1e3 * pct(gaps, 95) if gaps else math.nan,
               "late_p50_ms": 1e3 * pct(late, 50) if late else 0.0,
               "late_max_ms": 1e3 * max(late) if late else 0.0,
               "admit_steps": len(adm), "decode_steps": len(dec),
               "offered": len(self.arrivals)}
        out["prefill_step_ms_p50"] = pct(adm, 50) if adm else None
        out["decode_step_ms_p50"] = pct(dec, 50) if dec else None
        return out

    def record(self) -> dict:
        return {"step_ms": list(self.step_ms)}

    def free(self) -> None:
        if self.engine is not None:
            self.results = dict(self.engine.results)
        self.engine = None

    def sample(self) -> List[int]:
        """Finished requests to check: the longest output and a sample
        drawn from the seed, up to ``check_requests`` in all."""
        done = sorted(r for r in self.results if r < WARM_RID)
        if not done:
            return []
        longest = max(done, key=lambda r: (len(self.results[r]), r))
        rest = [r for r in done if r != longest]
        rng = np.random.Generator(np.random.Philox(key=self.seed,
                                                   counter=[0, 0, 0, 11]))
        k = min(len(rest), int(self.mix["check_requests"]) - 1)
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[j] for j in sorted(pick)]

    def sequences(self):
        """(prompt, served tokens) of each sampled request."""
        prompts = {a.rid: a.prompt for a in self.arrivals}
        return [(prompts[r], np.asarray(self.results[r], np.int32))
                for r in self.sample()]

    def check(self) -> Dict[str, dict]:
        seqs = self.sequences()
        if not seqs:
            return {"token_gap": {"value": math.inf, "requests": 0}}
        gap, served = token_gaps(self.arch, self.conf, self.key, seqs,
                                 int(self.mix["max_len"]))
        return {"token_gap": {"value": gap, "requests": len(seqs),
                              "tokens": served}}


def token_gaps(arch, conf: dict, key, seqs, length: int, pick=None):
    """(widest gap, tokens checked): at each position that produced a
    served token, the best logit of the architecture's reference less its
    logit of that token.
    ``pick(prompt, served)``, where given, names the token to judge at
    each of those positions instead of the served one (the control reads
    its own first choices so).  Every sequence is padded at its end to
    ``length``, so one program serves them all (the attention is causal,
    so the padding changes no logit that is read)."""
    with jax.default_matmul_precision("highest"):
        logits = arch.reference(key, conf)
        worst, served = 0.0, 0
        for prompt, toks in seqs:
            seq = np.zeros(length, np.int32)
            seq[:len(prompt) + len(toks) - 1] = np.concatenate(
                [prompt, toks[:-1]])
            lg = np.asarray(logits(seq))
            rows = lg[len(prompt) - 1:len(prompt) - 1 + len(toks)]
            judged = toks if pick is None else pick(prompt, toks)
            best = rows.max(axis=-1)
            got = rows[np.arange(len(judged)), judged]
            worst = max(worst, float(np.max(best - got)))
            served += len(judged)
        return worst, served
