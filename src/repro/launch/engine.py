"""Continuous-batching integer serving engine over the block-paged qcache
pool (docs/SERVING.md §Engine).

``serve.py`` runs one request set, lock-step: one prefill, then a decode
loop, one private contiguous cache.  This engine runs the real serving
shape instead — N streams arriving over time, admitted against a shared
page pool (``runtime.qpool``), prefill interleaved with iteration-level
batched decode, preemption-by-eviction when the pool runs dry — while
keeping the paper's discipline: batching moves THROUGHPUT, never results.

Determinism contract (everything is pinned by tests):

- per-request randomness replicates ``serve.py`` exactly: request key
  ``jax.random.key(seed)``, prefill key ``fold_in(key, 3)``, decode step
  ``i`` key ``fold_in(key, 10 + i)``, first token = argmax of the prefill
  logits.  An evicted sequence resumes at its saved step index, so
  preemption is invisible in the emitted tokens.
- with ``max_batch == 1`` the engine runs the very same jitted batch-1
  program ``serve.py`` runs — the single-stream golden pin.
- with ``max_batch > 1`` decode lanes run under ``jax.vmap`` of that
  program.  Each lane traces at batch-1 shapes, so per-tensor quantizer
  reductions, stochastic-rounding bits and cache appends are per-lane
  bit-identical to running the stream alone (``test_engine.py`` pins
  vmap-lane == plain).  Part-empty batches are padded with lanes read
  from the pool's zero page, whose writes go to its scratch page — one
  compiled program for the whole run.
- the clock is SIMULATED scheduler steps, not wall time: TTFT and
  tokens/s-per-step are deterministic and CI-stable
  (``benchmarks/serving_bench.py``).

Scheduler, one ``step()``:

1. arrivals whose ``arrival_step`` has come join the wait queue.
2. admission: at most one sequence per step (preempted sequences first,
   then arrivals FIFO), only if its pages fit above the free-page
   watermark.  A fresh admission prefills this step (its TTFT); a
   preempted one relocates its checkpoint into fresh pages.
3. capacity: every running sequence reserves the page its next row lands
   in; on ``PoolExhausted`` the lowest-priority running sequence (latest
   arrival, highest rid) is evicted and re-queued until the allocation
   fits.
4. decode: one batched step over all running lanes — gather each lane's
   contiguous cache through its page table on the device, run, write the
   one dirty block plus the state page back into the pool on the device.
   Only tokens, positions, keys and page-table arrays go in from the
   host, and only tokens come back.  Finished sequences hand their pages
   straight back to the free list.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import fault_injection
from ..runtime.qpool import PoolExhausted, QPool, host_nbytes
from ..runtime.spans import Recorder
from .speculative import draft_config, draft_params, make_spec_decode_step
from .steps import make_decode_step, make_prefill_step, quantize_serving_params

__all__ = ["Engine", "EngineConfig", "Request"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Pool geometry + scheduler bounds.  ``max_len`` bounds every
    admitted sequence's prompt+gen; ``page_size`` must divide it
    (stochastic-rounding bits are position-dependent, so gathered caches
    must reproduce the contiguous max_len layout exactly).

    ``speculate`` > 0 arms speculative decoding (launch.speculative): a
    ``draft_layers``-deep truncation of the model proposes up to
    ``speculate`` tokens per round for every opted-in lane, the target
    verifies, and the engine commits the accepted prefix — emitted tokens
    stay bitwise identical to ``speculate == 0``."""

    max_len: int
    page_size: int = 16
    n_pages: int = 64
    max_batch: int = 8
    watermark: int = 0        # free pages an admission must leave behind
    seed: int = 0             # model-load seed (matches serve.py)
    speculate: int = 0        # draft depth k per round (0 = off)
    draft_layers: int = 0     # truncated-draft depth (required when k > 0)


@dataclasses.dataclass(frozen=True)
class Request:
    """One stream: ``prompt`` is a (prompt_len,) int32 row; ``seed`` keys
    this stream's randomness exactly as ``serve(seed=...)`` would."""

    rid: int
    prompt: np.ndarray
    gen: int
    arrival_step: int = 0
    seed: int = 0
    # extra prefill inputs for the multimodal families (audio src_embeds,
    # vlm patch_embeds): unbatched arrays, keyed as the prefill batch dict
    # expects; the engine adds the batch-1 axis.
    extras: Optional[dict] = None
    # opt this stream out of the engine's speculative mode; a no-op when
    # the engine runs with ``EngineConfig.speculate == 0``.  Speculative
    # and plain lanes batch together in one scheduler step.
    speculate: bool = True


@dataclasses.dataclass
class _Running:
    req: Request
    n_decoded: int = 0                    # decode steps taken (serve's i)
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    # guard bookkeeping (docs/ROBUSTNESS.md §Serving resilience): all of
    # it is scheduling state — none of it feeds the decode programs.
    last_progress_step: int = 0           # clock of the last emitted token
    retries: int = 0                      # guard recoveries of this lane
    spec_disabled: bool = False           # per-lane ladder: fell to plain
    n_evictions: int = 0                  # priority-aging input
    lane_spec_rounds: int = 0             # per-lane tau numerator/denom
    lane_spec_committed: int = 0

    @property
    def pos(self) -> int:
        """Cache position the NEXT decode step writes (serve.py's
        ``prompt_len + i``)."""
        return len(self.req.prompt) + self.n_decoded

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.gen


def _priority(run: _Running):
    """Eviction order: latest arrival (then highest rid) goes first —
    the streams that have waited longest keep their pages."""
    return (run.req.arrival_step, run.req.rid)


class Engine:
    """One engine serves one (cfg, policy, EngineConfig) shape; submit
    any number of requests and ``run()`` them to completion."""

    def __init__(self, cfg, policy, ecfg: EngineConfig, params=None,
                 src_len: Optional[int] = None,
                 share_fns: Optional["Engine"] = None, guard=None):
        self.cfg = cfg
        self.policy = policy
        self.ecfg = ecfg
        # the guard turns on pool checksums; without one the engine takes
        # none of the guard paths and behaves exactly as before.
        self.guard = guard
        # host spans and counters (runtime/spans.py, docs/SERVING.md
        # §Spans and counters); recorded in memory only while started.
        # The pool counts the bytes it moves in the same recorder.
        self.spans = Recorder()
        self.pool = QPool(cfg, policy, page_size=ecfg.page_size,
                          n_pages=ecfg.n_pages, max_len=ecfg.max_len,
                          src_len=src_len, integrity=guard is not None,
                          spans=self.spans)
        self._index_counted = 0     # pool.index_bytes already counted
        if params is None:
            # model load, exactly as serve.py: init from the seed key,
            # weights quantized once (the deployment contract) when the
            # policy serves the persistent weight currency.
            key = jax.random.key(ecfg.seed)
            from ..models import get_model
            params = get_model(cfg).init_params(key, cfg)
            if policy.qweights_on:
                params = quantize_serving_params(
                    params, cfg, policy, jax.random.fold_in(key, 0x9E))
        self.params = params
        if share_fns is not None:
            # reuse another engine's jitted programs (same cfg/policy/
            # max_len required) — scheduler state is NOT shared, only the
            # compile cache, e.g. the bench's batched/serial twin runs.
            assert (share_fns.cfg, share_fns.policy,
                    share_fns.ecfg.max_len) == (cfg, policy, ecfg.max_len)
            self._prefill = share_fns._prefill
            self._decode1 = share_fns._decode1
            self._decodeN = share_fns._decodeN
        else:
            self._prefill = jax.jit(
                make_prefill_step(cfg, policy, ecfg.max_len))
            # the batch-1 program serve.py runs — the golden-pinned path.
            self._decode1 = jax.jit(make_decode_step(cfg, policy))
            # its vmap: params broadcast, (cache, token, pos, raw key)
            # per lane.  jax.jit is lazy, so a max_batch==1 engine never
            # compiles this.
            self._decodeN = jax.jit(jax.vmap(make_decode_step(cfg, policy),
                                             in_axes=(None, 0, 0, 0, 0)))
        if ecfg.speculate > 0:
            # truncated-draft speculative decoding: validate family
            # eligibility + draft depth up front (raises SpeculativeError),
            # slice the draft's weight view, and build the one-round
            # program (draft scan + verify scan + accept/reject in-jit).
            draft_config(cfg, ecfg.draft_layers)
            self._draft_params = draft_params(self.params, ecfg.draft_layers)
            if (share_fns is not None
                    and (share_fns.ecfg.speculate,
                         share_fns.ecfg.draft_layers)
                    == (ecfg.speculate, ecfg.draft_layers)):
                self._spec1 = share_fns._spec1
                self._specN = share_fns._specN
            else:
                step = make_spec_decode_step(
                    cfg, policy, k=ecfg.speculate,
                    draft_layers=ecfg.draft_layers, max_len=ecfg.max_len)
                self._spec1 = jax.jit(step)
                # params + draft params broadcast; (cache, token, pos,
                # step index, raw key, commit budget) per lane.
                self._specN = jax.jit(jax.vmap(
                    step, in_axes=(None, None, 0, 0, 0, 0, 0, 0)))
        self.spec_rounds = 0          # speculative lane-rounds run
        self.spec_accepted = 0        # draft tokens committed (bonus excl.)
        self.spec_rejections = 0      # rounds cut short by a rejection
        self.clock = 0
        self._pending: List[Request] = []
        self._waiting: List[Request] = []
        self._preempted: List[tuple] = []     # (_Running, pool checkpoint)
        self._running: Dict[int, _Running] = {}
        self.results: Dict[int, np.ndarray] = {}
        self.ttft_steps: Dict[int, int] = {}
        self.tokens_per_step: List[int] = []
        self.occupancy_trace: List[float] = []
        self.n_preemptions = 0
        # guard-visible state: dropped streams, recovery count, and the
        # batch ceiling the thrash ladder may shrink below max_batch (the
        # vmap program stays padded to max_batch either way).
        self.shed: Dict[int, str] = {}
        self.n_retries = 0
        self.eff_max_batch = ecfg.max_batch
        if guard is not None:
            guard.attach(self)

    # -- submission --------------------------------------------------------

    def submit(self, requests) -> None:
        for r in requests:
            if len(r.prompt) + r.gen > self.ecfg.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + gen {r.gen} "
                    f"exceeds engine max_len {self.ecfg.max_len}")
            self._pending.append(r)
            self.spans.begin(("queued", r.rid), "engine.queued", rid=r.rid)
        self._pending.sort(key=lambda r: (r.arrival_step, r.rid))

    # -- request-local randomness (serve.py-identical) ----------------------

    def _prefill_key(self, req: Request):
        return jax.random.fold_in(jax.random.key(req.seed), 3)

    def _decode_key(self, req: Request, i: int):
        return jax.random.fold_in(jax.random.key(req.seed), 10 + i)

    # -- scheduler ---------------------------------------------------------

    def _lane_priority(self, run: _Running):
        """Eviction/scheduling priority; with a guard attached this is the
        guard's AGED priority (each eviction boosts the lane), without one
        it is exactly the PR 8 rule — bit-identical scheduling."""
        if self.guard is not None:
            return self.guard.priority(run)
        return _priority(run)

    def _admit_one(self) -> None:
        """At most one admission per step, preempted sequences first."""
        if len(self._running) >= self.eff_max_batch:
            return
        if self._preempted:
            run, ckpt = self._preempted[0]
            need = self.pool.pages_needed(ckpt["length"])
            if self.pool.free_pages - need < self.ecfg.watermark:
                return
            with self.spans.span("engine.admit", rid=run.req.rid):
                self._preempted.pop(0)
                self.pool.readmit(run.req.rid, ckpt)
                run.last_progress_step = self.clock
                self._running[run.req.rid] = run
            return
        if not self._waiting:
            return
        if self.guard is not None and not self.guard.allow_admission(self):
            return
        req = self._waiting[0]
        need = self.pool.pages_needed(len(req.prompt))
        if self.pool.free_pages - need < self.ecfg.watermark:
            return
        self.spans.end(("queued", req.rid))
        with self.spans.span("engine.admit", rid=req.rid):
            self._waiting.pop(0)
            self.pool.admit(req.rid)
            self.pool.ensure_capacity(req.rid, len(req.prompt))
            run = _Running(req, last_progress_step=self.clock)
            self._running[req.rid] = run
            self._do_prefill(run)

    def _prefill_call(self, req: Request):
        """The jitted prefill at this request's batch-1 shape — shared by
        admission and guard lane recovery (both must hit the same program
        with the same key for the bitwise invariant).  The batch goes in
        as host arrays, so a new prompt length compiles the prefill
        program and nothing else; the cache stays on the device."""
        batch = {"tokens": np.asarray(req.prompt, np.int32)[None]}
        for name, arr in (req.extras or {}).items():
            batch[name] = np.asarray(arr)[None]
        self.spans.count("prefill.h2d_bytes", host_nbytes(batch))
        return self._prefill(self.params, batch, self._prefill_key(req))

    def _do_prefill(self, run: _Running) -> None:
        req, sp = run.req, self.spans
        with sp.span("engine.prefill", rid=req.rid):
            cache, logits = self._prefill_call(req)
            tok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        run.tokens.append(tok)
        run.last_progress_step = self.clock
        self.ttft_steps[req.rid] = self.clock - req.arrival_step
        with sp.span("engine.prefill_write", rid=req.rid):
            sp.count("prefill.d2h_bytes", host_nbytes(tok))
            self.pool.write(req.rid, cache, upto=len(req.prompt))
            self._retire_if_done(run)

    def _is_spec(self, run: _Running) -> bool:
        return (self.ecfg.speculate > 0 and run.req.speculate
                and not run.spec_disabled)

    def _spec_budget(self, run: _Running) -> int:
        """Tokens this round may commit: the k drafts + the target's own
        token, clamped to what the request still owes.  Bounds the
        round's page reservation, and the committed cache length stays
        <= max_len - 1 (the final token's row is never written), so the
        verify program's tail-row restoration always covers whatever a
        clamped out-of-range append touched."""
        return min(self.ecfg.speculate + 1, run.req.gen - len(run.tokens))

    def _reserve_or_preempt(self) -> List[_Running]:
        """Reserve next-row pages for every running sequence — a
        speculative lane reserves its whole worst-case block up front and
        gives the tail back after accept/reject (``trim_capacity``) —
        evicting the lowest-priority lane (possibly the requester itself)
        whenever the pool runs dry.  Returns this step's decode lanes."""
        for run in sorted(self._running.values(), key=self._lane_priority):
            if run.req.rid not in self._running:
                continue                      # evicted by an earlier lane
            while run.req.rid in self._running:
                need = (self._spec_budget(run) if self._is_spec(run) else 1)
                try:
                    self.pool.ensure_capacity(run.req.rid, run.pos + need)
                    break
                except PoolExhausted:
                    victim = max(self._running.values(),
                                 key=self._lane_priority)
                    if victim is run and need > 1:
                        # the speculative block itself doesn't fit: give
                        # it up and take a plain single-token reservation
                        # (the commit budget clamps to the reservation, so
                        # tokens are unchanged) before self-evicting.
                        try:
                            self.pool.ensure_capacity(run.req.rid,
                                                      run.pos + 1)
                            break
                        except PoolExhausted:
                            pass
                    self._evict(victim)
        return sorted(self._running.values(), key=self._lane_priority)

    def _evict(self, run: _Running) -> None:
        ckpt = self.pool.evict(run.req.rid)
        del self._running[run.req.rid]
        run.n_evictions += 1
        self._preempted.append((run, ckpt))
        self._preempted.sort(key=lambda rc: self._lane_priority(rc[0]))
        self.n_preemptions += 1

    def _retire_if_done(self, run: _Running) -> None:
        if run.done:
            self.pool.release(run.req.rid)
            del self._running[run.req.rid]
            self.results[run.req.rid] = np.concatenate(run.tokens)

    # -- guard recovery (docs/ROBUSTNESS.md §Serving resilience) -----------

    def _shed_lane(self, rid: int, reason: str) -> None:
        """Drop a running stream: pages back to the free list, no result
        recorded, the reason kept for stats/telemetry."""
        del self._running[rid]
        self.pool.discard(rid)
        self.shed[rid] = reason
        if self.guard is not None:
            self.guard.clear_lane_faults(rid)

    def _replay(self, run: _Running):
        """Rebuild a lane's contiguous cache from its committed tokens:
        re-prefill the prompt, then re-run every committed decode step
        with its original per-step key and the committed token forced.
        The chain is deterministic in (prompt, tokens, keys) — and the
        speculative verify scan IS the sequential program — so the result
        is bitwise identical to the cache the lane held before the fault,
        for the KV families and the recurrent state slots alike.  The
        cache stays on the device."""
        req = run.req
        cache, _ = self._prefill_call(req)
        for i in range(run.n_decoded):
            tok = jnp.asarray(np.asarray(run.tokens[i], np.int32))
            _, cache = self._decode1(self.params, cache, tok,
                                     jnp.int32(len(req.prompt) + i),
                                     self._decode_key(req, i))
        return cache

    def _recover_lane(self, rid: int, reason: str,
                      quarantine_pid: Optional[int] = None) -> None:
        """Guard-driven lane retry: discard the lane's pages (retiring the
        corrupt one to quarantine), clear any injected lane fault, and
        re-admit the replayed cache into fresh pages — evicting other
        lanes if the (possibly shrunken) pool demands it."""
        run = self._running[rid]
        self.pool.discard(rid, quarantine={quarantine_pid}
                          if quarantine_pid is not None else None)
        if self.guard is not None:
            self.guard.clear_lane_faults(rid)
        run.retries += 1
        self.n_retries += 1
        self.pool.admit(rid)
        while True:
            try:
                self.pool.ensure_capacity(rid, run.pos)
                break
            except PoolExhausted:
                others = [r for r in self._running.values()
                          if r.req.rid != rid]
                if not others:
                    self.pool.release(rid)
                    del self._running[rid]
                    self.shed[rid] = f"{reason}: pool cannot hold the lane"
                    return
                self._evict(max(others, key=self._lane_priority))
        self.pool.write(rid, self._replay(run), upto=run.pos)
        run.last_progress_step = self.clock

    def _decode_batch(self, lanes: List[_Running]) -> None:
        """One scheduler step's decode: speculative and plain lanes split
        into their two programs (each pads to max_batch under vmap, so
        per-lane numerics never depend on who else is in the step)."""
        plain = [r for r in lanes if not self._is_spec(r)]
        spec = [r for r in lanes if self._is_spec(r)]
        if plain:
            self._decode_plain(plain)
        if spec:
            self._decode_spec(spec)

    def _gather_lanes(self, lanes: List[_Running]):
        """Each lane's contiguous cache, gathered from the pool on the
        device, and its last token; under vmap padded to ``max_batch``
        with lanes read from the zero page and stacked.  Returns
        (caches, tokens, pad)."""
        rids = [r.req.rid for r in lanes]
        toks = [np.asarray(r.tokens[-1], np.int32) for r in lanes]
        if self.ecfg.max_batch == 1:
            return self.pool.gather_lane(rids[0]), toks[0], 0
        pad = self.ecfg.max_batch - len(lanes)
        vtok = np.stack(toks + [np.zeros(1, np.int32)] * pad)
        return self.pool.gather_lanes(rids, pad), vtok, pad

    def _lane_keys(self, keys, pad: int):
        """The lanes' raw decode keys on the host, padded with zeros."""
        return np.stack(
            [np.asarray(jax.random.key_data(k)) for k in keys]
            + [np.zeros_like(np.asarray(jax.random.key_data(
                jax.random.key(0))))] * pad)

    def _decode_plain(self, lanes: List[_Running]) -> None:
        sp = self.spans
        with sp.span("engine.gather", lanes=len(lanes)):
            vcache, vtok, pad = self._gather_lanes(lanes)
            vpos = np.asarray([r.pos for r in lanes] + [0] * pad, np.int32)
        sp.count("engine.lanes", len(lanes))
        sp.count("engine.pad_lanes", pad)
        if self.ecfg.max_batch == 1:
            # the exact batch-1 program serve.py runs (golden pin).
            run = lanes[0]
            with sp.span("engine.keys"):
                key = self._decode_key(run.req, run.n_decoded)
            with sp.span("engine.decode"):
                sp.count("engine.h2d_bytes", host_nbytes(vtok, vpos[0]))
                logits, cache = self._decode1(self.params, vcache, vtok,
                                              vpos[0], key)
                out_toks = [np.asarray(
                    jnp.argmax(logits, -1).astype(jnp.int32))]
                sp.count("engine.d2h_bytes", host_nbytes(out_toks))
        else:
            with sp.span("engine.keys"):
                vkey = self._lane_keys(
                    [self._decode_key(r.req, r.n_decoded) for r in lanes],
                    pad)
            with sp.span("engine.decode"):
                sp.count("engine.h2d_bytes", host_nbytes(vtok, vpos, vkey))
                vlogits, cache = self._decodeN(self.params, vcache, vtok,
                                               vpos, vkey)
                vout = np.asarray(jnp.argmax(vlogits, -1).astype(jnp.int32))
                sp.count("engine.d2h_bytes", vout.nbytes)
                out_toks = [vout[j] for j in range(len(lanes))]
        with sp.span("engine.scatter"):
            page = self.pool.page_size
            self.pool.write_lanes([r.req.rid for r in lanes], cache,
                                  [[r.pos // page] for r in lanes], pad)
            for run, tok in zip(lanes, out_toks):
                self.pool.set_length(run.req.rid, run.pos + 1)
                run.n_decoded += 1
                run.tokens.append(tok)
                run.last_progress_step = self.clock
                self._retire_if_done(run)

    def _decode_spec(self, lanes: List[_Running]) -> None:
        """One speculative round per lane: draft k, verify, commit the
        accepted prefix.  The committed block scatters through the page
        table exactly like sequential steps would have (the verify scan
        IS the sequential program), then ``trim_capacity`` hands the
        over-reserved tail pages straight back to the free list."""
        sp = self.spans
        with sp.span("engine.gather", lanes=len(lanes)):
            vcache, vtok, pad = self._gather_lanes(lanes)
            vpos = np.asarray([r.pos for r in lanes] + [0] * pad, np.int32)
            vi0 = np.asarray([r.n_decoded for r in lanes] + [0] * pad,
                             np.int32)
            # commit budget: tokens still owed, clamped to the reservation
            # the scheduler actually got (a degraded lane commits fewer).
            mcs = [min(self._spec_budget(r),
                       self.pool.capacity(r.req.rid) - r.pos) for r in lanes]
            vmc = np.asarray(mcs + [1] * pad, np.int32)
        sp.count("engine.lanes", len(lanes))
        sp.count("engine.pad_lanes", pad)
        if self.ecfg.max_batch == 1:
            run = lanes[0]
            with sp.span("engine.keys"):
                key = jax.random.key(run.req.seed)
            with sp.span("engine.decode"):
                sp.count("engine.h2d_bytes",
                         host_nbytes(vtok, vpos[0], vi0[0], vmc[0]))
                targets, commit, cache = self._spec1(
                    self.params, self._draft_params, vcache, vtok, vpos[0],
                    vi0[0], key, vmc[0])
                vtargets, vcommit = np.asarray(targets)[None], \
                    np.asarray(commit)[None]
                sp.count("engine.d2h_bytes", host_nbytes(targets, commit))
        else:
            with sp.span("engine.keys"):
                vkey = self._lane_keys(
                    [jax.random.key(r.req.seed) for r in lanes], pad)
            with sp.span("engine.decode"):
                sp.count("engine.h2d_bytes",
                         host_nbytes(vtok, vpos, vi0, vkey, vmc))
                vtargets, vcommit, cache = self._specN(
                    self.params, self._draft_params, vcache, vtok, vpos, vi0,
                    vkey, vmc)
                vtargets = np.asarray(vtargets)
                vcommit = np.asarray(vcommit)
                sp.count("engine.d2h_bytes", host_nbytes(vtargets, vcommit))
        with sp.span("engine.scatter"):
            page = self.pool.page_size
            commits = [int(vcommit[j][0]) for j in range(len(lanes))]
            blocks = [list(range(r.pos // page, (r.pos + m - 1) // page + 1))
                      for r, m in zip(lanes, commits)]
            # a round of k drafts commits at most k + 1 rows: 1 + ceil(k /
            # page) blocks
            self.pool.write_lanes([r.req.rid for r in lanes], cache, blocks,
                                  pad, 1 - (-self.ecfg.speculate // page))
            self._commit_spec(lanes, mcs, [(vtargets[j], commits[j])
                                           for j in range(len(lanes))])

    def _commit_spec(self, lanes, mcs, outs) -> None:
        for run, mc, (targets, m) in zip(lanes, mcs, outs):
            rid = run.req.rid
            p0 = run.pos
            for j in range(m):
                run.tokens.append(targets[j])
            run.n_decoded += m
            self.pool.set_length(rid, p0 + m)
            self.pool.trim_capacity(rid, p0 + m)
            self.spec_rounds += 1
            self.spec_accepted += m - 1
            if m < mc:
                self.spec_rejections += 1
            run.last_progress_step = self.clock
            run.lane_spec_rounds += 1
            run.lane_spec_committed += m
            self._retire_if_done(run)

    def step(self) -> int:
        """One simulated scheduler step; returns tokens emitted.  The page
        tables and ids the pool sent count as the engine's own traffic."""
        with self.spans.span("engine.step"):
            emitted = self._step()
        self.spans.count("engine.h2d_bytes",
                         self.pool.index_bytes - self._index_counted)
        self._index_counted = self.pool.index_bytes
        return emitted

    def _step(self) -> int:
        self.clock += 1
        while self._pending and self._pending[0].arrival_step <= self.clock:
            self._waiting.append(self._pending.pop(0))
        if self.guard is not None:
            self.guard.on_step(self)
        emitted_before = sum(len(r) for r in self.results.values()) + sum(
            len(r.tokens) for r in self._running.values()) + sum(
            len(rc[0].tokens) for rc in self._preempted)
        self._admit_one()
        with self.spans.span("engine.reserve"):
            lanes = self._reserve_or_preempt()
        # an injected lane stall models a hung device: the lane keeps its
        # pages but gets no decode work, so only the guard's stall
        # watchdog (or a shed) can get it moving again.  With nothing
        # stalled this filter is the identity.
        lanes = [r for r in lanes
                 if not fault_injection.lane_stalled(r.req.rid)]
        lanes = lanes[:self.eff_max_batch]
        if lanes:
            self._decode_batch(lanes)
        emitted = sum(len(r) for r in self.results.values()) + sum(
            len(r.tokens) for r in self._running.values()) + sum(
            len(rc[0].tokens) for rc in self._preempted) - emitted_before
        self.tokens_per_step.append(emitted)
        self.occupancy_trace.append(self.pool.occupancy()["occupancy"])
        return emitted

    def run(self, requests=None, max_steps: int = 100000):
        """Drive every submitted request to completion; returns
        ``{rid: (gen,) int32 token array}``."""
        if requests is not None:
            self.submit(requests)
        while (self._pending or self._waiting or self._preempted
               or self._running):
            if self.clock >= max_steps:
                raise RuntimeError(
                    f"engine wedged after {max_steps} steps: "
                    f"{len(self.results)} done, {len(self._running)} "
                    f"running, {len(self._preempted)} preempted, "
                    f"pool {self.pool.occupancy()}")
            self.step()
        return dict(self.results)

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """Simulated-step serving metrics + pool accounting, the record
        ``benchmarks/serving_bench.py`` emits into BENCH_serving.json."""
        ttfts = sorted(self.ttft_steps.values())
        steps = len(self.tokens_per_step)
        toks = int(sum(self.tokens_per_step))
        pct = (lambda q: float(np.percentile(ttfts, q)) if ttfts else 0.0)
        occ = self.occupancy_trace
        out = {
            "steps": steps,
            "tokens": toks,
            "tokens_per_step": toks / steps if steps else 0.0,
            "ttft_p50_steps": pct(50),
            "ttft_p99_steps": pct(99),
            "n_preemptions": self.n_preemptions,
            "n_retries": self.n_retries,
            "n_shed": len(self.shed),
            "pool": {**self.pool.accounting(),
                     "n_pages": self.pool.n_pages,
                     "peak_live": self.pool.peak_live,
                     "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
                     "peak_occupancy": float(np.max(occ)) if occ else 0.0},
        }
        if self.guard is not None:
            out["guard"] = {"events": len(self.guard.events),
                            "event_counts": self.guard.event_counts(),
                            "eff_max_batch": self.eff_max_batch,
                            "shed": {str(k): v for k, v in self.shed.items()}}
        if self.ecfg.speculate > 0:
            out["speculate"] = self.ecfg.speculate
            out["draft_layers"] = self.ecfg.draft_layers
            out["spec_rounds"] = self.spec_rounds
            out["spec_rejections"] = self.spec_rejections
            # acceptance length tau: mean tokens COMMITTED per speculative
            # round (accepted draft prefix + the target's own token).  A
            # plain decode step commits exactly 1.0, so the trend gate
            # requires strictly > 1.0 — at 1.0 the verifier never accepted
            # a single draft token and speculation is pure overhead.
            out["accepted_tokens_per_step"] = (
                (self.spec_accepted + self.spec_rounds) / self.spec_rounds
                if self.spec_rounds else 0.0)
            # and the draft-only view: mean accepted drafts per round
            # (tau - 1), the raw agreement between truncation and target.
            out["accepted_drafts_per_round"] = (
                self.spec_accepted / self.spec_rounds if self.spec_rounds
                else 0.0)
        return out

    # -- crash-recoverable snapshots (docs/ROBUSTNESS.md) ------------------
    #
    # Everything the scheduler knows is host-side integers: pool pages
    # (int8 mantissas + int32 exponents), page tables, the free list,
    # committed token streams, and per-request seeds — the step keys are
    # pure functions of (seed, step index), so they re-derive exactly.  A
    # snapshot therefore captures serving state EXACTLY, and a restored
    # engine continues every stream bitwise identical to an uninterrupted
    # run.  Preempted lanes' caches were already freed at eviction; their
    # checkpoints are rebuilt at restore by the same committed-token
    # replay the guard's lane recovery uses.

    def save_snapshot(self, mgr, step: Optional[int] = None) -> int:
        """Serialize the full serving state through ``CheckpointManager``
        at a step boundary; returns the snapshot's step id."""
        step = self.clock if step is None else step
        reqs_meta: Dict[str, dict] = {}
        prompts: Dict[str, np.ndarray] = {}
        tokens: Dict[str, np.ndarray] = {}
        extras: Dict[str, dict] = {}

        def add(req: Request, status: str, run: Optional[_Running] = None):
            rid = str(req.rid)
            entry = {"status": status, "gen": req.gen,
                     "arrival_step": req.arrival_step, "seed": req.seed,
                     "speculate": bool(req.speculate),
                     "prompt_len": int(len(req.prompt)),
                     "extras": {k: {"shape": list(np.shape(v)),
                                    "dtype": str(np.asarray(v).dtype)}
                                for k, v in (req.extras or {}).items()}}
            if run is not None:
                entry.update(
                    n_decoded=run.n_decoded, retries=run.retries,
                    spec_disabled=run.spec_disabled,
                    n_evictions=run.n_evictions,
                    last_progress_step=run.last_progress_step,
                    lane_spec_rounds=run.lane_spec_rounds,
                    lane_spec_committed=run.lane_spec_committed,
                    n_tokens=len(run.tokens))
                if run.tokens:
                    tokens[rid] = np.concatenate(
                        [np.asarray(t, np.int32) for t in run.tokens])
            reqs_meta[rid] = entry
            prompts[rid] = np.asarray(req.prompt, np.int32)
            if req.extras:
                extras[rid] = {k: np.asarray(v)
                               for k, v in req.extras.items()}

        for r in self._pending:
            add(r, "pending")
        for r in self._waiting:
            add(r, "waiting")
        for run in self._running.values():
            add(run.req, "running", run)
        for run, _ckpt in self._preempted:
            add(run.req, "preempted", run)
        tree = {"pool": self.pool.snapshot_arrays(),
                "prompts": prompts, "tokens": tokens, "extras": extras,
                "results": {str(rid): np.asarray(v, np.int32)
                            for rid, v in self.results.items()}}
        meta = {
            "kind": "engine_snapshot",
            "clock": self.clock,
            "n_preemptions": self.n_preemptions,
            "n_retries": self.n_retries,
            "eff_max_batch": self.eff_max_batch,
            "shed": {str(k): v for k, v in self.shed.items()},
            "ttft_steps": {str(k): int(v)
                           for k, v in self.ttft_steps.items()},
            "tokens_per_step": [int(x) for x in self.tokens_per_step],
            "occupancy_trace": [float(x) for x in self.occupancy_trace],
            "spec_rounds": self.spec_rounds,
            "spec_accepted": self.spec_accepted,
            "spec_rejections": self.spec_rejections,
            "result_lens": {str(rid): int(len(v))
                            for rid, v in self.results.items()},
            "pool": self.pool.snapshot_meta(),
            "requests": reqs_meta,
            "order": {"pending": [r.rid for r in self._pending],
                      "waiting": [r.rid for r in self._waiting],
                      "preempted": [run.req.rid
                                    for run, _ in self._preempted]},
            "ecfg": dataclasses.asdict(self.ecfg),
            "guard": (self.guard.state_dict()
                      if self.guard is not None else None),
        }
        mgr.save(step, tree, extra=meta)
        return step

    def restore_snapshot(self, mgr, step: Optional[int] = None) -> int:
        """Rebuild serving state on this freshly-constructed engine (same
        cfg/policy/EngineConfig as the snapshotting one — validated).  The
        jit caches are not state: programs recompile (or come via
        ``share_fns``) and retrace to the same bits."""
        step = mgr.latest_step() if step is None else step
        if step is None:
            raise ValueError("no snapshot to restore")
        meta = mgr.load_extra(step)
        if meta.get("kind") != "engine_snapshot":
            raise ValueError(f"step {step} is not an engine snapshot")
        if meta["ecfg"] != dataclasses.asdict(self.ecfg):
            raise ValueError(
                f"snapshot EngineConfig {meta['ecfg']} != this engine's "
                f"{dataclasses.asdict(self.ecfg)}")
        rm = meta["requests"]
        template = {
            "pool": self.pool.snapshot_template(),
            "prompts": {rid: np.zeros(e["prompt_len"], np.int32)
                        for rid, e in rm.items()},
            "tokens": {rid: np.zeros(e["n_tokens"], np.int32)
                       for rid, e in rm.items() if e.get("n_tokens")},
            "extras": {rid: {k: np.zeros(s["shape"], np.dtype(s["dtype"]))
                             for k, s in e["extras"].items()}
                       for rid, e in rm.items() if e["extras"]},
            "results": {rid: np.zeros(n, np.int32)
                        for rid, n in meta["result_lens"].items()},
        }
        tree = mgr.restore(step, template)
        self.pool.restore_state(meta["pool"], tree["pool"])

        def build_req(rid: str) -> Request:
            e = rm[rid]
            ex = None
            if e["extras"]:
                ex = {k: np.asarray(v) for k, v in tree["extras"][rid].items()}
            return Request(rid=int(rid),
                           prompt=np.asarray(tree["prompts"][rid], np.int32),
                           gen=int(e["gen"]),
                           arrival_step=int(e["arrival_step"]),
                           seed=int(e["seed"]), extras=ex,
                           speculate=bool(e["speculate"]))

        def build_run(rid: str) -> _Running:
            e = rm[rid]
            toks = (np.asarray(tree["tokens"][rid], np.int32)
                    if e["n_tokens"] else np.zeros(0, np.int32))
            return _Running(
                build_req(rid), n_decoded=int(e["n_decoded"]),
                tokens=[toks[i:i + 1] for i in range(len(toks))],
                last_progress_step=int(e["last_progress_step"]),
                retries=int(e["retries"]),
                spec_disabled=bool(e["spec_disabled"]),
                n_evictions=int(e["n_evictions"]),
                lane_spec_rounds=int(e["lane_spec_rounds"]),
                lane_spec_committed=int(e["lane_spec_committed"]))

        self._pending = [build_req(str(r)) for r in meta["order"]["pending"]]
        self._waiting = [build_req(str(r)) for r in meta["order"]["waiting"]]
        self._running = {int(rid): build_run(rid)
                         for rid, e in rm.items() if e["status"] == "running"}
        # preempted checkpoints were freed at eviction; rebuild them by
        # committed-token replay (bitwise — the eviction-resume invariant)
        self._preempted = []
        for rid in meta["order"]["preempted"]:
            run = build_run(str(rid))
            self._preempted.append(
                (run, {"cache": self._replay(run), "length": run.pos}))
        self.results = {int(rid): np.asarray(v, np.int32)
                        for rid, v in tree["results"].items()}
        self.clock = int(meta["clock"])
        self.n_preemptions = int(meta["n_preemptions"])
        self.n_retries = int(meta["n_retries"])
        self.eff_max_batch = int(meta["eff_max_batch"])
        self.shed = {int(k): v for k, v in meta["shed"].items()}
        self.ttft_steps = {int(k): int(v)
                           for k, v in meta["ttft_steps"].items()}
        self.tokens_per_step = [int(x) for x in meta["tokens_per_step"]]
        self.occupancy_trace = [float(x) for x in meta["occupancy_trace"]]
        self.spec_rounds = int(meta["spec_rounds"])
        self.spec_accepted = int(meta["spec_accepted"])
        self.spec_rejections = int(meta["spec_rejections"])
        if self.guard is not None and meta["guard"] is not None:
            self.guard.load_state(meta["guard"])
        return step
