"""Block-paged qcache pool: the serving engine's cache allocator.

``launch/serve.py`` gives every sequence a private, contiguously allocated
decode cache sized to ``max_len``.  A serving engine admitting and retiring
streams continuously cannot: it needs one shared physical pool whose unit
of allocation is much smaller than a whole sequence.  This module provides
that pool over the qcache currency of PR 4 (docs/SERVING.md).

The per-row-exponent layout is what makes this cheap: a quantized cache
leaf stores int8/int16 mantissas plus ONE int32 exponent per cache row, so
a block of rows carries everything needed to dequantize it.  Pages
therefore relocate between physical slots — and between eviction
checkpoints and re-admission — as pure integer copies, never a
requantization (``test_qpool.py`` pins ``==`` on mantissas AND exponents).

Layout, per ``models.get_cache_page_spec``:

- leaves with a ``seq_axis`` (transformer/encdec/rglru K/V) are split into
  fixed-size row-blocks of ``page_size`` positions; a per-sequence page
  table maps block index -> physical page.
- leaves without one (recurrent state, token-shift registers, the conv
  ring, encdec cross K/V) live whole in a single-slot STATE page per
  sequence, so the ``QC_STATE`` families serve through the same pool and
  the same free list as the KV families.

Pages are reset to the qcache zero (mantissa 0, exponent 1 — exactly what
``qcache_prefill`` pads with) when allocated, so a gathered cache is
bit-identical to the contiguous cache the single-stream path would hold.
Freeing is copy-free: pages go back on the free list untouched.

Where the pages live: the page stores are device arrays (host memory on
the CPU backend).  Each part of a leaf keeps its pages flat, in rows of
128 elements where they divide, so that on the chip a page is whole
tiles.  Two physical pages sit past the ``n_pages`` usable ones and are
never on the free list: the ZERO page, always at the qcache zero, which
page tables name for unallocated blocks and for padding lanes; and the
SCRATCH page, which takes the writes of padding lanes and of unused
write entries.  The free list, page tables, owners, quarantine and
accounting are host Python, and count the ``n_pages`` usable pages only.

The decode path moves no page bytes between host and device:
``gather_lanes`` builds the lanes' contiguous caches from the stores by
page table in one jitted program, and ``write_lanes`` writes each lane's
dirty blocks back with one donated program that updates the stores in
place.  Pages cross only where a caller needs host bytes — ``gather``
(eviction checkpoints), ``write`` of a host tree (re-admission),
snapshots, the integrity checksums and fault injection — and the
counters ``pool.d2h_bytes`` / ``pool.h2d_bytes`` of the pool's recorder
(the engine's) count those bytes.  The page-table and page-id arrays the
pool sends are summed in ``index_bytes``, for the owner to count as its
own traffic (the engine's ``engine.h2d_bytes``).  The jitted programs are
module-level and keyed by static geometry, so every pool of one shape
shares them and a new pool of a known shape compiles nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import BFP
from ..models import get_cache_page_spec
from .spans import Recorder

__all__ = ["QPool", "PoolConfigError", "PoolExhausted",
           "PoolAccountingError", "SeqPages", "host_nbytes"]

IN_BOUNDS = "promise_in_bounds"     # every page id the pool sends is valid


class PoolConfigError(ValueError):
    """A pool geometry that can never serve (zero pages, page size not
    dividing the cache length) — reject at construction, not mid-request."""


class PoolExhausted(RuntimeError):
    """No free page for an allocation.  The engine catches this and
    preempts the lowest-priority running sequence (docs/SERVING.md)."""


class PoolAccountingError(RuntimeError):
    """The free list was about to be corrupted: a double free, a free of a
    page owned by another sequence, or an alloc/free imbalance.  Raised
    instead of silently appending — a duplicated free-list entry would
    hand the same physical page to two sequences and corrupt both their
    caches (docs/ROBUSTNESS.md)."""


@dataclasses.dataclass
class SeqPages:
    """Per-sequence pool residency: the page table (block index ->
    physical page id), the state page (or -1), and how many positions of
    cache the sequence has actually written."""

    rid: int
    blocks: List[int]
    state_page: int
    length: int = 0


def host_nbytes(*trees) -> int:
    """Bytes of the host (numpy) arrays in ``trees`` as a jitted call
    copies them to the device (in JAX's canonical dtypes)."""
    return sum(x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
               for t in trees for x in jax.tree_util.tree_leaves(t)
               if isinstance(x, (np.ndarray, np.generic)))


def _leaf_parts(leaf) -> Dict[str, "np.ndarray"]:
    """A cache leaf as a dict of plain arrays: BFP -> mantissas + per-row
    exponents (the gradient carrier is a training artifact, never present
    at serving); float leaf -> itself."""
    if isinstance(leaf, BFP):
        return {"m": leaf.m, "e": leaf.e}
    return {"a": leaf}


def _reset_fill(part: str):
    """The qcache zero: exponent 1 dequantizes mantissa 0 to exact 0.0 —
    the same (m=0, e=1) every init_cache/qcache_prefill pad row holds."""
    return 1 if part == "e" else 0


def _page_shape(shape, axis: Optional[int], page_size: int) -> tuple:
    """One page of a leaf part whose batch-1 shape is ``shape``: its
    ``page_size`` rows along the seq axis, or the whole part for a state
    slot."""
    if axis is None:
        return tuple(shape)
    out = list(shape)
    out[axis] = page_size
    return tuple(out)


def _row_shape(page: tuple) -> tuple:
    """How a store keeps one page: its elements flat (C order), in rows of
    128 where they divide."""
    n = math.prod(page)
    return (n // 128, 128) if n % 128 == 0 else (n,)


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """The static shape of a pool: its page size, and per leaf (sorted by
    name) the seq axis (None for a state slot) and each part's batch-1
    shape.  The module's jitted programs are keyed by it."""

    page_size: int
    leaves: Tuple[Tuple[str, Optional[int],
                        Tuple[Tuple[str, Tuple[int, ...]], ...]], ...]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gather(geom: _Geometry, lanes: Optional[int], paged, slots, table,
            state):
    """Contiguous caches from the stores: ``table`` names each lane's
    pages block by block, flat (lane-major), and ``state`` each lane's
    state page; ``lanes`` lanes stacked along a new leading axis, or one
    lane's tree where it is None."""
    lead = () if lanes is None else (lanes,)
    out = {}
    for name, axis, parts in geom.leaves:
        out[name] = {}
        for pname, shape in parts:
            if axis is None:
                x = slots[name][pname].at[state].get(mode=IN_BOUNDS)
                out[name][pname] = x.reshape(lead + shape)
                continue
            page = _page_shape(shape, axis, geom.page_size)
            x = paged[name][pname].at[table].get(mode=IN_BOUNDS)
            x = x.reshape(lead + (-1,) + page)
            # blocks next to the rows within a block, then merged
            x = jnp.moveaxis(x, len(lead), len(lead) + axis)
            out[name][pname] = x.reshape(lead + shape)
    return out


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def _scatter(geom: _Geometry, paged, slots, cache, pages, blocks, state):
    """Blocks of contiguous caches into their pages, in place: lane
    ``j``'s block ``blocks[j, i]`` into page ``pages[j * w + i]`` (``w``
    blocks a lane), and lane ``j``'s state parts into page ``state[j]``.
    ``cache`` is stacked over lanes, or one lane's tree; where ``blocks``
    is None, block ``i`` of one lane goes to ``pages[i]``."""
    ps = geom.page_size
    paged = {k: dict(v) for k, v in paged.items()}
    slots = {k: dict(v) for k, v in slots.items()}
    for name, axis, parts in geom.leaves:
        for pname, shape in parts:
            x = cache[name][pname]
            if x.ndim == len(shape):
                x = x[None]
            if axis is None:
                st = slots[name][pname]
                slots[name][pname] = st.at[state].set(
                    x.reshape(state.shape + st.shape[1:]), mode=IN_BOUNDS)
                continue
            st = paged[name][pname]
            if blocks is None:
                # every block of one lane, in order: a reshape
                x = x[0].reshape(shape[:axis] + (-1, ps) + shape[axis + 1:])
                rows = jnp.moveaxis(x, axis, 0)
            else:
                n, w = blocks.shape
                rows = jnp.stack([
                    lax.dynamic_slice_in_dim(x[j], blocks[j, i] * ps, ps,
                                             axis)
                    for j in range(n) for i in range(w)])
            paged[name][pname] = st.at[pages].set(
                rows.reshape((-1,) + st.shape[1:]), mode=IN_BOUNDS)
    return paged, slots


@functools.partial(jax.jit, donate_argnums=0)
def _reset(store, pid):
    """Page ``pid`` of every part of ``store`` back to the qcache zero, in
    place (a dynamic update of that page alone)."""
    return {name: {p: lax.dynamic_update_index_in_dim(
        a, jnp.full(a.shape[1:], _reset_fill(p), a.dtype), pid, 0)
        for p, a in parts.items()} for name, parts in store.items()}


@jax.jit
def _take(store, pids):
    """Pages ``pids`` of every part of ``store``."""
    return {name: {p: a.at[pids].get(mode=IN_BOUNDS)
                   for p, a in parts.items()}
            for name, parts in store.items()}


@functools.partial(jax.jit, donate_argnums=0)
def _xor(a, pid, mask):
    page = lax.dynamic_index_in_dim(a, pid, 0, keepdims=False)
    return lax.dynamic_update_index_in_dim(a, page ^ mask, pid, 0)


class QPool:
    """Fixed-size page pool for one (cfg, policy, max_len) serving shape.

    ``template`` is the batch-1 ``cache_template`` tree; its structure
    (BFP vs float leaves, QuantConfigs) is kept to rebuild gathered
    caches.  One free list covers row-block pages and state pages alike:
    accounting must always balance ``allocs == frees + live``.  ``spans``
    is the recorder the pool counts the page bytes it moves in (the
    engine's; a private one, off, by default); ``index_bytes`` is the
    running total of the page tables and page ids it has sent.
    """

    def __init__(self, cfg, policy, *, page_size: int, n_pages: int,
                 max_len: int, src_len: Optional[int] = None,
                 integrity: bool = False, spans: Optional[Recorder] = None):
        if page_size <= 0:
            raise PoolConfigError(
                f"page_size must be >= 1 cache row, got {page_size}")
        if n_pages <= 0:
            raise PoolConfigError(
                f"a zero-page pool cannot admit anything: n_pages={n_pages}")
        if max_len % page_size != 0:
            raise PoolConfigError(
                f"page_size {page_size} must divide max_len {max_len}: the "
                f"gathered cache must reproduce the contiguous max_len "
                f"layout exactly (stochastic rounding bits are "
                f"position-dependent)")
        if getattr(cfg, "local_window", 0) and cfg.local_window % page_size:
            raise PoolConfigError(
                f"page_size {page_size} must divide the attention window "
                f"{cfg.local_window} so a window never straddles a "
                f"part-page")
        from ..launch.steps import cache_template
        self.cfg = cfg
        self.policy = policy
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_len = max_len
        self.blocks_per_seq = max_len // page_size
        self.spans = Recorder() if spans is None else spans
        self.index_bytes = 0
        self._tmpl = cache_template(cfg, 1, max_len, src_len=src_len,
                                    policy=policy)
        self._spec = get_cache_page_spec(cfg)
        if set(self._spec) != set(self._tmpl):
            raise PoolConfigError(
                f"cache_page_spec keys {sorted(self._spec)} != cache leaves "
                f"{sorted(self._tmpl)} for family {cfg.family!r}")
        # physical storage: paged leaves hold (n_pages + 2) pages of
        # page_size rows, slot leaves (n_pages + 2) whole leaves — a state
        # page is an ordinary page id whose storage lives in the slot
        # stores.  Pages n_pages and n_pages + 1 are the zero and scratch
        # pages.
        self.zero_page = n_pages
        self.scratch_page = n_pages + 1
        self._paged: Dict[str, Dict[str, jax.Array]] = {}
        self._slots: Dict[str, Dict[str, jax.Array]] = {}
        leaves = []
        for name in sorted(self._spec):
            axis = self._spec[name].seq_axis
            parts = _leaf_parts(self._tmpl[name])
            store = {}
            for pname, part in sorted(parts.items()):
                rows = _row_shape(_page_shape(part.shape, axis, page_size))
                store[pname] = jnp.full((n_pages + 2,) + rows,
                                        _reset_fill(pname), part.dtype)
            (self._paged if axis is not None else self._slots)[name] = store
            leaves.append((name, axis, tuple(
                (pname, tuple(part.shape))
                for pname, part in sorted(parts.items()))))
        self._geom = _Geometry(page_size, tuple(leaves))
        self.has_state_page = bool(self._slots)
        self.has_paged = bool(self._paged)
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self._seqs: Dict[int, SeqPages] = {}
        self.page_allocs = 0
        self.page_frees = 0
        self.peak_live = 0
        # integrity layer: page -> owning sequence for every live page,
        # quarantined pages (never returned to the free list), and — when
        # ``integrity`` is on — a pure-integer checksum per page folded
        # over its mantissas + exponents (docs/ROBUSTNESS.md).
        self.integrity = integrity
        self._owner: Dict[int, int] = {}
        self._quarantined: Set[int] = set()
        self._role: Dict[int, str] = {}
        self._sums: Dict[int, int] = {}
        self._fill_sums: Dict[str, int] = {}

    # -- free-list primitives ----------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return self.n_pages - len(self._free) - len(self._quarantined)

    def _alloc_page(self, rid: int, reset_paged: bool) -> int:
        if not self._free:
            raise PoolExhausted(
                f"pool exhausted: {self.n_pages} pages all live"
                + (f" ({len(self._quarantined)} quarantined)"
                   if self._quarantined else ""))
        pid = self._free.pop()
        self.page_allocs += 1
        self._owner[pid] = rid
        # the reset runs on the device; only the page id crosses
        pid32 = np.int32(pid)
        self.index_bytes += host_nbytes(pid32)
        if reset_paged:
            self._paged = _reset(self._paged, pid32)
        else:
            self._slots = _reset(self._slots, pid32)
        if self.integrity:
            role = "paged" if reset_paged else "slot"
            self._role[pid] = role
            self._sums[pid] = self._fill_sum(role)
        self.peak_live = max(self.peak_live, self.live_pages)
        return pid

    def _free_page(self, pid: int, rid: int,
                   quarantine: bool = False) -> None:
        # copy-free handoff: the data is left in place; the next alloc
        # resets it.  Double frees and frees of a page another sequence
        # owns are accounting corruption, not recoverable states.
        owner = self._owner.get(pid)
        if pid in self._quarantined or owner is None:
            raise PoolAccountingError(
                f"double free of page {pid} by sequence {rid}: page is "
                f"{'quarantined' if pid in self._quarantined else 'already free'}")
        if owner != rid:
            raise PoolAccountingError(
                f"sequence {rid} freed page {pid} owned by sequence "
                f"{owner}")
        del self._owner[pid]
        if quarantine:
            self._quarantined.add(pid)
        else:
            self._free.append(pid)
        self.page_frees += 1
        if self.page_allocs != self.page_frees + self.live_pages:
            raise PoolAccountingError(
                f"pool accounting out of balance after freeing page {pid} "
                f"(sequence {rid}): allocs={self.page_allocs} != "
                f"frees={self.page_frees} + live={self.live_pages}")

    # -- sequence lifecycle ------------------------------------------------

    def pages_needed(self, n_positions: int) -> int:
        """Pages an admission must be able to allocate: blocks covering the
        prompt plus the state page, if this family has one."""
        blocks = -(-n_positions // self.page_size) if self.has_paged else 0
        return blocks + (1 if self.has_state_page else 0)

    def admit(self, rid: int) -> SeqPages:
        if rid in self._seqs:
            raise ValueError(f"sequence {rid} already admitted")
        state_page = self._alloc_page(rid, False) if self.has_state_page else -1
        seq = SeqPages(rid=rid, blocks=[], state_page=state_page)
        self._seqs[rid] = seq
        return seq

    def ensure_capacity(self, rid: int, n_positions: int) -> None:
        """Grow the page table until it covers ``n_positions`` cache rows.
        Raises ``PoolExhausted`` (sequence left intact) when the free list
        runs dry — the engine's preemption trigger."""
        if n_positions > self.max_len:
            raise PoolConfigError(
                f"sequence {rid} wants {n_positions} positions > "
                f"max_len {self.max_len}")
        if not self.has_paged:
            return
        seq = self._seqs[rid]
        while len(seq.blocks) * self.page_size < n_positions:
            seq.blocks.append(self._alloc_page(rid, True))

    def capacity(self, rid: int) -> int:
        """Cache rows the sequence's current page table can hold (the
        reservation ``ensure_capacity`` built; families with no paged
        leaves can always write their state page)."""
        if not self.has_paged:
            return self.max_len
        return len(self._seqs[rid].blocks) * self.page_size

    def trim_capacity(self, rid: int, n_positions: int) -> None:
        """Shrink the page table to exactly cover ``n_positions`` cache
        rows, handing surplus tail pages back to the free list — the
        speculative-decode give-back: a round reserves pages for the full
        speculated block up front, then returns whatever the accept/reject
        didn't commit.  Copy-free like ``release``; a returned page is
        reset on its next allocation, so nothing speculative ever leaks
        into another sequence's gather."""
        if not self.has_paged:
            return
        seq = self._seqs[rid]
        keep = -(-n_positions // self.page_size)
        if seq.length > n_positions:
            raise PoolConfigError(
                f"sequence {rid}: cannot trim to {n_positions} positions "
                f"below the {seq.length} already written")
        while len(seq.blocks) > keep:
            self._free_page(seq.blocks.pop(), rid)

    def release(self, rid: int) -> None:
        """Completion handoff: every page straight back to the free list,
        no data movement."""
        self.discard(rid)

    def discard(self, rid: int, quarantine: Optional[Set[int]] = None) -> None:
        """Drop a sequence's residency without gathering its cache.  Pages
        named in ``quarantine`` (e.g. a page whose checksum no longer
        verifies) are retired to the quarantine set instead of the free
        list, so the corruption can never be handed to another sequence;
        everything else goes back to the free list untouched."""
        quarantine = quarantine or set()
        seq = self._seqs.pop(rid)
        for pid in seq.blocks:
            self._free_page(pid, rid, quarantine=pid in quarantine)
        if seq.state_page >= 0:
            self._free_page(seq.state_page, rid,
                            quarantine=seq.state_page in quarantine)

    # -- data movement -----------------------------------------------------

    def _gather_on_device(self, rids: Sequence[int], lanes: Optional[int]):
        """The device gather of sequences ``rids``, padded to ``lanes``
        stacked lanes (one unstacked lane where None).  It sends the page
        table, ``blocks_per_seq`` int32 ids per lane, flat, and the state
        pages, one per lane; unallocated blocks and the padding lanes
        name the zero page.  A family without paged leaves or without a
        state page sends no table or no state pages."""
        n = 1 if lanes is None else lanes
        table = state = None
        if self.has_paged:
            table = np.full((n, self.blocks_per_seq), self.zero_page,
                            np.int32)
            for j, rid in enumerate(rids):
                blocks = self._seqs[rid].blocks
                table[j, :len(blocks)] = blocks
            table = table.reshape(-1)
        if self.has_state_page:
            state = np.full(n, self.zero_page, np.int32)
            for j, rid in enumerate(rids):
                state[j] = self._seqs[rid].state_page
            if lanes is None:
                state = state[0]
        self.index_bytes += host_nbytes(table, state)
        parts = _gather(self._geom, lanes, self._paged, self._slots, table,
                        state)
        # the cache tree, BFP leaves with the template's QuantConfigs
        return {name: BFP(parts[name]["m"], parts[name]["e"], leaf.cfg)
                if isinstance(leaf, BFP) else parts[name]["a"]
                for name, leaf in self._tmpl.items()}

    def gather_lanes(self, rids: Sequence[int], pad: int = 0):
        """The lanes' contiguous batch-1 caches, stacked along a new
        leading axis of ``len(rids) + pad`` lanes, built on the device by
        page table: each lane is ``==`` to ``gather(rid)``, and each
        padding lane to ``empty_cache()``."""
        return self._gather_on_device(rids, len(rids) + pad)

    def gather_lane(self, rid: int):
        """One sequence's contiguous batch-1 cache, on the device."""
        return self._gather_on_device([rid], None)

    def gather(self, rid: int):
        """The sequence's cache as one contiguous batch-1 tree on the host,
        exactly as the single-stream path would hold it: allocated blocks
        copied into place, unallocated tail blocks left at the qcache zero
        (identical to ``qcache_prefill`` padding), state leaves from the
        state page."""
        out = jax.device_get(self.gather_lane(rid))
        self.spans.count("pool.d2h_bytes", host_nbytes(out))
        return out

    def empty_cache(self):
        """A freshly-reset contiguous batch-1 cache tree (host numpy) —
        what a padding lane gathered from the zero page holds."""
        out = {}
        for name, leaf in self._tmpl.items():
            if isinstance(leaf, BFP):
                m = np.zeros(leaf.m.shape, np.dtype(leaf.m.dtype))
                e = np.ones(leaf.e.shape, np.dtype(leaf.e.dtype))
                out[name] = BFP(m, e, leaf.cfg)
            else:
                out[name] = np.zeros(leaf.shape, np.dtype(leaf.dtype))
        return out

    def _write_pages(self, cache, pages, blocks, state, touched) -> None:
        self.index_bytes += host_nbytes(pages, blocks, state)
        parts = {name: _leaf_parts(leaf) for name, leaf in cache.items()}
        self._paged, self._slots = _scatter(
            self._geom, self._paged, self._slots, parts, pages, blocks,
            state)
        if self.integrity:
            self._sums.update(self._checksums(touched))

    def write(self, rid: int, cache, upto: int) -> None:
        """Scatter one contiguous batch-1 cache tree (device or host) into
        the sequence's pages: every block covering positions [0, upto)
        (prefill, checkpoint restore, lane recovery), and the state-slot
        leaves whole.  One program for every ``upto``: the page list has
        ``blocks_per_seq`` entries, those not written aimed at the
        scratch page."""
        seq = self._seqs[rid]
        pages = state = None
        touched: List[int] = []
        if self.has_paged:
            pages = np.full(self.blocks_per_seq, self.scratch_page, np.int32)
            for b in range(-(-upto // self.page_size)):
                pages[b] = seq.blocks[b]
                touched.append(seq.blocks[b])
        if self.has_state_page:
            state = np.asarray([seq.state_page], np.int32)
            touched.append(seq.state_page)
        self.spans.count("pool.h2d_bytes", host_nbytes(cache))
        self._write_pages(cache, pages, None, state, touched)
        seq.length = max(seq.length, upto)

    def write_lanes(self, rids: Sequence[int], vcache,
                    blocks: Sequence[Sequence[int]], pad: int = 0,
                    width: int = 1) -> None:
        """Write a decode step back, in place on the device: lane ``j``'s
        blocks ``blocks[j]`` (at most ``width``) and its state leaves,
        from ``vcache`` stacked over ``len(rids) + pad`` lanes (or one
        lane's unstacked tree).  Padding lanes and unused entries write
        the scratch page."""
        n = len(rids) + pad
        pages = rows = state = None
        touched: List[int] = []
        if self.has_paged:
            rows = np.zeros((n, width), np.int32)
            pages = np.full((n, width), self.scratch_page, np.int32)
            for j, (rid, bs) in enumerate(zip(rids, blocks)):
                table = self._seqs[rid].blocks
                for w, b in enumerate(bs):
                    rows[j, w], pages[j, w] = b, table[b]
                    touched.append(table[b])
            pages = pages.reshape(-1)
        if self.has_state_page:
            state = np.full(n, self.scratch_page, np.int32)
            for j, rid in enumerate(rids):
                state[j] = self._seqs[rid].state_page
                touched.append(int(state[j]))
        self._write_pages(vcache, pages, rows, state, touched)

    def set_length(self, rid: int, n_positions: int) -> None:
        """Advance the sequence's written-position count (the engine calls
        this after a decode step appended row ``n_positions - 1``)."""
        self._seqs[rid].length = n_positions

    def xor_page(self, pid: int, name: str, part: str, mask) -> None:
        """XOR ``mask`` (a scalar, or one page of leaf ``name``'s part
        ``part``, shaped or flat) into page ``pid``, functionally on the
        device, leaving its recorded checksum as it was — the fault
        injector's bit flip."""
        store = self._paged if name in self._paged else self._slots
        a = store[name][part]
        mask = np.asarray(mask, a.dtype)
        if mask.ndim:
            mask = mask.reshape(a.shape[1:])
        self.spans.count("pool.h2d_bytes", host_nbytes(mask))
        store[name][part] = _xor(a, np.int32(pid), mask)

    # -- eviction / re-admission -------------------------------------------

    def evict(self, rid: int):
        """Preemption: checkpoint the sequence's pages to host copies and
        free them.  The checkpoint is pure integer data (mantissas +
        exponents) — re-admission relocates it into whatever pages are
        then free without requantizing anything."""
        ckpt = {"cache": self.gather(rid),
                "length": self._seqs[rid].length}
        self.release(rid)
        return ckpt

    def readmit(self, rid: int, ckpt) -> SeqPages:
        """Restore an evicted sequence into fresh pages (raises
        ``PoolExhausted``, leaving nothing allocated, if they don't fit)."""
        seq = self.admit(rid)
        try:
            self.ensure_capacity(rid, ckpt["length"])
        except PoolExhausted:
            self.release(rid)
            raise
        self.write(rid, ckpt["cache"], upto=ckpt["length"])
        return seq

    # -- page integrity ----------------------------------------------------
    #
    # The qcache layout makes a page pure integer data (int8 mantissas +
    # one int32 exponent per row), so a page has ONE well-defined byte
    # image and a checksum over it detects any corruption exactly — no
    # float tolerance.  Checksums are recorded at alloc (over the reset
    # fill) and after every write; freeing is copy-free so a free page's
    # sum stays valid until reallocation.  Computing one brings the page
    # to the host.

    def _fill_sum(self, role: str) -> int:
        """The checksum of a freshly reset page in ``role``'s store, from
        the fill alone (nothing crosses)."""
        if role not in self._fill_sums:
            store = self._paged if role == "paged" else self._slots
            crc = 0
            for name in sorted(store):
                for pname in sorted(store[name]):
                    a = store[name][pname]
                    crc = zlib.crc32(np.full(a.shape[1:], _reset_fill(pname),
                                             a.dtype).tobytes(), crc)
            self._fill_sums[role] = crc
        return self._fill_sums[role]

    def _checksums(self, pids: Sequence[int]) -> Dict[int, int]:
        """crc32 of each page in ``pids`` folded over every leaf part of
        its store, in sorted (leaf, part) order so the fold is
        deterministic.  The pages come to the host in one take per store,
        the id list padded to a power of two with the zero page."""
        out: Dict[int, int] = {}
        for role, store in (("paged", self._paged), ("slot", self._slots)):
            group = [pid for pid in pids if self._role[pid] == role]
            if not group:
                continue
            ids = np.full(1 << (len(group) - 1).bit_length(),
                          self.zero_page, np.int32)
            ids[:len(group)] = group
            self.index_bytes += host_nbytes(ids)
            pages = jax.device_get(_take(store, ids))
            self.spans.count("pool.d2h_bytes", host_nbytes(pages))
            for i, pid in enumerate(group):
                crc = 0
                for name in sorted(pages):
                    for pname in sorted(pages[name]):
                        crc = zlib.crc32(np.ascontiguousarray(
                            pages[name][pname][i]).tobytes(), crc)
                out[pid] = crc
        return out

    def owner_of(self, pid: int) -> Optional[int]:
        """The sequence holding page ``pid``, or None if it is free."""
        return self._owner.get(pid)

    def verify_page(self, pid: int) -> bool:
        """True iff page ``pid``'s bytes still match its recorded checksum
        (pages never allocated have no record and verify trivially)."""
        if pid not in self._sums:
            return True
        return self._checksums([pid])[pid] == self._sums[pid]

    def scan_integrity(self) -> dict:
        """Verify every page with a recorded checksum — live AND free
        (free pages keep their data until reallocation, so a corrupt free
        page must be caught before it is handed out).  Quarantined pages
        are already retired and are not re-checked."""
        pids = [pid for pid in sorted(self._sums)
                if pid not in self._quarantined]
        got = self._checksums(pids)
        return {"checked": len(self._sums) - len(self._quarantined),
                "corrupt": [pid for pid in pids
                            if got[pid] != self._sums[pid]]}

    def quarantine_page(self, pid: int) -> None:
        """Retire a FREE corrupted page so it can never be allocated again.
        A live corrupted page must go through ``discard(rid,
        quarantine={pid})`` so its sequence's accounting stays balanced."""
        owner = self._owner.get(pid)
        if owner is not None:
            raise PoolAccountingError(
                f"page {pid} is live (sequence {owner}); quarantine it via "
                f"discard(rid, quarantine={{pid}})")
        if pid in self._quarantined:
            return
        self._free.remove(pid)
        self._quarantined.add(pid)

    @property
    def quarantined_pages(self) -> int:
        return len(self._quarantined)

    # -- snapshot / restore ------------------------------------------------

    def snapshot_meta(self) -> dict:
        """JSON-able pool bookkeeping for an engine snapshot: the free
        list, page tables, quarantine set, counters, and page roles.  The
        page DATA travels separately via ``snapshot_arrays``."""
        return {
            "free": list(self._free),
            "quarantined": sorted(self._quarantined),
            "page_allocs": self.page_allocs,
            "page_frees": self.page_frees,
            "peak_live": self.peak_live,
            "owner": {str(pid): rid for pid, rid in self._owner.items()},
            "roles": {str(pid): role for pid, role in self._role.items()},
            "seqs": {str(rid): {"blocks": list(s.blocks),
                                "state_page": s.state_page,
                                "length": s.length}
                     for rid, s in self._seqs.items()},
        }

    def _snapshot_shapes(self):
        """(kind, name, part, store, shape) of every part as a snapshot
        holds it: ``(n_pages, *page)`` — the usable pages only."""
        for name, axis, parts in self._geom.leaves:
            kind, store = (("slots", self._slots) if axis is None
                           else ("paged", self._paged))
            for pname, shape in parts:
                yield kind, name, pname, store[name][pname], (
                    (self.n_pages,) + _page_shape(shape, axis,
                                                  self.page_size))

    def snapshot_template(self) -> dict:
        """The shapes and dtypes of ``snapshot_arrays``, with no data: the
        template a checkpoint restores into."""
        out = {"paged": {}, "slots": {}}
        for kind, name, pname, a, shape in self._snapshot_shapes():
            out[kind].setdefault(name, {})[pname] = jax.ShapeDtypeStruct(
                shape, a.dtype)
        return out

    def snapshot_arrays(self) -> dict:
        """The usable pages of the stores as host copies, in a flat
        two-level dict of ``(n_pages, *page)`` arrays."""
        out = {"paged": {}, "slots": {}}
        for kind, name, pname, a, shape in self._snapshot_shapes():
            host = np.asarray(a[:self.n_pages]).reshape(shape)
            self.spans.count("pool.d2h_bytes", host.nbytes)
            out[kind].setdefault(name, {})[pname] = host
        return out

    def restore_state(self, meta: dict, arrays: dict) -> None:
        """Overwrite this pool's bookkeeping and page data from a
        snapshot (host or device arrays).  The pool must have been built
        with the same geometry; checksums are recomputed from the
        restored bytes (the checkpoint manager already verified them
        against its own crc32s)."""
        for kind, name, pname, a, _ in self._snapshot_shapes():
            src = arrays[kind][name][pname]
            self.spans.count("pool.h2d_bytes", host_nbytes(src))
            store = self._paged if kind == "paged" else self._slots
            store[name][pname] = a.at[:self.n_pages].set(
                jnp.asarray(src).reshape((self.n_pages,) + a.shape[1:]))
        self._free = [int(p) for p in meta["free"]]
        self._quarantined = {int(p) for p in meta["quarantined"]}
        self.page_allocs = int(meta["page_allocs"])
        self.page_frees = int(meta["page_frees"])
        self.peak_live = int(meta["peak_live"])
        self._owner = {int(p): int(r) for p, r in meta["owner"].items()}
        self._role = {int(p): str(r) for p, r in meta["roles"].items()}
        self._seqs = {int(rid): SeqPages(rid=int(rid),
                                         blocks=[int(b) for b in s["blocks"]],
                                         state_page=int(s["state_page"]),
                                         length=int(s["length"]))
                      for rid, s in meta["seqs"].items()}
        self._sums = (self._checksums(sorted(self._role))
                      if self.integrity else {})

    # -- observability -----------------------------------------------------

    def accounting(self) -> dict:
        """Must always balance: pages allocated == pages freed + live
        (gated by tools/check_bench_trend.py on BENCH_serving.json).
        Quarantined pages are retired, not live: a quarantine is counted
        as a free that never returns to the free list."""
        return {"page_allocs": self.page_allocs,
                "page_frees": self.page_frees,
                "live_pages": self.live_pages,
                "quarantined": len(self._quarantined),
                "balanced": self.page_allocs == self.page_frees
                + self.live_pages}

    def occupancy(self) -> dict:
        return {"n_pages": self.n_pages, "live_pages": self.live_pages,
                "free_pages": self.free_pages, "peak_live": self.peak_live,
                "occupancy": self.live_pages / self.n_pages}
