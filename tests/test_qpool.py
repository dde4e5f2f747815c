"""Pool invariants for the block-paged qcache pool (runtime.qpool).

Everything here runs the pool's own programs over ``cache_template``
trees filled with synthetic integer data — no model runs.  The engine-level
claims (golden pin, vmap-lane bit-identity, preemption resume) live in
``test_engine.py``; this file pins the allocator itself:

- page-spec metadata is declared for every family and congruent with its
  ``cache_layout``;
- alloc/free/evict round-trips keep the accounting balanced (pages
  allocated == pages freed + live) and exhaustion raises, never corrupts;
- a page-table gather is bit-identical to the contiguous cache it
  shreds, including the qcache zero (m=0, e=1) in unwritten tail blocks;
- eviction + re-admission relocates pages as pure integer copies: ``==``
  on mantissas AND exponents, with physically different page ids;
- the device-side batch paths (``gather_lanes`` / ``write_lanes``) are
  ``==`` to the per-lane host gather, the zero page stays the qcache
  zero whatever padding lanes write, and the zero and scratch pages are
  never handed out.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import BFP
from repro.core.policy import PAPER_INT8, QC_ROWS, QC_STATE
from repro.launch.steps import cache_template
from repro.models import get_cache_layout, get_cache_page_spec
from repro.runtime.qpool import (PoolAccountingError, PoolConfigError,
                                PoolExhausted, QPool)

QC = dataclasses.replace(PAPER_INT8, qcache=True)


def _tiny_cfg():
    return dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                               n_layers=2, d_model=32, d_ff=64, n_heads=2,
                               n_kv_heads=2, vocab=97)


def _random_cache(cfg, max_len, seed, src_len=None):
    """A contiguous batch-1 cache tree with random (but valid) integer
    mantissas and per-row exponents — stands in for real prefill output."""
    rs = np.random.RandomState(seed)
    tmpl = cache_template(cfg, 1, max_len, src_len=src_len, policy=QC)
    out = {}
    for name, leaf in tmpl.items():
        if isinstance(leaf, BFP):
            info = np.iinfo(np.dtype(leaf.m.dtype))
            m = rs.randint(info.min, info.max + 1,
                           size=leaf.m.shape).astype(leaf.m.dtype)
            e = rs.randint(1, 40, size=leaf.e.shape).astype(leaf.e.dtype)
            out[name] = BFP(m, e, leaf.cfg)
        else:
            out[name] = rs.randn(*leaf.shape).astype(leaf.dtype)
    return out


def _parts(leaf):
    return {"m": np.asarray(leaf.m), "e": np.asarray(leaf.e)} \
        if isinstance(leaf, BFP) else {"a": np.asarray(leaf)}


def _assert_tree_equal(a, b, where=slice(None)):
    for name in a:
        pa, pb = _parts(a[name]), _parts(b[name])
        for pn in pa:
            np.testing.assert_array_equal(pa[pn], pb[pn],
                                          err_msg=f"{name}.{pn}")


ALL_ARCHS = ["qwen2_0_5b", "rwkv6_3b", "recurrentgemma_2b",
             "seamless_m4t_medium", "pixtral_12b", "minicpm_2b"]


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_page_spec_matches_layout(arch):
    """Every family declares pool metadata congruent with its quantized
    cache layout: same leaves, same currency kind per leaf, and seq axes
    only on leaves that actually grow with decoded positions."""
    cfg = get_smoke_config(arch)
    spec = get_cache_page_spec(cfg)
    layout = get_cache_layout(cfg)
    assert set(spec) == set(layout)
    for name, s in spec.items():
        assert s.kind == layout[name], name
        assert s.kind in (QC_ROWS, QC_STATE)
        if s.kind == QC_STATE:
            # accumulator state is rewritten in place, never appended
            assert s.seq_axis is None, name


def test_alloc_free_evict_roundtrip():
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=6, max_len=12)
    assert pool.pages_needed(6) == 2          # ceil(6/4), no state page
    pool.admit(0)
    pool.ensure_capacity(0, 6)
    assert pool.live_pages == 2 and pool.free_pages == 4
    pool.admit(1)
    pool.ensure_capacity(1, 12)
    assert pool.live_pages == 5
    with pytest.raises(PoolExhausted):
        pool.admit(2)
        pool.ensure_capacity(2, 12)           # needs 3, only 1 free
    pool.release(2)
    pool.release(0)
    acct = pool.accounting()
    assert acct["balanced"]
    assert acct["live_pages"] == 3            # seq 1 only
    pool.release(1)
    acct = pool.accounting()
    assert acct["balanced"] and acct["live_pages"] == 0
    assert acct["page_allocs"] == acct["page_frees"] > 0
    assert pool.peak_live == 6


def test_capacity_and_trim_give_back():
    """The speculative reserve/give-back cycle at the allocator level:
    ``ensure_capacity`` books the worst-case block, ``capacity`` reports
    the reservation, ``trim_capacity`` returns exactly the surplus tail
    pages — and refuses to trim below rows already written."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=6, max_len=12)
    pool.admit(0)
    pool.ensure_capacity(0, 6)                 # prompt: 2 pages
    assert pool.capacity(0) == 8
    pool.set_length(0, 6)
    pool.ensure_capacity(0, 6 + 5)             # speculative block: +1 page
    assert pool.capacity(0) == 12 and pool.live_pages == 3
    pool.set_length(0, 7)                      # round committed 1 token
    pool.trim_capacity(0, 7)                   # give the tail page back
    assert pool.capacity(0) == 8 and pool.live_pages == 2
    pool.trim_capacity(0, 7)                   # idempotent
    assert pool.capacity(0) == 8
    with pytest.raises(PoolConfigError, match="below the 7 already"):
        pool.trim_capacity(0, 6)
    # accepted-everything round: trim is a no-op, nothing freed
    pool.ensure_capacity(0, 12)
    pool.set_length(0, 12)
    pool.trim_capacity(0, 12)
    assert pool.capacity(0) == 12
    pool.release(0)
    acct = pool.accounting()
    assert acct["balanced"] and acct["live_pages"] == 0
    assert acct["page_allocs"] == acct["page_frees"] == 4  # 3 + retaken 1


def test_trim_capacity_state_family_is_noop():
    """QC_STATE families hold one state page regardless of decoded
    length: capacity is always max_len and trim never frees anything."""
    cfg = get_smoke_config("rwkv6_3b")
    pool = QPool(cfg, QC, page_size=4, n_pages=4, max_len=12)
    pool.admit(0)
    pool.ensure_capacity(0, 6)
    assert pool.capacity(0) == 12              # never the binding bound
    live = pool.live_pages
    pool.trim_capacity(0, 6)
    assert pool.live_pages == live
    pool.release(0)
    assert pool.accounting()["balanced"]


def test_gather_bit_identity_vs_contiguous():
    """Shredding a contiguous cache into pages and gathering it back is
    the identity, bit for bit — mantissas and exponents."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12)
    src = _random_cache(cfg, 12, seed=0)
    pool.admit(0)
    pool.ensure_capacity(0, 12)
    pool.write(0, src, upto=12)
    _assert_tree_equal(pool.gather(0), src)


def test_gather_unwritten_tail_is_qcache_zero():
    """Blocks past the written length read back as the qcache zero
    (m=0, e=1) — exactly what qcache_prefill pads with, so a gathered
    part-full cache is bit-identical to the single-stream layout."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12)
    src = _random_cache(cfg, 12, seed=1)
    pool.admit(0)
    pool.ensure_capacity(0, 7)                # blocks 0..1 only
    pool.write(0, src, upto=7)
    got = pool.gather(0)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[name].m[..., :8, :]),
                                      np.asarray(src[name].m[..., :8, :]))
        assert (np.asarray(got[name].m[..., 8:, :]) == 0).all()
        assert (np.asarray(got[name].e[..., 8:, :]) == 1).all()


def test_relocation_without_requantization():
    """Evict -> scramble the free list -> readmit: the sequence lands in
    physically different pages, yet mantissas AND exponents compare
    ``==`` — relocation is pure integer copy, no quantizer ran."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12)
    src = _random_cache(cfg, 12, seed=2)
    pool.admit(0)
    pool.ensure_capacity(0, 12)
    pool.write(0, src, upto=12)
    old_pages = list(pool._seqs[0].blocks)
    ckpt = pool.evict(0)
    assert 0 not in pool._seqs
    # scramble: another sequence grabs (and dirties) some freed pages
    pool.admit(7)
    pool.ensure_capacity(7, 8)
    pool.write(7, _random_cache(cfg, 12, seed=3), upto=8)
    pool.readmit(0, ckpt)
    assert pool._seqs[0].blocks != old_pages
    got = pool.gather(0)
    for name in ("k", "v"):
        assert (np.asarray(got[name].m) == np.asarray(src[name].m)).all()
        assert (np.asarray(got[name].e) == np.asarray(src[name].e)).all()
    assert pool.accounting()["balanced"]


@pytest.mark.parametrize("arch", ["rwkv6_3b", "recurrentgemma_2b",
                                  "seamless_m4t_medium"])
def test_state_slot_families_roundtrip(arch):
    """QC_STATE families (and encdec's write-once cross K/V) ride the
    single-slot state page; mixed paged+slot families round-trip whole."""
    cfg = get_smoke_config(arch)
    max_len, src_len = 8, 8
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=max_len,
                 src_len=src_len)
    if arch == "rwkv6_3b":
        assert not pool.has_paged
        assert pool.pages_needed(max_len) == 1    # the state page alone
    else:
        assert pool.has_paged and pool.has_state_page
    src = _random_cache(cfg, max_len, seed=4, src_len=src_len)
    pool.admit(0)
    pool.ensure_capacity(0, max_len)
    pool.write(0, src, upto=max_len)
    _assert_tree_equal(pool.gather(0), src)
    ckpt = pool.evict(0)
    pool.readmit(0, ckpt)
    _assert_tree_equal(pool.gather(0), src)
    pool.release(0)
    assert pool.accounting()["balanced"]


def test_pool_config_errors():
    cfg = _tiny_cfg()
    with pytest.raises(PoolConfigError, match="page_size"):
        QPool(cfg, QC, page_size=0, n_pages=4, max_len=12)
    with pytest.raises(PoolConfigError, match="zero-page"):
        QPool(cfg, QC, page_size=4, n_pages=0, max_len=12)
    with pytest.raises(PoolConfigError, match="divide max_len"):
        QPool(cfg, QC, page_size=5, n_pages=4, max_len=12)
    win = get_smoke_config("recurrentgemma_2b")     # local_window=16
    with pytest.raises(PoolConfigError, match="window"):
        QPool(win, QC, page_size=3, n_pages=4, max_len=12)


def test_validate_request_pool_errors():
    """serve.validate_request rejects bad pool geometry with clean,
    fix-naming errors (no traceback from inside the pool)."""
    from repro.launch.serve import ServeConfigError, validate_request
    ok = dict(batch=2, prompt_len=6, gen=4, qcache=True, engine=True)
    validate_request("qwen2_0_5b", "int8", page_size=5, n_pages=8, **ok)
    with pytest.raises(ServeConfigError, match="zero-page"):
        validate_request("qwen2_0_5b", "int8", page_size=5, n_pages=0, **ok)
    with pytest.raises(ServeConfigError, match="page-size"):
        validate_request("qwen2_0_5b", "int8", page_size=0, n_pages=8, **ok)
    with pytest.raises(ServeConfigError, match="divide prompt_len"):
        validate_request("qwen2_0_5b", "int8", page_size=3, n_pages=8, **ok)
    # page size must divide the attention window (recurrentgemma: 16)
    with pytest.raises(ServeConfigError, match="window"):
        validate_request("recurrentgemma_2b", "int8", page_size=5,
                         n_pages=8, **ok)
    with pytest.raises(ServeConfigError, match="cannot hold even one"):
        validate_request("qwen2_0_5b", "int8", page_size=5, n_pages=1,
                         batch=2, prompt_len=26, gen=4, qcache=True,
                         engine=True)
    with pytest.raises(ServeConfigError, match="qcache"):
        validate_request("qwen2_0_5b", "int8", page_size=5, n_pages=8,
                         batch=2, prompt_len=6, gen=4, qcache=False,
                         engine=True)


# -- PR 10: accounting guards, page integrity, snapshot/restore ------------


def test_double_free_raises_accounting_error():
    """Freeing a page twice is accounting corruption, not a recoverable
    state — the error names the page and the offending sequence."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=6, max_len=12)
    pool.admit(0)
    pool.ensure_capacity(0, 8)
    pid = pool._seqs[0].blocks[-1]
    pool.trim_capacity(0, 4)                  # frees pid legitimately
    with pytest.raises(PoolAccountingError, match=f"double free of page {pid}"):
        pool._free_page(pid, 0)
    pool.release(0)
    assert pool.accounting()["balanced"]


def test_foreign_free_raises_accounting_error():
    """A sequence freeing a page another sequence owns names both ids."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=6, max_len=12)
    pool.admit(0)
    pool.ensure_capacity(0, 4)
    pid = pool._seqs[0].blocks[0]
    with pytest.raises(PoolAccountingError,
                       match=f"sequence 1 freed page {pid} owned by sequence 0"):
        pool._free_page(pid, 1)
    pool.release(0)
    assert pool.accounting()["balanced"]


def test_page_checksums_verify_and_scan():
    """Integrity pools checksum every page at alloc and write; a bit flip
    in a live page's mantissas is found by ``scan_integrity`` and
    attributed to its owner."""
    from repro.runtime.fault_injection import flip_pool_page_bits
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12,
                 integrity=True)
    pool.admit(0)
    pool.ensure_capacity(0, 12)
    pool.write(0, _random_cache(cfg, 12, seed=5), upto=12)
    scan = pool.scan_integrity()
    assert scan["corrupt"] == [] and scan["checked"] == 3
    pid = pool._seqs[0].blocks[1]
    flip_pool_page_bits(pool, pid, seed=0)
    assert not pool.verify_page(pid)
    scan = pool.scan_integrity()
    assert scan["corrupt"] == [pid]
    assert pool.owner_of(pid) == 0
    # guard-style recovery: discard the lane, retiring the corrupt page
    pool.discard(0, quarantine={pid})
    acct = pool.accounting()
    assert acct["balanced"] and acct["quarantined"] == 1
    assert pool.scan_integrity()["corrupt"] == []
    # the quarantined page never comes back: all 7 remaining pages can be
    # allocated, the 8th admission starves
    pool.admit(1)
    pool.ensure_capacity(1, 12)               # 3 pages
    pool.admit(2)
    pool.ensure_capacity(2, 12)               # 6 pages
    pool.admit(3)
    with pytest.raises(PoolExhausted, match="quarantined"):
        pool.ensure_capacity(3, 12)


def test_quarantine_free_page_and_live_page_rules():
    """A corrupt FREE page is retired directly; quarantining a live page
    must go through ``discard`` so its sequence stays balanced."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=6, max_len=12,
                 integrity=True)
    pool.admit(0)
    pool.ensure_capacity(0, 8)
    live_pid = pool._seqs[0].blocks[0]
    tail_pid = pool._seqs[0].blocks[-1]
    pool.trim_capacity(0, 4)                  # tail_pid back on free list
    # free pages keep their recorded checksum until realloc
    pool.xor_page(tail_pid, "k", "m", 1)
    assert pool.scan_integrity()["corrupt"] == [tail_pid]
    pool.quarantine_page(tail_pid)
    pool.quarantine_page(tail_pid)            # idempotent
    assert pool.quarantined_pages == 1
    with pytest.raises(PoolAccountingError, match="live"):
        pool.quarantine_page(live_pid)
    pool.release(0)
    acct = pool.accounting()
    assert acct["balanced"] and acct["quarantined"] == 1
    assert pool.free_pages == 5


def test_snapshot_restore_roundtrip_bitwise():
    """meta + arrays from ``snapshot_*`` rebuild an equivalent pool in a
    fresh instance: same gather bytes, same accounting, clean scan."""
    cfg = _tiny_cfg()
    pool = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12,
                 integrity=True)
    src = _random_cache(cfg, 12, seed=6)
    pool.admit(0)
    pool.ensure_capacity(0, 12)
    pool.write(0, src, upto=12)
    pool.set_length(0, 12)
    meta = pool.snapshot_meta()
    arrays = {kind: {name: {pn: np.copy(arr) for pn, arr in parts.items()}
                     for name, parts in store.items()}
              for kind, store in pool.snapshot_arrays().items()}
    fresh = QPool(cfg, QC, page_size=4, n_pages=8, max_len=12,
                  integrity=True)
    fresh.restore_state(meta, arrays)
    _assert_tree_equal(fresh.gather(0), src)
    assert fresh.accounting() == pool.accounting()
    assert fresh.scan_integrity()["corrupt"] == []
    fresh.release(0)
    assert fresh.accounting()["balanced"]


# -- the device-resident pool: batched gather and write-back ---------------


def _stack(trees):
    """Per-lane batch-1 trees stacked along a new leading lane axis."""
    out = {}
    for name in trees[0]:
        ps = [_parts(t[name]) for t in trees]
        st = {pn: np.stack([p[pn] for p in ps]) for pn in ps[0]}
        out[name] = BFP(st["m"], st["e"], trees[0][name].cfg) \
            if isinstance(trees[0][name], BFP) else st["a"]
    return out


def _lane(tree, j):
    """Lane ``j`` of a stacked tree, on the host."""
    return {name: (BFP(np.asarray(leaf.m[j]), np.asarray(leaf.e[j]),
                       leaf.cfg) if isinstance(leaf, BFP)
                   else np.asarray(leaf[j]))
            for name, leaf in tree.items()}


def _pool_for(arch):
    cfg = _tiny_cfg() if arch == "tiny" else get_smoke_config(arch)
    return cfg, QPool(cfg, QC, page_size=4, n_pages=12, max_len=12,
                      src_len=12)


@pytest.mark.parametrize("arch", ["tiny", "rwkv6_3b", "recurrentgemma_2b"])
def test_gather_lanes_equals_stacked_host_gather(arch):
    """The device gather of a batch is ``==`` to the per-lane host gather
    stacked — mantissas and exponents, part-written lanes whose tail
    blocks are the qcache zero, a state-page family beside a paged one —
    and every padding lane is ``empty_cache()``."""
    cfg, pool = _pool_for(arch)
    for rid, upto in ((0, 12), (1, 5), (2, 9)):
        pool.admit(rid)
        pool.ensure_capacity(rid, upto)
        pool.write(rid, _random_cache(cfg, 12, seed=10 + rid, src_len=12),
                   upto=upto)
    got = pool.gather_lanes([2, 0, 1], pad=2)
    _assert_tree_equal(
        got, _stack([pool.gather(r) for r in (2, 0, 1)]
                    + [pool.empty_cache()] * 2))
    if pool.has_paged:
        tail = np.asarray(got["k"].m[2][..., 8:, :])
        assert tail.size and (tail == 0).all()
        assert (np.asarray(got["k"].e[2][..., 8:, :]) == 1).all()


@pytest.mark.parametrize("arch", ["tiny", "recurrentgemma_2b"])
def test_write_lanes_then_gather_lanes_round_trip(arch):
    """``write_lanes`` writes exactly each live lane's listed blocks and
    its state leaves: gathered back, those blocks and the state equal the
    written batch, and every other block is what it was."""
    cfg, pool = _pool_for(arch)
    before = {}
    for rid in (0, 1):
        pool.admit(rid)
        pool.ensure_capacity(rid, 12)
        pool.write(rid, _random_cache(cfg, 12, seed=20 + rid, src_len=12),
                   upto=12)
        before[rid] = pool.gather(rid)
    batch = _stack([_random_cache(cfg, 12, seed=30 + j, src_len=12)
                    for j in range(3)])
    blocks = [[1], [0, 2]]
    pool.write_lanes([0, 1], batch, blocks, pad=1, width=2)
    got = pool.gather_lanes([0, 1])
    for j, rid in enumerate((0, 1)):
        new, old, lane = _lane(got, j), before[rid], _lane(batch, j)
        for name, spec in pool._spec.items():
            pn, po, pl = _parts(new[name]), _parts(old[name]), \
                _parts(lane[name])
            for part in pn:
                if spec.seq_axis is None:
                    np.testing.assert_array_equal(pn[part], pl[part])
                    continue
                for b in range(3):
                    idx = [slice(None)] * pn[part].ndim
                    idx[spec.seq_axis] = slice(4 * b, 4 * b + 4)
                    want = pl if b in blocks[j] else po
                    np.testing.assert_array_equal(
                        pn[part][tuple(idx)], want[part][tuple(idx)],
                        err_msg=f"lane {rid} {name}.{part} block {b}")


def test_zero_page_survives_padding_lane_writes():
    """Padding lanes read the zero page and write the scratch page: after
    a write-back whose padding lanes hold random data, a padding lane
    still gathers as ``empty_cache()``, and so does a sequence's
    unallocated tail."""
    cfg, pool = _pool_for("tiny")
    pool.admit(0)
    pool.ensure_capacity(0, 4)
    noise = _stack([_random_cache(cfg, 12, seed=40 + j) for j in range(3)])
    pool.write_lanes([0], noise, [[0]], pad=2)
    got = pool.gather_lanes([0], pad=2)
    for j in (1, 2):
        _assert_tree_equal(_lane(got, j), pool.empty_cache())
    for name in ("k", "v"):
        assert (np.asarray(got[name].m[0][..., 4:, :]) == 0).all()
        assert (np.asarray(got[name].e[0][..., 4:, :]) == 1).all()


def test_zero_and_scratch_pages_never_handed_out():
    """The two extra physical pages sit past the usable ones and off the
    free list: filling the pool hands out exactly pages 0..n_pages-1,
    and accounting and occupancy count those alone."""
    cfg, pool = _pool_for("tiny")
    assert (pool.zero_page, pool.scratch_page) == (12, 13)
    for rid in range(4):
        pool.admit(rid)
        pool.ensure_capacity(rid, 12)
    handed = sorted(p for rid in range(4) for p in pool._seqs[rid].blocks)
    assert handed == list(range(12))
    occ = pool.occupancy()
    assert occ["n_pages"] == 12 and occ["live_pages"] == 12
    assert occ["free_pages"] == 0 and occ["occupancy"] == 1.0
    pool.admit(4)
    with pytest.raises(PoolExhausted):
        pool.ensure_capacity(4, 1)
    for rid in range(5):
        pool.release(rid)
    acct = pool.accounting()
    assert acct["balanced"] and acct["live_pages"] == 0
    assert acct["page_allocs"] == acct["page_frees"] == 12
    assert pool.free_pages == 12
