"""Serving guard contract (launch/engine_guard.py + docs/ROBUSTNESS.md).

The guard may change SCHEDULING and COST, never numerics.  Every test
here pins that as a bitwise claim against the guard-off references
test_engine.py already golden-pins:

- guard attached, nothing wrong: zero events, tokens bitwise identical
  to the guard-off engine (and guard=None IS the PR 9 engine — the
  integrity machinery never runs);
- a corrupted pool page is found by the checksum scan, its lane rebuilt
  by committed-token replay, the page quarantined — tokens unchanged;
- a stalled lane is recovered the same way; with retries exhausted the
  stream is shed, and every OTHER stream still matches bitwise;
- TTFT overload sheds waiting streams without touching running ones;
- the degradation ladder (per-lane speculation off, ``qdecode_block``
  administratively dropped to its bit-exact mirror) moves cost only.

One module fixture compiles the jitted programs once; every engine
shares them via ``share_fns``.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.policy import PAPER_INT8
from repro.kernels import dispatch
from repro.launch.engine import Engine, EngineConfig, Request, _Running
from repro.launch.engine_guard import EngineGuard, ServeGuardConfig
from repro.runtime import fault_injection as fi

POLICY = dataclasses.replace(PAPER_INT8, qweights=True, qcache=True)
PROMPT_LEN, GEN, MAX_LEN, PAGE = 6, 6, 12, 4


def _tiny_cfg():
    return dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                               n_layers=2, d_model=32, d_ff=64, n_heads=2,
                               n_kv_heads=2, vocab=97)


def _requests(cfg, n):
    rs = np.random.RandomState(7)
    return [Request(rid=i,
                    prompt=rs.randint(0, cfg.vocab,
                                      size=PROMPT_LEN).astype(np.int32),
                    gen=GEN, arrival_step=i, seed=100 + i)
            for i in range(n)]


@pytest.fixture(scope="module")
def world():
    cfg = _tiny_cfg()
    base = Engine(cfg, POLICY, EngineConfig(
        max_len=MAX_LEN, page_size=PAGE, n_pages=16, max_batch=4, seed=0))
    reqs = _requests(cfg, 4)
    refs = base.run(list(reqs))         # guard-off tokens, golden-pinned
    return {"cfg": cfg, "base": base, "reqs": reqs, "refs": refs}


def _twin(world, guard=None, **over):
    kw = dict(max_len=MAX_LEN, page_size=PAGE, n_pages=16, max_batch=4,
              seed=0)
    kw.update(over)
    return Engine(world["cfg"], POLICY, EngineConfig(**kw),
                  params=world["base"].params, share_fns=world["base"],
                  guard=guard)


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    fi.clear_lane_stalls()
    fi.clear_kernel_failure()
    dispatch.enable_ops()


def test_guard_off_engine_has_no_integrity_machinery(world):
    """guard=None IS the PR 9 engine: no checksums, no guard stats key."""
    eng = _twin(world)
    assert not eng.pool.integrity
    eng.run(list(world["reqs"]))
    assert "guard" not in eng.stats()


def test_guard_on_no_fault_is_bitwise_and_silent(world):
    """Attached guard, healthy run: zero events, zero sheds, and every
    stream's tokens bitwise equal the guard-off references."""
    guard = EngineGuard(ServeGuardConfig(scan_every=1))
    eng = _twin(world, guard=guard)
    assert eng.pool.integrity
    out = eng.run(list(world["reqs"]))
    assert guard.events == []
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(out[rid], ref,
                                      err_msg=f"stream {rid}")
    s = eng.stats()
    assert s["guard"]["events"] == 0 and s["n_shed"] == 0
    assert s["pool"]["balanced"]


def test_page_corruption_recovered_bitwise(world):
    """Bit-flip a live page mid-run: the scan attributes it to its owner,
    the lane is rebuilt by committed-token replay, the page is
    quarantined — and the stream's tokens never change."""
    guard = EngineGuard(ServeGuardConfig(scan_every=1))
    eng = _twin(world, guard=guard)
    eng.submit(list(world["reqs"]))
    for _ in range(6):                  # all lanes running, some decoded
        eng.step()
    victim = next(iter(eng._running))
    pid = eng.pool._seqs[victim].blocks[0]
    fi.flip_pool_page_bits(eng.pool, pid, seed=3)
    out = eng.run()
    counts = guard.event_counts()
    assert counts.get("page_corruption", 0) >= 1
    assert counts.get("lane_recovered", 0) >= 1
    assert eng.n_retries >= 1
    assert eng.pool.quarantined_pages == 1
    assert eng.pool.accounting()["balanced"]
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(
            out[rid], ref,
            err_msg=f"stream {rid}: recovery changed tokens")


def test_corrupt_free_page_is_quarantined_not_reissued(world):
    """Corruption on a FREE page (data persists until realloc) is retired
    directly — no lane recovery, no token change."""
    guard = EngineGuard(ServeGuardConfig(scan_every=1))
    eng = _twin(world, guard=guard)
    eng.submit(list(world["reqs"]))
    # run until a stream completed and released its pages: only a page
    # that was allocated (checksummed) and freed can corrupt on the free
    # list — never-allocated pages have no bytes to protect yet.
    while not eng.results:
        eng.step()
    free_pid = next(p for p in eng.pool._free if p in eng.pool._sums)
    eng.pool.xor_page(free_pid, "k", "m", 4)
    out = eng.run()
    assert guard.event_counts() == {"page_quarantined": 1}
    assert eng.pool.quarantined_pages == 1
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(out[rid], ref)


def test_lane_stall_recovered_bitwise(world):
    """An injected lane hang trips the stall watchdog; recovery rebuilds
    the lane and clears the fault; tokens unchanged."""
    guard = EngineGuard(ServeGuardConfig(stall_deadline_steps=3))
    eng = _twin(world, guard=guard)
    eng.submit(list(world["reqs"]))
    for _ in range(4):
        eng.step()
    victim = next(iter(eng._running))
    fi.stall_lane(victim)
    out = eng.run()
    counts = guard.event_counts()
    assert counts.get("lane_stalled", 0) >= 1
    assert counts.get("lane_recovered", 0) >= 1
    assert not fi.lane_stalled(victim)
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(
            out[rid], ref,
            err_msg=f"stream {rid}: stall recovery changed tokens")


def test_retries_exhausted_sheds_lane_others_bitwise(world):
    """max_lane_retries=0: the first fault sheds the stream instead of
    retrying.  The shed stream has no result; every other stream still
    matches its reference bitwise and the pool stays balanced."""
    guard = EngineGuard(ServeGuardConfig(scan_every=1, max_lane_retries=0))
    eng = _twin(world, guard=guard)
    eng.submit(list(world["reqs"]))
    for _ in range(6):
        eng.step()
    victim = next(iter(eng._running))
    pid = eng.pool._seqs[victim].blocks[0]
    fi.flip_pool_page_bits(eng.pool, pid, seed=4)
    out = eng.run()
    assert guard.event_counts().get("stream_shed", 0) == 1
    assert victim in eng.shed and victim not in out
    assert eng.stats()["n_shed"] == 1
    assert eng.pool.accounting()["balanced"]
    for rid, ref in world["refs"].items():
        if rid == victim:
            continue
        np.testing.assert_array_equal(
            out[rid], ref,
            err_msg=f"stream {rid}: neighbour shed changed tokens")


def test_ttft_deadline_sheds_waiting_not_running(world):
    """A 1-lane engine with a tight TTFT deadline sheds the streams it
    cannot start in time; the streams it does serve match bitwise."""
    guard = EngineGuard(ServeGuardConfig(ttft_deadline_steps=3))
    eng = _twin(world, guard=guard, max_batch=1)
    out = eng.run(list(world["reqs"]))
    assert len(eng.shed) >= 1
    assert all(v == "ttft_deadline" for v in eng.shed.values())
    assert set(out) | set(eng.shed) == {r.rid for r in world["reqs"]}
    for rid in out:
        np.testing.assert_array_equal(
            out[rid], world["refs"][rid],
            err_msg=f"stream {rid}: shedding neighbours changed tokens")


def test_low_tau_disables_lane_speculation_bitwise(world):
    """An impossible acceptance floor trips the per-lane ladder: every
    lane falls back to plain decode after ``min_spec_rounds`` — and the
    tokens stay bitwise identical (the PR 9 spec-off pin)."""
    guard = EngineGuard(ServeGuardConfig(min_accept_tau=99.0,
                                         min_spec_rounds=1))
    eng = Engine(world["cfg"], POLICY, EngineConfig(
        max_len=MAX_LEN, page_size=PAGE, n_pages=16, max_batch=4, seed=0,
        speculate=2, draft_layers=1),
        params=world["base"].params, share_fns=world["base"], guard=guard)
    out = eng.run(list(world["reqs"]))
    assert guard.event_counts().get("spec_disabled", 0) >= 1
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(
            out[rid], ref,
            err_msg=f"stream {rid}: spec disable changed tokens")


def test_kernel_fallback_storm_drops_qdecode_block(world):
    """Repeated dispatch ladder fallbacks make the guard drop the decode
    megakernel: subsequent plans come back JNP/OP_DISABLED (the chain's
    bit-exact mirror), and tokens still match the references.  The
    fallback storm itself is synthesized on the counter the guard
    watches; tools/chaos_smoke.py --serving drives the real
    armed-kernel-failure path end to end."""
    base = dict(dispatch.fallback_counts())
    try:
        guard = EngineGuard(ServeGuardConfig(max_kernel_fallbacks=2))
        eng = _twin(world, guard=guard)
        # the storm lands AFTER attach (which snapshots the baseline), as
        # real trace-time fallbacks would
        dispatch._fallback_counts["fused->unfused"] = (
            dispatch._fallback_counts.get("fused->unfused", 0) + 3)
        out = eng.run(list(world["reqs"]))
        assert guard.event_counts().get("qdecode_block_dropped", 0) == 1
        assert "qdecode_block" in dispatch.disabled_ops()
        for rid, ref in world["refs"].items():
            np.testing.assert_array_equal(
                out[rid], ref,
                err_msg=f"stream {rid}: qdecode_block drop changed tokens")
    finally:
        dispatch.enable_ops()
        dispatch._fallback_counts.clear()
        dispatch._fallback_counts.update(base)


def test_disabled_op_plans_as_jnp_mirror():
    """disable_op converts would-be-FUSED plans into JNP decisions tagged
    OP_DISABLED — the chain call sites keep running the chain's bit-exact
    mirror instead of falling back to per-op numerics."""
    from repro.core.bfp import PER_TENSOR, QuantConfig
    qc = QuantConfig(8, PER_TENSOR, True, "threefry")
    dispatch.disable_op("qdecode_block")
    try:
        assert dispatch.disabled_ops() == {"qdecode_block"}
        dec = dispatch.plan_decode_block(
            "qdecode_block", 1, 32, 64, 12, 2, 2, 16, qc,
            kernel_mode="fused")
        assert dec.path == dispatch.JNP
        assert dec.reason == dispatch.OP_DISABLED
    finally:
        dispatch.enable_ops()
    dec = dispatch.plan_decode_block(
        "qdecode_block", 1, 32, 64, 12, 2, 2, 16, qc, kernel_mode="fused")
    assert dec.reason != dispatch.OP_DISABLED


def test_priority_aging_boosts_evicted_lanes(world):
    """Each eviction moves a lane's effective arrival earlier, so a
    repeatedly preempted stream eventually outranks fresh arrivals."""
    guard = EngineGuard(ServeGuardConfig(age_boost_steps=4))
    young = _Running(Request(rid=9, prompt=np.zeros(4, np.int32), gen=2,
                             arrival_step=10))
    old = _Running(Request(rid=1, prompt=np.zeros(4, np.int32), gen=2,
                           arrival_step=4))
    assert guard.priority(young) > guard.priority(old)
    young.n_evictions = 2               # boosted to effective step 2
    assert guard.priority(young) < guard.priority(old)


def test_thrash_shrinks_eff_max_batch_bitwise(world):
    """A pool far too small for the load preempts constantly; the guard
    halves the batch ceiling (cost, not correctness: tokens still match)
    and backpressures fresh admissions during the cooldown."""
    guard = EngineGuard(ServeGuardConfig(thrash_preemptions=2,
                                         thrash_window_steps=8))
    eng = _twin(world, guard=guard, n_pages=4)
    out = eng.run(list(world["reqs"]))
    assert eng.n_preemptions > 0
    counts = guard.event_counts()
    assert counts.get("max_batch_shrunk", 0) >= 1
    assert eng.eff_max_batch < 4
    for rid, ref in world["refs"].items():
        np.testing.assert_array_equal(
            out[rid], ref,
            err_msg=f"stream {rid}: batch shrink changed tokens")


def test_guard_attach_is_exclusive(world):
    guard = EngineGuard()
    _twin(world, guard=guard)
    with pytest.raises(ValueError, match="already attached"):
        _twin(world, guard=guard)
