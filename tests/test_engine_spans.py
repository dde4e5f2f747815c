"""Spans and counters of the serving engine (runtime/spans.py).

The recorder is off by default; with it on, every ``Engine.step()`` is
one ``engine.step`` span whose children are its phases, prefill spans
carry the request id, the byte counters equal what the page tables,
tokens and keys give (no cache crosses but on eviction), and the
emitted tokens are bitwise those of a run with it off.  The same spans
land in a ``jax.profiler`` trace.  One module fixture compiles the tiny
engine's programs once; every engine shares them.
"""

import dataclasses
import glob

import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.core.policy import PAPER_INT8
from repro.launch.engine import Engine, EngineConfig, Request
from repro.runtime.spans import Recorder

POLICY = dataclasses.replace(PAPER_INT8, qweights=True, qcache=True)
PROMPT_LEN, GEN, MAX_LEN, PAGE, LANES = 6, 3, 12, 4, 2
PHASES = ["engine.reserve", "engine.gather", "engine.keys", "engine.decode",
          "engine.scatter"]


def _tiny_cfg():
    return dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                               n_layers=2, d_model=32, d_ff=64, n_heads=2,
                               n_kv_heads=2, vocab=97)


def _requests(n, prompt_len=PROMPT_LEN, rid0=0):
    rs = np.random.RandomState(11 + rid0)
    return [Request(rid=rid0 + i,
                    prompt=rs.randint(0, 97, size=prompt_len).astype(np.int32),
                    gen=GEN, arrival_step=i, seed=200 + rid0 + i)
            for i in range(n)]


def _engine(base=None):
    return Engine(_tiny_cfg(), POLICY, EngineConfig(
        max_len=MAX_LEN, page_size=PAGE, n_pages=16, max_batch=LANES),
        params=None if base is None else base.params, share_fns=base)


@pytest.fixture(scope="module")
def runs():
    """The same requests with the recorder off, then on."""
    off = _engine()
    tokens_off = off.run(_requests(3))
    on = _engine(off)
    on.spans.start()
    tokens_on = on.run(_requests(3))
    return {"off": off, "tokens_off": tokens_off, "on": on,
            "tokens_on": tokens_on, "record": on.spans.stop()}


def _children(spans, parent):
    return [s for s in spans if s[3] == parent]


def test_tokens_equal_and_nothing_recorded_off(runs):
    assert runs["tokens_on"].keys() == runs["tokens_off"].keys()
    for rid, toks in runs["tokens_off"].items():
        assert np.array_equal(runs["tokens_on"][rid], toks)
    assert runs["off"].spans.stop() == {"spans": [], "counters": {}}


def test_each_step_is_a_span_tree(runs):
    """Every step is a top-level ``engine.step`` whose children are an
    admission (with its prefill and the write into the pool) where one
    happened, then the decode phases in order, all inside it."""
    spans = runs["record"]["spans"]
    steps = [i for i, s in enumerate(spans) if s[0] == "engine.step"]
    assert len(steps) == runs["on"].clock
    admitted = 0
    for i in steps:
        assert spans[i][3] == -1
        kids = _children(spans, i)
        names = [s[0] for s in kids]
        if names[0] == "engine.admit":
            admitted += 1
            adm = spans.index(kids[0])
            assert [s[0] for s in _children(spans, adm)] == [
                "engine.prefill", "engine.prefill_write"]
            names = names[1:]
        assert names == PHASES
        assert spans[i][1] <= kids[0][1] and kids[-1][2] <= spans[i][2]
        for a, b in zip(kids, kids[1:]):
            assert a[1] <= a[2] <= b[1]
    assert admitted == 3


def test_request_ids(runs):
    spans = runs["record"]["spans"]
    rids = {0, 1, 2}
    for name in ("engine.prefill", "engine.prefill_write", "engine.queued",
                 "engine.admit"):
        assert sorted(s[4]["rid"] for s in spans if s[0] == name) == \
            sorted(rids)
    queued = [s for s in spans if s[0] == "engine.queued"]
    assert all(s[3] == -1 and s[1] <= s[2] for s in queued)
    lanes = [s[4]["lanes"] for s in spans if s[0] == "engine.gather"]
    assert lanes == [1, 2, 2, 1]


def _cache_bytes():
    """One lane's cache at these shapes: K and V, each layers x kv heads
    x max_len rows of head_dim int8 mantissas and one int32 exponent."""
    cfg = _tiny_cfg()
    hd = cfg.d_model // cfg.n_heads
    rows = cfg.n_layers * cfg.n_kv_heads * MAX_LEN
    return 2 * (rows * hd + rows * 4)


def test_byte_counters_from_the_cache_shapes(runs):
    """No cache bytes cross on the decode path.  Every decode call sends
    per lane a token (int32), a position (int32), a raw key (2 x
    uint32), its page table (one int32 page id per block) and, for the
    write-back, a block index and a page id (int32 each), and brings a
    token per lane back.  A prefill's write into the pool sends its page
    list (one id per block), each page allocation its page id, and the
    prefill brings back its first token only.  With no eviction, no page
    crosses."""
    assert _cache_bytes() == 1920
    c = runs["record"]["counters"]
    calls = sum(s[0] == "engine.decode" for s in runs["record"]["spans"])
    blocks = MAX_LEN // PAGE
    # each request decodes GEN - 1 tokens after its prefill's first
    assert c["engine.lanes"] == 3 * (GEN - 1)
    assert c["engine.pad_lanes"] == calls * LANES - c["engine.lanes"]
    assert runs["on"].n_preemptions == 0
    assert c["engine.h2d_bytes"] == \
        calls * LANES * (4 + 4 + 8 + 4 * blocks + 4 + 4) \
        + 3 * 4 * blocks + 4 * runs["on"].pool.page_allocs
    assert c["engine.d2h_bytes"] == calls * LANES * 4
    assert c["prefill.h2d_bytes"] == 3 * PROMPT_LEN * 4
    assert c["prefill.d2h_bytes"] == 3 * 4
    assert c.get("pool.d2h_bytes", 0) == 0
    assert c.get("pool.h2d_bytes", 0) == 0


def test_pool_counters_count_evictions(runs):
    """A pool too small for the requests evicts: each eviction brings
    one lane's cache to the host and its re-admission sends it back,
    both counted under ``pool.*``; the tokens are those of the run with
    room."""
    eng = Engine(_tiny_cfg(), POLICY, EngineConfig(
        max_len=MAX_LEN, page_size=2, n_pages=7, max_batch=LANES),
        params=runs["off"].params, share_fns=runs["off"])
    eng.spans.start()
    out = eng.run(_requests(3))
    c = eng.spans.stop()["counters"]
    assert eng.n_preemptions > 0
    assert c["pool.d2h_bytes"] == eng.n_preemptions * _cache_bytes()
    assert c["pool.h2d_bytes"] == c["pool.d2h_bytes"]
    assert out.keys() == runs["tokens_off"].keys()
    for rid, toks in runs["tokens_off"].items():
        assert np.array_equal(out[rid], toks)


def test_compiles_count_under_the_span_that_compiled(runs):
    """A prompt length the programs have not seen compiles one prefill
    program under ``engine.prefill``; the same length again compiles
    nothing."""
    eng = _engine(runs["off"])
    eng.spans.start()
    eng.run(_requests(1, prompt_len=PROMPT_LEN - 1, rid0=10))
    first = dict(eng.spans.stop()["counters"])
    assert first.get("compiles.engine.prefill") == 1
    eng.spans.start()
    eng.run(_requests(1, prompt_len=PROMPT_LEN - 1, rid0=20))
    again = eng.spans.stop()["counters"]
    assert not any(k.startswith("compiles.") for k in again)


def test_spans_land_in_a_profiler_trace(runs, tmp_path):
    """With the recorder off, the spans still reach the profiler's host
    plane under their bare names, with their ids as stats."""
    from jax.profiler import ProfileData
    eng = _engine(runs["off"])
    with jax.profiler.trace(str(tmp_path)):
        eng.run(_requests(2, rid0=30))
    seen = {}
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    seen.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    assert set(seen) == {"engine.step", "engine.admit", "engine.prefill",
                         "engine.prefill_write", *PHASES}
    assert sorted(s["rid"] for s in seen["engine.prefill"]) == [30, 31]
    assert {s["lanes"] for s in seen["engine.gather"]} == {1, 2}


def test_recorder_by_hand():
    """Parents by index, counters only while on, a span left open by
    ``stop`` ends there, and one opened before ``start`` stays out."""
    rec = Recorder()
    rec.count("x", 3)
    with rec.span("outer"):
        rec.start()
        with rec.span("a", rid=1):
            with rec.span("b"):
                rec.count("x", 2)
        rec.begin("k", "q", rid=5)
        rec.end("k")
        rec.end("never-begun")
    with rec.span("c"):
        got = rec.stop()
    assert [(s[0], s[3], s[4]) for s in got["spans"]] == [
        ("a", -1, {"rid": 1}), ("b", 0, {}), ("q", -1, {"rid": 5}),
        ("c", -1, {})]
    assert all(s[1] <= s[2] for s in got["spans"])
    assert got["counters"] == {"x": 2}
    rec.count("x")
    assert got["counters"] == {"x": 2}
