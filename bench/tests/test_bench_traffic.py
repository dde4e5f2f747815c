"""The traffic generators: seeded, the same work for every seed, lengths
in their buckets and ranges."""

import json
from statistics import NormalDist

import numpy as np
import pytest

from bench import spec, traffic

MIXES = ["serve.code", "serve.chat-sat"]


def _mix(name, rate=3.0):
    with open(spec.BENCH / "traffic" / f"{name}.json") as f:
        return dict(json.load(f), rate_per_s=rate)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(_mix(name), 1000, 2**33 + 5, 30)
    b = traffic.schedule(_mix(name), 1000, 2**33 + 5, 30)
    assert [(x.rid, x.due_s, x.gen) for x in a] == \
        [(x.rid, x.due_s, x.gen) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_same_work(name):
    a = traffic.schedule(_mix(name), 1000, 11, 30)
    b = traffic.schedule(_mix(name), 1000, 12, 30)
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.gen for x in a) == sorted(x.gen for x in b)
    # the same gaps in another order: the last request is due at the same
    # time, and the gaps between requests come from one set
    assert a[-1].due_s == pytest.approx(b[-1].due_s)
    ga, gb = np.diff([x.due_s for x in a]), np.diff([x.due_s for x in b])
    assert len(set(np.round(ga, 9)) - set(np.round(gb, 9))) <= 1


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_buckets_and_ranges(name):
    mix = _mix(name)
    s = traffic.schedule(mix, 1000, 7, 40)
    assert len(s) == round(mix["rate_per_s"] * 40)
    assert all(0 <= x.due_s < 40 for x in s)
    assert all(np.diff([x.due_s for x in s]) > 0)
    assert {len(x.prompt) for x in s} <= set(mix["prompt_buckets"])
    assert all(mix["gen_min"] <= x.gen <= mix["gen_max"] for x in s)
    assert all(len(x.prompt) + x.gen <= mix["max_len"] for x in s)
    assert all(x.prompt.dtype == np.int32 and x.prompt.max() < 1000
               for x in s)
    # each bucket holds the log-normal's share between the log midpoints
    # to its neighbours; the outputs' median is the mix's
    b = np.asarray(mix["prompt_buckets"], np.float64)
    counts = np.array([sum(len(x.prompt) == v for x in s) for v in b])
    cdf = [NormalDist().cdf(np.log(e / mix["prompt_median"])
                            / mix["prompt_sigma"])
           for e in np.sqrt(b[:-1] * b[1:])]
    want = np.diff([0.0, *cdf, 1.0]) * len(s)
    assert np.all(np.abs(counts - want) <= 1.0)
    assert np.median([x.gen for x in s]) == pytest.approx(mix["gen_median"],
                                                          abs=1)


def test_synthetic_lm_is_the_programs():
    """The copy draws the batches the program's own generator draws."""
    from repro.data.pipeline import SyntheticLM as Program
    for seed in (0, 3, 2**31 + 17):
        mine = traffic.SyntheticLM(vocab=151936, seq_len=64, batch=4,
                                   seed=seed)
        theirs = Program(vocab=151936, seq_len=64, global_batch=4, seed=seed)
        for step in (0, 1, 9):
            a, b = mine.batch_for_step(step), theirs.batch_for_step(step)
            assert all(np.array_equal(a[k], b[k]) for k in ("tokens",
                                                            "labels"))


def test_synthetic_lm_rows_differ():
    b = traffic.SyntheticLM(vocab=122753, seq_len=512, batch=4,
                            seed=9).batch_for_step(0)
    assert len({r.tobytes() for r in b["tokens"]}) == 4
