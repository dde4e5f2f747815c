"""Substrate tests: data pipeline, checkpoint manager, fault tolerance, optim."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM, UniformLM
from repro.optim import (adamw_init, adamw_step, cosine_schedule, sgd_init,
                         sgd_step, step_decay, wsd_schedule)
from repro.runtime.fault_tolerance import (Heartbeat, StragglerMonitor,
                                           plan_elastic_mesh)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    ds = SyntheticLM(vocab=512, seq_len=16, global_batch=8, seed=3)
    b1 = ds.batch_for_step(42)
    b2 = ds.batch_for_step(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = ds.batch_for_step(43)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_host_sharding_disjoint():
    kw = dict(vocab=512, seq_len=8, global_batch=8, seed=1, n_hosts=2)
    h0 = SyntheticLM(host=0, **kw).batch_for_step(7)
    h1 = SyntheticLM(host=1, **kw).batch_for_step(7)
    assert h0["tokens"].shape == (4, 8)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_pipeline_labels_shifted():
    ds = SyntheticLM(vocab=512, seq_len=16, global_batch=2, seed=0)
    b = ds.batch_for_step(0)
    # labels are next tokens of the same walk: verify the affine relation
    pred = (ds.a * b["tokens"][:, 0].astype(np.int64) + ds.b) % ds.vocab
    assert np.all((b["labels"][:, 0] - pred) % ds.vocab < ds.noise)


def test_pipeline_has_learnable_structure():
    ds = SyntheticLM(vocab=128, seq_len=64, global_batch=4, seed=2)
    b = ds.batch_for_step(0)
    # entropy of (label | token) is ~log2(noise), far below log2(vocab)
    residual = (b["labels"].astype(np.int64)
                - (ds.a * b["tokens"].astype(np.int64) + ds.b)) % ds.vocab
    assert residual.max() < ds.noise


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(8, 8).astype(np.float32)),
            "inner": {"b": jnp.asarray(rng.randn(4).astype(np.float32)),
                      "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    tree = _tree()
    mgr.save(10, tree)
    out = mgr.restore(10, jax.tree_util.tree_map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_fence_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(1, _tree(1))
    mgr.save(5, _tree(2))
    mgr.wait()
    assert mgr.latest_step() == 5
    step, out = mgr.restore_latest(jax.tree_util.tree_map(jnp.zeros_like, _tree()))
    assert step == 5


def test_checkpoint_gc_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_integrity_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(3, _tree())
    path = os.path.join(str(tmp_path), "step_3", "leaf_0.npy")
    a = np.load(path)
    a[0] += 1
    np.save(path, a)
    with pytest.raises(IOError):
        mgr.restore(3, jax.tree_util.tree_map(jnp.zeros_like, _tree()))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree())
    bad = _tree()
    bad["w"] = jnp.zeros((4, 4))
    with pytest.raises(ValueError):
        mgr.restore(1, bad)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_timeout():
    t = [0.0]
    hb = Heartbeat([0, 1, 2], timeout_s=10, clock=lambda: t[0])
    t[0] = 5.0
    hb.beat(0)
    hb.beat(1)
    t[0] = 12.0
    assert hb.dead() == {2}
    assert hb.alive() == {0, 1}


def test_straggler_detection_and_mitigation():
    mon = StragglerMonitor([0, 1, 2, 3], warmup_steps=3)
    for _ in range(5):
        for h in (0, 1, 2):
            mon.record(h, 1.0)
        mon.record(3, 2.5)
    assert mon.stragglers() == {3}
    plan = mon.mitigation(spares={9})
    assert plan == {3: 9}
    assert mon.mitigation(spares=set()) == {3: None}


def test_straggler_warmup_suppresses_flags():
    mon = StragglerMonitor([0, 1], warmup_steps=10)
    for _ in range(3):
        mon.record(0, 1.0)
        mon.record(1, 9.0)
    assert mon.stragglers() == set()


def test_elastic_mesh_shrinks_data_axis():
    plan = plan_elastic_mesh(240, model_parallel=16, restore_step=100,
                             dropped_hosts=(7,))
    assert plan.mesh_shape == (15, 16)
    assert plan.restore_step == 100
    with pytest.raises(ValueError):
        plan_elastic_mesh(8, model_parallel=16)


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------

def test_sgd_matches_reference():
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 0.5)}
    st = sgd_init(p)
    st, p2 = sgd_step(st, p, g, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(np.asarray(p2["w"]), 1 - 0.1 * 0.5)
    st, p3 = sgd_step(st, p2, g, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(np.asarray(p3["w"]),
                               np.asarray(p2["w"]) - 0.1 * (0.9 * 0.5 + 0.5))


def test_adamw_descends_quadratic():
    p = {"w": jnp.full((4,), 5.0)}
    st = adamw_init(p)
    for _ in range(200):
        g = jax.tree_util.tree_map(lambda w: 2 * w, p)
        st, p = adamw_step(st, p, g, lr=0.05)
    assert float(jnp.abs(p["w"]).max()) < 1.0


def test_schedules_shapes_and_endpoints():
    s = jnp.int32(0)
    assert float(cosine_schedule(s, 1.0, 100)) == pytest.approx(1.0)
    assert float(cosine_schedule(jnp.int32(100), 1.0, 100)) == pytest.approx(0.0)
    assert float(step_decay(jnp.int32(59), 0.1, 30)) == pytest.approx(0.01)
    assert float(step_decay(jnp.int32(65), 0.1, 30)) == pytest.approx(0.001)
    w = wsd_schedule(jnp.int32(5), 1.0, warmup_steps=10, stable_steps=100,
                     decay_steps=50)
    assert float(w) == pytest.approx(0.5)
    mid = wsd_schedule(jnp.int32(60), 1.0, 10, 100, 50)
    assert float(mid) == pytest.approx(1.0)


def test_mesh_shape_parsing_and_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_local_mesh, parse_mesh_shape
    assert parse_mesh_shape("2,2") == (2, 2)
    assert parse_mesh_shape("1,1") == (1, 1)
    for bad in ("2", "0,2", "2,2,1", "a,b"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
