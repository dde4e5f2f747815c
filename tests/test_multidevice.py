"""Multi-device tests (8 fake CPU devices via subprocess: XLA_FLAGS must be
set before jax initializes, so these run as child processes).  The
children are pinned to the CPU platform: on a machine with an accelerator
they must never reach for a device the parent may hold."""

import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(code: str) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=_ENV, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


def test_quantized_psum_matches_float_psum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, Mesh, PartitionSpec as P
        from jax import shard_map
        from functools import partial
        from repro.runtime.compression import quantized_psum, psum16

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = jnp.asarray(np.random.RandomState(0).randn(8, 16, 32).astype(np.float32))

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P()),
                 out_specs=P("data"), check_vma=False)
        def f8(x, key):
            return quantized_psum(x[0], "data", key)[None]

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P()),
                 out_specs=P("data"), check_vma=False)
        def f16(x, key):
            return psum16(x[0], "data", key)[None]

        ref = x.sum(axis=0)
        y8 = f8(x, jax.random.key(0))[0]
        y16 = f16(x, jax.random.key(1))[0]
        scale = float(jnp.abs(ref).max()) + 1e-6
        e8 = float(jnp.abs(y8 - ref).max()) / scale
        e16 = float(jnp.abs(y16 - ref).max()) / scale
        assert e8 < 0.15, e8      # int8 with 3 guard bits: ~2^-4 grade
        assert e16 < 0.002, e16   # int16: ~2^-12 grade
        print("OK", e8, e16)
    """)
    assert "OK" in out


def test_quantized_psum_unbiased():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, Mesh, PartitionSpec as P
        from jax import shard_map
        from functools import partial
        from repro.runtime.compression import quantized_psum

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = jnp.asarray(np.random.RandomState(1).randn(8, 8, 8).astype(np.float32))

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P()),
                 out_specs=P("data"), check_vma=False)
        def f(x, key):
            return quantized_psum(x[0], "data", key)[None]

        ref = np.asarray(x.sum(axis=0), np.float64)
        n = 256

        @jax.jit
        def total(x):
            def body(i, acc):
                return acc + f(x, jax.random.key(i))[0]
            return jax.lax.fori_loop(0, n, body, jnp.zeros_like(x[0]))

        mean = np.asarray(total(x), np.float64) / n
        ulp = np.abs(ref).max() / 16   # int8 minus 3 guard bits
        assert np.abs(mean - ref).max() < 6 * ulp / np.sqrt(n) + 1e-3
        print("OK")
    """)
    assert "OK" in out


def test_model_loss_under_pjit_dp_tp():
    """Smoke config trains one step under a (2 data x 4 model) mesh with the
    production sharding rules: proves the integer pipeline is shardable."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import get_model
        from repro.core import PAPER_INT8
        from repro.runtime.sharding import DEFAULT_RULES, spec_tree, use_rules

        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(2, 4)
        cfg = get_smoke_config("qwen2_0_5b")
        mod = get_model(cfg)
        key = jax.random.key(0)
        params = mod.init_params(key, cfg)
        pspecs = spec_tree(DEFAULT_RULES, mod.param_specs(cfg))
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspecs)
        toks = jax.random.randint(key, (8, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        bspec = NamedSharding(mesh, P("data"))
        batch = jax.tree_util.tree_map(lambda a: jax.device_put(a, bspec), batch)

        with use_rules(DEFAULT_RULES, mesh):
            @jax.jit
            def step(params, batch, key):
                loss, grads = jax.value_and_grad(
                    lambda p: mod.loss_fn(p, batch, key, PAPER_INT8, cfg))(params)
                return loss, grads

            loss, grads = step(params, batch, jax.random.fold_in(key, 1))
        assert np.isfinite(float(loss))
        for g in jax.tree_util.tree_leaves(grads):
            assert np.isfinite(np.asarray(g)).all()
        print("OK", float(loss))
    """)
    assert "OK" in out


def test_intsgd_state_checkpoint_reshard(tmp_path):
    """IntSGDState (BFP int16 mantissas + scalar-exponent leaves) through
    save -> async wait() -> restore onto a *different* mesh's sharding
    template: dtype, structure, cfg and values must survive exactly."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_smoke_config
        from repro.core import PAPER_INT8, BFP, integer_sgd_init
        from repro.launch.steps import state_shardings, train_state_template
        from repro.models import get_model
        from repro.runtime.sharding import DEFAULT_RULES

        cfg = get_smoke_config("qwen2_0_5b")
        mod = get_model(cfg)
        state = integer_sgd_init(mod.init_params(jax.random.key(0), cfg),
                                 PAPER_INT8, key=jax.random.key(0))
        m1 = jax.make_mesh((4, 2), ("data", "model"))
        sh1 = state_shardings(cfg, PAPER_INT8, m1, DEFAULT_RULES)
        state = jax.tree_util.tree_map(jax.device_put, state, sh1)

        mgr = CheckpointManager({str(tmp_path)!r}, async_write=True)
        mgr.save(7, state)
        mgr.wait()                                 # ready-fence

        m2 = jax.make_mesh((2, 4), ("data", "model"))
        sh2 = state_shardings(cfg, PAPER_INT8, m2, DEFAULT_RULES)
        tmpl = train_state_template(cfg, PAPER_INT8)
        step, restored = mgr.restore_latest(tmpl, shardings=sh2)
        assert step == 7
        for l1, l2 in zip(jax.tree_util.tree_leaves(state),
                          jax.tree_util.tree_leaves(restored)):
            assert l1.dtype == l2.dtype, (l1.dtype, l2.dtype)
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        mast = jax.tree_util.tree_leaves(
            restored.masters, is_leaf=lambda x: isinstance(x, BFP))
        assert all(isinstance(b, BFP) and b.cfg.bits == 16 and
                   b.m.dtype == jnp.int16 for b in mast)
        assert mast[0].m.sharding.mesh.shape["model"] == 4   # on the NEW mesh
        print("OK")
    """)
    assert "OK" in out


def test_intsgd_checkpoint_rejects_wrong_master_width(tmp_path):
    """The dtype guard: an int8-masters checkpoint must not silently restore
    into an int16 template (same shapes, different width)."""
    out = _run(f"""
        import jax, numpy as np
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_smoke_config
        from repro.core import NumericPolicy, integer_sgd_init
        from repro.launch.steps import train_state_template
        from repro.models import get_model

        cfg = get_smoke_config("qwen2_0_5b")
        mod = get_model(cfg)
        pol8 = NumericPolicy(master_bits=8)
        state = integer_sgd_init(mod.init_params(jax.random.key(0), cfg),
                                 pol8, key=jax.random.key(0))
        mgr = CheckpointManager({str(tmp_path)!r}, async_write=False)
        mgr.save(1, state)
        tmpl = train_state_template(cfg, NumericPolicy())   # int16 masters
        try:
            mgr.restore(1, tmpl)
        except ValueError as e:
            assert "dtype" in str(e), e
            print("OK")
        else:
            print("FAIL: restored across master widths")
    """)
    assert "OK" in out


def test_checkpoint_reshard_across_meshes(tmp_path):
    """Save on a (4,2) mesh, restore onto (2,4): elastic re-mesh path."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager

        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        m1 = jax.make_mesh((4, 2), ("data", "model"))
        t1 = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(m1, P("data", "model"))), tree)
        mgr = CheckpointManager({str(tmp_path)!r}, async_write=False)
        mgr.save(1, t1)

        m2 = jax.make_mesh((2, 4), ("data", "model"))
        shard = {{"w": NamedSharding(m2, P("data", "model"))}}
        out = mgr.restore(1, tree, shardings=shard)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
        assert out["w"].sharding.mesh.shape["model"] == 4
        print("OK")
    """)
    assert "OK" in out
