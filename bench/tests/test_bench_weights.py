"""``bench/weights``: stacked layers made and quantized one at a time, and
the reference's twin, on the CPU at a small size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serve_cell, weights

D, F = 64, 96
LAYER_F32 = 4 * (D + 2 * D * F)


def _make(key):
    ka, kb = jax.random.split(key)
    return {"g": jnp.ones((D,), jnp.float32),
            "a": jax.random.truncated_normal(ka, -2.0, 2.0, (D, F)) / 8.0,
            "b": jax.random.truncated_normal(kb, -2.0, 2.0, (F, D)) / 8.0}


def _finish():
    from repro.core.policy import QW_NONE, QW_STACKED
    return weights.quantizer({"g": QW_NONE, "a": QW_STACKED,
                              "b": QW_STACKED}, serve_cell.serving_policy())


def _temp(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().memory_analysis() \
        .temp_size_in_bytes


def _equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def test_stacked_equals_its_layers_stacked_in_python():
    key, finish = jax.random.key(7), _finish()
    got = jax.jit(lambda k: weights.stacked(k, 4, _make, finish))(key)
    layers = []
    for i in range(4):
        k = jax.random.fold_in(key, i)
        layers.append(finish(_make(k), jax.random.fold_in(k, weights.FINISH)))
    _equal(got, jax.tree_util.tree_map(lambda *xs: np.stack(xs), *layers))
    assert got["a"].m.dtype == jnp.int8 and got["a"].e.shape == (4,)


def test_scan_equals_a_python_loop_over_the_same_layers():
    def layer(x, w):
        return jnp.tanh(x @ w["a"] @ w["b"]) * w["g"]

    key, x = jax.random.key(9), jnp.ones((3, D), jnp.float32)
    got = jax.jit(lambda k, x: weights.scan(k, 4, _make, layer, x))(key, x)
    want = x
    for i in range(4):
        want = layer(want, _make(jax.random.fold_in(key, i)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_memory_holds_one_layer_at_any_depth():
    """The streamed loader's and the reference twin's working memory is
    the same at 4 layers and at 8, while the paths that make every layer
    first hold at least every layer's float32.  (On the CPU a layer's
    random bits and the quantizer's intermediates are materialized, some
    ten times the layer's float32, so the working memory is not under two
    layers' float32 here; the v5e compiler fuses them away.)"""
    from repro.core.integer_sgd import quantize_weights_once
    from repro.core.policy import QW_NONE, QW_STACKED
    key, x, finish = jax.random.key(7), jnp.ones((2, D)), _finish()
    mask = {"g": QW_NONE, "a": QW_STACKED, "b": QW_STACKED}
    pol = serve_cell.serving_policy()

    def every(k, n):
        return jax.vmap(lambda i: _make(jax.random.fold_in(k, i)))(
            jnp.arange(n))

    def forward(x, w):
        return x @ w["a"] @ w["b"]

    def loop(x, ws, n):
        for i in range(n):
            x = forward(x, jax.tree_util.tree_map(lambda a: a[i], ws))
        return x

    for n in (4, 8):
        assert _temp(lambda k: weights.stacked(k, n, _make, finish),
                     key) == _temp(lambda k: weights.stacked(
                         k, 4, _make, finish), key)
        assert _temp(lambda k: quantize_weights_once(
            every(k, n), pol, k, mask), key) >= n * LAYER_F32
        assert _temp(lambda k, x: weights.scan(k, n, _make, forward, x),
                     key, x) == _temp(lambda k, x: weights.scan(
                         k, 4, _make, forward, x), key, x)
        assert _temp(lambda k, x: loop(x, every(k, n), n),
                     key, x) >= n * LAYER_F32


def test_quantizer_refuses_one_scale_over_all_layers():
    from repro.core.policy import QW_TENSOR
    with pytest.raises(ValueError, match="layer at a time"):
        weights.quantizer({"a": QW_TENSOR}, serve_cell.serving_policy())
