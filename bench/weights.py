"""Weights made one layer at a time, for models whose float32 does not
fit one chip.

``stacked`` makes a tree whose leaves are stacked over the layers inside
``lax.map``: layer i's float32 comes from ``fold_in(key, i)``, and the
serving loader quantizes it in the same body (``quantizer``), so only one
layer's float32 is live at a time.  ``scan`` is its twin for the
reference: layer i's float32 weights are made again from the same key
inside the body of the reference's layer loop, so the reference fits one
chip at any depth.  Both sides hold the same weights, and neither reads
the other's arrays.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

# folded into a layer's key for its ``finish`` (the quantizer's rounding)
FINISH = 0x51


def stacked(key: jax.Array, n: int, make: Callable[[jax.Array], Any],
            finish: Callable[[Any, jax.Array], Any] = None):
    """``make(fold_in(key, i))`` for i < n, stacked along a leading axis,
    one layer at a time; ``finish(tree, fold_in(layer key, FINISH))``,
    where given, runs on each layer in the same body."""
    def body(i):
        k = jax.random.fold_in(key, i)
        tree = make(k)
        if finish is None:
            return tree
        return finish(tree, jax.random.fold_in(k, FINISH))

    return jax.lax.map(body, jnp.arange(n))


def scan(key: jax.Array, n: int, make: Callable[[jax.Array], Any],
         layer: Callable[[Any, Any], Any], x):
    """``x`` through n layers, ``x = layer(x, make(fold_in(key, i)))``:
    the twin of ``stacked``, each layer's weights made inside the loop's
    body."""
    def body(x, i):
        return layer(x, make(jax.random.fold_in(key, i))), None

    return jax.lax.scan(body, x, jnp.arange(n))[0]


def quantizer(mask, policy) -> Callable[[Any, jax.Array], Any]:
    """``finish`` for ``stacked``: one layer's tree quantized as
    ``launch/steps.quantize_serving_params`` quantizes the slice of a
    stacked leaf (one scale a layer), by the program's own
    ``quantize_weights_once``.  ``mask`` is the stacked leaves' part of
    the program's weight mask (``models.registry.get_weight_mask``)."""
    from repro.core.integer_sgd import quantize_weights_once
    from repro.core.policy import QW_STACKED, QW_STACKED2, QW_TENSOR
    one_layer = {QW_STACKED: QW_TENSOR, QW_STACKED2: QW_STACKED}

    def per_layer(m):
        if m == QW_TENSOR:
            raise ValueError("a stacked leaf with one scale over all its "
                             "layers cannot be made a layer at a time")
        return one_layer.get(m, m)

    layer_mask = jax.tree_util.tree_map(per_layer, mask)
    return lambda tree, key: quantize_weights_once(tree, policy, key,
                                                   layer_mask)
