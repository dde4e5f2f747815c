"""What every architecture shares: the key from a seed, the dense sizes of
a configuration file, and the names of a weight tree's leaves.

Each architecture's ``ArchConfig``, weights and reference are in
``bench/archs/<model_type>.py``, found by ``spec.arch``.
"""

from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size: the low 31 bits seed it, the rest
    is folded in."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF)
    hi = seed >> 31
    while hi:
        key = jax.random.fold_in(key, hi & 0x7FFFFFFF)
        hi >>= 31
    return key


def dims(conf: dict) -> dict:
    """The dense decoder's sizes of a configuration file."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hd = conf.get("head_dim") or d // h
    return dict(L=conf["num_hidden_layers"], d=d, h=h,
                kv=conf["num_key_value_heads"], hd=hd,
                ff=conf["intermediate_size"], V=conf["vocab_size"])


def leaf_names(tree) -> list:
    """'layers/wq'-style names of a weight tree's leaves, in flatten
    order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]
