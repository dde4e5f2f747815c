"""A configuration file as the program's ``ArchConfig``, and the weights.

The weights are the benchmark's own: made on the device from the seed in
one jitted call, in the layout of the program's dense decoder, and made
again by the reference from the same seed.  Neither side reads the
other's arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# published (Hugging Face config.json) key -> ArchConfig field
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab",
         "rope_theta": "rope_theta", "attention_bias": "qkv_bias",
         "tie_word_embeddings": "tie_embeddings"}
DECODERS = ("qwen2", "minicpm")


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.common import ArchConfig
    if conf["model_type"] not in DECODERS or conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only the dense SiLU-gated "
                         f"decoders {DECODERS} are wired to the program")
    if not conf["tie_word_embeddings"]:
        raise ValueError(f"{conf['name']}: an untied head is not wired")
    kw = {field: conf[key] for key, field in _KEYS.items()}
    return ArchConfig(name=conf["name"], family="dense", norm="rmsnorm",
                      act="silu", **kw)


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
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hd = conf.get("head_dim") or d // h
    return dict(L=conf["num_hidden_layers"], d=d, h=h,
                kv=conf["num_key_value_heads"], hd=hd,
                ff=conf["intermediate_size"], V=conf["vocab_size"])


def _normal(key, shape, sigma):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * sigma


def init_weights(key: jax.Array, conf: dict) -> dict:
    """float32 weights: fan-in truncated normals, embedding sigma 0.02,
    projection biases sigma 0.02, norm gains 1 (stacked over layers)."""
    g = dims(conf)
    L, d, hq, hkv, ff = g["L"], g["d"], g["h"] * g["hd"], g["kv"] * g["hd"], g["ff"]
    ks = iter(jax.random.split(key, 12))
    lay = {
        "ln1_g": jnp.ones((L, d), jnp.float32),
        "ln2_g": jnp.ones((L, d), jnp.float32),
        "wq": _normal(next(ks), (L, d, hq), 1 / math.sqrt(d)),
        "wk": _normal(next(ks), (L, d, hkv), 1 / math.sqrt(d)),
        "wv": _normal(next(ks), (L, d, hkv), 1 / math.sqrt(d)),
        "wo": _normal(next(ks), (L, hq, d), 1 / math.sqrt(hq)),
        "w_gate": _normal(next(ks), (L, d, ff), 1 / math.sqrt(d)),
        "w_up": _normal(next(ks), (L, d, ff), 1 / math.sqrt(d)),
        "w_down": _normal(next(ks), (L, ff, d), 1 / math.sqrt(ff)),
    }
    if conf["attention_bias"]:
        lay["bq"] = _normal(next(ks), (L, hq), 0.02)
        lay["bk"] = _normal(next(ks), (L, hkv), 0.02)
        lay["bv"] = _normal(next(ks), (L, hkv), 0.02)
    return {"layers": lay,
            "embed": _normal(next(ks), (g["V"], d), 0.02),
            "fn_g": jnp.ones((d,), jnp.float32)}


def leaf_names(tree) -> list:
    """'layers/wq'-style names of a weight tree's leaves, in flatten
    order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]
