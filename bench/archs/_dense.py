"""The dense SiLU-gated decoders (qwen2, MiniCPM), in the program's layout.

The weights are made whole, in float32, in one jitted call from the seed,
and the serving loader quantizes that tree in the same call.  The
reference is ``bench/reference/dense.py``, on weights made again from the
same seed.  ``qwen2.py`` and ``minicpm.py`` name this module.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.model import dims
from bench.reference.dense import leaf_norms, logits, loss, sgd_readings

__all__ = ["arch_config", "serving_loader", "reference", "init_weights",
           "loss", "sgd_readings", "leaf_norms", "logits"]

# published (Hugging Face config.json) key -> ArchConfig field
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab",
         "rope_theta": "rope_theta", "attention_bias": "qkv_bias",
         "tie_word_embeddings": "tie_embeddings"}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.common import ArchConfig
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only the dense SiLU-gated "
                         f"decoder is wired to the program")
    if not conf["tie_word_embeddings"]:
        raise ValueError(f"{conf['name']}: an untied head is not wired")
    kw = {field: conf[key] for key, field in _KEYS.items()}
    return ArchConfig(name=conf["name"], family="dense", norm="rmsnorm",
                      act="silu", **kw)


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


def serving_loader(conf: dict, cfg, policy):
    """The whole float32 tree made and quantized in one jitted call."""
    from repro.launch.steps import quantize_serving_params
    return jax.jit(lambda k: quantize_serving_params(
        init_weights(k, conf), cfg, policy, jax.random.fold_in(k, 0x9E)))


def reference(key: jax.Array, conf: dict):
    """The reference's logits of one sequence, its float32 tree made once
    from the key."""
    params = jax.jit(lambda k: init_weights(k, conf))(key)
    fwd = jax.jit(lambda p, t: logits(p, t, conf)[0])
    return lambda tokens: fwd(params, jnp.asarray(tokens)[None])
