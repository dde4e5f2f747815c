"""Plain float32 reference of the dense decoders (qwen2, MiniCPM).

Written from the published description, in ``jax.numpy`` and float32,
with every matrix product at ``highest`` precision; it imports nothing of
the program.  A layer is

    x = x + o(attn(rope(q(n1(x))), rope(k(n1(x))), v(n1(x))))
    x = x + down(silu(gate(n2(x))) * up(n2(x)))

with RMSNorm n (eps from the configuration), RoPE on the two halves of
each head (theta from the configuration), grouped-query causal softmax
attention (query head j reads key/value head j // (heads / kv_heads)),
qwen2's biases on q, k and v, and the head tied to the embedding.  The
loss is the mean next-token cross-entropy.

Departure, written down in the MiniCPM configuration's file: MiniCPM's
muP scalars (``scale_emb``, ``scale_depth``, ``dim_model_base``) are
left out, as the program leaves them out.

Sizes are run in blocks so that the timed sizes fit one chip: each layer
is rematerialized in the backward pass, and the head and the loss run one
sequence at a time.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, positions, theta):
    """x (..., S, D): rotate the pairs (x[i], x[i + D/2])."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_positions, k_positions):
    """q (B, Hq, S, D), k v (B, Hkv, T, D), causal by position."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    q = q.reshape(b, hkv, hq // hkv, s, d)
    sc = jnp.einsum("bkgsd,bktd->bkgst", q, k, precision=HIGHEST)
    sc = sc / math.sqrt(d)
    mask = k_positions[None, :] <= q_positions[:, None]
    sc = jnp.where(mask, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, v, precision=HIGHEST)
    return o.reshape(b, hq, s, d)


def _split_heads(x, n):
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)


def layer(x, lp, conf, positions):
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    n = rmsnorm(x, lp["ln1_g"], eps)
    q, k, v = _mm(n, lp["wq"]), _mm(n, lp["wk"]), _mm(n, lp["wv"])
    if conf["attention_bias"]:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = rope(_split_heads(q, h), positions, theta)
    k = rope(_split_heads(k, kv), positions, theta)
    v = _split_heads(v, kv)
    o = attention(q, k, v, positions, positions)
    b, _, s, _ = o.shape
    x = x + _mm(o.transpose(0, 2, 1, 3).reshape(b, s, -1), lp["wo"])
    n = rmsnorm(x, lp["ln2_g"], eps)
    return x + _mm(jax.nn.silu(_mm(n, lp["w_gate"])) * _mm(n, lp["w_up"]),
                   lp["w_down"])


def hidden(params, tokens, conf):
    """Final-normed hidden states (B, S, d)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: layer(x, lp, conf, positions))(
            x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["fn_g"], conf["rms_norm_eps"])


def logits(params, tokens, conf):
    """(B, S, V) next-token logits of the tied head."""
    return _mm(hidden(params, tokens, conf), params["embed"].T)


def loss(params, batch, conf):
    """Mean next-token cross-entropy over every row and position; the head
    and the loss run one sequence at a time."""
    h = hidden(params, batch["tokens"], conf)

    def one(total, xs):
        hs, lab = xs
        lg = _mm(hs, params["embed"].T)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(one), jnp.float32(0),
                            (h, batch["labels"]))
    return total / batch["labels"].size


def leaf_norms(tree) -> jnp.ndarray:
    """float32 norm of each leaf, in flatten order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def sgd_readings(init, key, batches: List[Dict], conf: dict, lr: float,
                 momentum: float) -> Tuple[List[float], jnp.ndarray, jnp.ndarray]:
    """Follow len(batches) steps of SGD with momentum (v = mu v + g,
    w = w - lr v) from the weights ``init(key)`` makes: each step's loss,
    the per-leaf norms of the first gradient, and those of the weights'
    change over all the steps (the start is made again from the key, so
    that no third copy of the weights is held)."""
    tmap = jax.tree_util.tree_map
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(lambda p, b: loss(p, b, conf)))

        @partial(jax.jit, donate_argnums=(0, 1))
        def update(p, v, g):
            v = tmap(lambda v, g: momentum * v + g, v, g)
            return tmap(lambda p, v: p - lr * v, p, v), v

        @jax.jit
        def change(p, key):
            return leaf_norms(tmap(jnp.subtract, p, init(key)))

        params = jax.jit(init)(key)
        v = None
        losses, g0 = [], None
        for b in batches:
            lval, g = vg(params, {k: jnp.asarray(x) for k, x in b.items()})
            losses.append(float(lval))
            if g0 is None:
                g0 = leaf_norms(g)
                v = tmap(jnp.zeros_like, g)
            params, v = update(params, v, g)
            del g
        del v
        return losses, g0, change(params, key)
