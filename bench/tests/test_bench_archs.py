"""Architectures found by ``model_type``: the dense decoders through the
lookup read as the composition the serving cell used before it, bit for
bit; and an architecture that only added files bring is found, loaded
and served, and checked against its own reference (CPU, tiny sizes)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, serve_cell, spec, train_cell
from bench.tests.smoke import SMALL_SERVE

SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             intermediate_size=96, vocab_size=509)
KV_HEADS = {"qwen2-0.5b": 2, "minicpm-2b-stage": 4}


def _small_conf(name):
    with open(spec.BENCH / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    return dict(conf, **SMALL, num_key_value_heads=KV_HEADS[name])


def _same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))


@pytest.mark.parametrize("name", sorted(KV_HEADS))
def test_lookup_matches_the_dense_composition(name):
    """The serving parameters and the reference's logits through
    ``spec.arch`` equal ``quantize_serving_params(init_weights(...))``
    and ``dense.logits`` composed as the serving cell composed them."""
    from repro.launch.steps import quantize_serving_params
    from bench.archs._dense import init_weights
    from bench.reference import dense
    conf = _small_conf(name)
    arch = spec.arch(conf["model_type"])
    cfg, policy = arch.arch_config(conf), serve_cell.serving_policy()
    key = model.seed_key(2**33 + 5)
    old = jax.jit(lambda k: quantize_serving_params(
        init_weights(k, conf), cfg, policy, jax.random.fold_in(k, 0x9E)))
    _same(arch.serving_loader(conf, cfg, policy)(key), old(key))
    toks = np.arange(1, 20, dtype=np.int32) * 7 % conf["vocab_size"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_weights(k, conf))(key)
        fwd = jax.jit(lambda p, t: dense.logits(p, t, conf)[0])
        want = fwd(params, jnp.asarray(toks)[None])
        got = arch.reference(key, conf)(toks)
    _same(got, want)


TOY_ARCH = '''"""A toy architecture that added files alone bring: the program's dense
decoder with an untied head, its layers made and quantized one at a time,
and its own plain reference."""

import math

import jax
import jax.numpy as jnp

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6
CALLS = []


def arch_config(conf):
    from repro.models.common import ArchConfig
    return ArchConfig(name=conf["name"], family="dense",
                      n_layers=conf["num_hidden_layers"],
                      d_model=conf["hidden_size"],
                      n_heads=conf["num_attention_heads"],
                      n_kv_heads=conf["num_key_value_heads"],
                      d_ff=conf["intermediate_size"],
                      vocab=conf["vocab_size"], rope_theta=conf["rope_theta"],
                      tie_embeddings=False)


def _normal(key, shape, sigma):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape) * sigma


def _layer(conf):
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    hd = d // conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"] * hd
    shapes = {"wq": (d, d), "wk": (d, hkv), "wv": (d, hkv), "wo": (d, d),
              "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}

    def make(key):
        ks = jax.random.split(key, len(shapes))
        out = {n: _normal(k, s, 1 / math.sqrt(s[0]))
               for k, (n, s) in zip(ks, shapes.items())}
        return dict(out, ln1_g=jnp.ones((d,)), ln2_g=jnp.ones((d,)))

    return make


def _ends(key, conf):
    d, v = conf["hidden_size"], conf["vocab_size"]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1))
    return {"embed": _normal(ke, (v, d), 0.02), "fn_g": jnp.ones((d,)),
            "lm_head": _normal(kh, (d, v), 1 / math.sqrt(d))}


def serving_loader(conf, cfg, policy):
    from repro.core.integer_sgd import quantize_weights_once
    from repro.models.registry import get_weight_mask
    mask = dict(get_weight_mask(cfg))
    finish = weights.quantizer(mask.pop("layers"), policy)

    def load(key):
        params = quantize_weights_once(_ends(key, conf), policy,
                                       jax.random.fold_in(key, 2), mask)
        params["layers"] = weights.stacked(
            jax.random.fold_in(key, 0), conf["num_hidden_layers"],
            _layer(conf), finish)
        return params

    return jax.jit(load)


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, theta):
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    x1, x2 = jnp.split(x, 2, -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, conf):
    s, h = x.shape[0], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], x.shape[1] // h

    def mm(a, b):
        return jnp.matmul(a, b, precision=HIGHEST)

    n = _rms(x, w["ln1_g"])
    q = _rope(mm(n, w["wq"]).reshape(s, h, hd), conf["rope_theta"])
    k = _rope(mm(n, w["wk"]).reshape(s, kv, hd), conf["rope_theta"])
    v = mm(n, w["wv"]).reshape(s, kv, hd)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    sc = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST)
    x = x + mm(o.reshape(s, -1), w["wo"])
    n = _rms(x, w["ln2_g"])
    return x + mm(jax.nn.silu(mm(n, w["w_gate"])) * mm(n, w["w_up"]),
                  w["w_down"])


def reference(key, conf):
    CALLS.append(conf["name"])

    def fwd(key, tokens):
        ends = _ends(key, conf)
        x = weights.scan(jax.random.fold_in(key, 0),
                         conf["num_hidden_layers"], _layer(conf),
                         lambda x, w: _block(x, w, conf),
                         jnp.take(ends["embed"], tokens, axis=0))
        return jnp.matmul(_rms(x, ends["fn_g"]), ends["lm_head"],
                          precision=HIGHEST)

    f = jax.jit(fwd)
    return lambda tokens: f(key, jnp.asarray(tokens))
'''

TOY_CONF = {"name": "toy", "model_type": "toy", "num_hidden_layers": 2,
            "hidden_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 48,
            "vocab_size": 97, "rope_theta": 10000.0}


def _toy_root(tmp_path):
    """A checkout of added files only: an architecture, a configuration
    and a traffic mix."""
    bench = tmp_path / "bench"
    for sub in ("archs", "configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "archs" / "toy.py").write_text(TOY_ARCH)
    (bench / "configs" / "toy.json").write_text(json.dumps(TOY_CONF))
    mix = dict(SMALL_SERVE, kind="serve", rate_per_s=8.0, check_requests=2)
    (bench / "traffic" / "serve.toy.json").write_text(json.dumps(mix))
    return tmp_path


def test_an_architecture_from_added_files_is_served(tmp_path):
    root = _toy_root(tmp_path)
    assert not (spec.BENCH / "archs" / "toy.py").exists()
    cell = spec.cell_from_files("toy", "serve.toy", root=root)
    run = serve_cell.ServeRun(cell, seed=2**32 + 11)
    assert run.arch is spec.arch("toy", root)
    assert not run.cfg.tie_embeddings
    run.setup()
    run.window(0.5)
    run.free()
    got = run.check()["token_gap"]
    assert got["requests"] == 2 and got["tokens"] > 0
    assert run.arch.CALLS == ["toy"]
    assert 0.0 <= got["value"] < 1.0, got


def test_the_training_runner_refuses_an_architecture_without_its_hooks(
        tmp_path):
    root = _toy_root(tmp_path)
    with pytest.raises(NotImplementedError, match="init_weights"):
        train_cell.training_arch(TOY_CONF, root)
