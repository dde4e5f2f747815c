"""The plain reference against the program's float32 and int8 paths at a
small size, on the CPU."""

import jax
import jax.numpy as jnp

from bench import model, spec
from bench.traffic import SyntheticLM

SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=96, vocab_size=509)
QWEN = dict(SMALL, name="q", model_type="qwen2", hidden_act="silu",
            rms_norm_eps=1e-6, rope_theta=1e6, attention_bias=True,
            tie_word_embeddings=True)
MINICPM = dict(SMALL, num_key_value_heads=4, vocab_size=251, name="m",
               model_type="minicpm", hidden_act="silu", rms_norm_eps=1e-6,
               rope_theta=1e4, attention_bias=False, tie_word_embeddings=True)


def _arch(conf):
    return spec.arch(conf["model_type"])


def _setup(conf, seed=3):
    key = model.seed_key(seed)
    params = _arch(conf).init_weights(key, conf)
    b = SyntheticLM(conf["vocab_size"], 24, 3, seed=seed).batch_for_step(0)
    return key, params, {k: jnp.asarray(v) for k, v in b.items()}


def _program_loss(conf, policy):
    from repro.models import transformer
    cfg = _arch(conf).arch_config(conf)
    return lambda p, b, k: transformer.loss_fn(p, b, k, policy, cfg)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_float32_path_matches_reference(conf=QWEN):
    """Both are float32 at full precision, so they differ only by the
    order of summation: 1e-5 relative on the loss and the logits, 1e-4 on
    each gradient leaf (a difference of sums of many terms)."""
    from repro.core.policy import FLOAT32
    from repro.models import transformer
    key, params, batch = _setup(conf)
    arch = _arch(conf)
    cfg = arch.arch_config(conf)
    with jax.default_matmul_precision("highest"):
        ref_l, ref_g = jax.value_and_grad(
            lambda p: arch.loss(p, batch, conf))(params)
        prog_l, prog_g = jax.value_and_grad(
            lambda p: _program_loss(conf, FLOAT32)(p, batch, key))(params)
        h, _, _ = transformer.forward_hidden(params, batch["tokens"], key,
                                             FLOAT32, cfg)
        prog_logits = h @ params["embed"].T
        ref_logits = arch.logits(params, batch["tokens"], conf)
    assert abs(float(prog_l) - float(ref_l)) <= 1e-5 * abs(float(ref_l))
    assert _rel(prog_logits, ref_logits) <= 1e-5
    for name, a, b in zip(model.leaf_names(ref_g),
                          jax.tree_util.tree_leaves(prog_g),
                          jax.tree_util.tree_leaves(ref_g)):
        assert _rel(a, b) <= 1e-4, name


def test_int8_path_near_reference(conf=MINICPM):
    """The paper's int8 path rounds every operand to 8 bits with
    stochastic rounding: its loss stays within 1% of the reference (the
    band the bring-up smoke holds at full width) and each gradient leaf
    points the same way, cosine above 0.9.  Its error is far above the
    float32 path's, which the check's limits rely on."""
    from repro.core.policy import PAPER_INT8
    key, params, batch = _setup(conf)
    with jax.default_matmul_precision("highest"):
        ref_l, ref_g = jax.value_and_grad(
            lambda p: _arch(conf).loss(p, batch, conf))(params)
    prog_l, prog_g = jax.value_and_grad(
        lambda p: _program_loss(conf, PAPER_INT8)(p, batch, key))(params)
    assert abs(float(prog_l) - float(ref_l)) <= 1e-2 * abs(float(ref_l))
    for name, a, b in zip(model.leaf_names(ref_g),
                          jax.tree_util.tree_leaves(prog_g),
                          jax.tree_util.tree_leaves(ref_g)):
        cos = float(jnp.vdot(a, b) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
        assert cos > 0.9, (name, cos)
