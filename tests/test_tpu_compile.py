"""Compile-only tests against a described TPU v5e (no chip needed).

Every Pallas kernel the TPU routing plan picks at qwen2-0.5b's published
widths (d_model 896, 14/2 heads of 64, d_ff 4864, vocab 151936; a train
step of M = 4096 token rows, batch 8 x seq 512) is lowered and compiled
by the TPU compiler (Mosaic) for one chip of a described ``v5e:2x2``
topology, plus the full-width decode step and the whole-layer decode
kernel at layer sizes its residency holds.  Interpret-mode tests cannot
see what this catches: ops Mosaic does not legalize, and blocks that
overrun the scoped VMEM limit the residency model is meant to respect.
Each test also checks that the plan picks the kernel path, and that the
compiled program really holds a kernel (``tpu_custom_call``) — a
trace-time failure would otherwise degrade to the jnp mirror in silence.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and several test workers import
this file), and the persistent compilation cache is off in here.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bfp import BFP, QuantConfig
from repro.kernels import dispatch as D
from repro.kernels import fused_attention as fa
from repro.kernels import fused_chain as fc
from repro.kernels.fused_linear import fused_gemm_epi_pallas

CFG = QuantConfig(8)                      # the paper's int8, stochastic
M = 8 * 512                               # train tokens per step
D_MODEL, D_FF, VOCAB = 896, 4864, 151936
G, DH, HKV = 7, 64, 2                     # query heads per kv head, head dim
I8, I32, U32, F32 = jnp.int8, jnp.int32, jnp.uint32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(autouse=True)
def _clean_ladder(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    D.reset_fallback_counts()
    yield
    D.reset_fallback_counts()


def _compile(fn, *args):
    """Lower + compile for the described chip; the program must hold a
    Pallas kernel and no kernel may have fallen back while tracing."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert not D.fallback_counts(), D.fallback_counts()
    assert "tpu_custom_call" in text
    return text


def _shape(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _keys(r):
    k = jax.random.wrap_key_data(r)
    return k, jax.random.fold_in(k, 1)


# (op, kind, m, k, n, path): one row per kernel family the train/serve
# plan picks at these widths (see kernels.dispatch.plan_contract).
GEMMS = [
    ("qmatmul_fwd", "qq", M, D_MODEL, HKV * DH, D.FUSED),      # k/v proj
    ("qmatmul_dx", "qi", M, D_MODEL, D_MODEL, D.FUSED),        # dX, q proj
    ("qmatmul_dw", "ii", D_MODEL, M, D_MODEL, D.FUSED),        # dW, q proj
    ("qmatmul_fwd", "pp", 32, D_MODEL, D_FF, D.FUSED),         # qflow serve
    ("qmatmul_fwd", "qq", M, D_MODEL, D_FF, D.UNFUSED),        # MLP up
    ("qmatmul_dw", "ii", D_MODEL, M, VOCAB, D.UNFUSED),        # lm head dW
]


@pytest.mark.parametrize("op,kind,m,k,n,path", GEMMS,
                         ids=[f"{g[1]}-{g[5]}-{g[2]}x{g[3]}x{g[4]}"
                              for g in GEMMS])
def test_gemm_kernels_compile(one_chip, op, kind, m, k, n, path):
    cfg2 = None if kind == "qq" else CFG
    dec = D.plan_contract(op, m, k, n, CFG, kind=kind, cfg2=cfg2,
                          backend="tpu")
    assert dec.path == path and not dec.interpret, dec
    S = _shape(one_chip)
    key = S((2,), U32)
    if kind == "qq":
        text = _compile(lambda a, b, r: D.contract_qq(a, b, CFG, *_keys(r),
                                                      dec)[0],
                        S((m, k), F32), S((n, k), F32), key)
    elif kind == "qi":
        text = _compile(lambda a, b, e, r: D.contract_qi(
            a, BFP(b, e, CFG), CFG, _keys(r)[0], dec)[0],
            S((m, k), F32), S((n, k), I8), S((), I32), key)
    else:
        text = _compile(lambda a, b, ea, eb: D.contract_ii(
            BFP(a, ea, CFG), BFP(b, eb, CFG), dec),
            S((m, k), I8), S((n, k), I8), S((), I32), S((), I32))
    if path == D.UNFUSED and kind == "qq":
        # both halves of the pair: quantizer kernels and the GEMM kernel
        assert text.count("tpu_custom_call") >= 3


def test_attention_kernels_compile(one_chip):
    """Fused attention forward at the train band, backward at the longest
    band its residency admits, decode over a serving cache."""
    S = _shape(one_chip)
    e = S((), I32)
    s, t = 512, 512
    fwd = D.plan_attention("attn_fwd", G * s, t, DH, CFG, s=s, kind="pp",
                           backend="tpu")
    assert fwd.path == D.FUSED and not fwd.interpret, fwd
    _compile(lambda q, k, v, r, e: fa.attn_fwd(
        q, k, v, r, e, e, e, 0, t, p=7, s=s, bq=fwd.bm, bt=fwd.bt,
        causal=True, window=0, stochastic=True, interpret=False, pallas=True),
        S((1, G * s, DH), I8), S((1, t, DH), I8), S((1, t, DH), I8),
        S((1, G * s, t), U32), e)

    s = t = 128
    bwd = D.plan_attention("attn_bwd", G * s, t, DH, CFG, s=s, kind="pp",
                           backend="tpu")
    assert bwd.path == D.FUSED, bwd
    gs = G * s
    _compile(lambda q, g, k, v, m, l, dl, rs, rp, e: fa.attn_bwd(
        q, g, k, v, m, l, dl, rs, rp, e, e, e, e, 0, t, p=7, s=s, bt=bwd.bt,
        causal=True, window=0, stochastic=True, interpret=False, pallas=True),
        S((1, gs, DH), I8), S((1, gs, DH), I8), S((1, t, DH), I8),
        S((1, t, DH), I8), S((1, gs, 1), F32), S((1, gs, 1), F32),
        S((1, gs, 1), F32), S((1, gs, t), U32), S((1, gs, t), U32), e)

    t = 48
    dec = D.plan_attention("attn_decode", G, t, DH, CFG, s=1, kind="qi",
                           backend="tpu")
    assert dec.path == D.FUSED, dec
    _compile(lambda q, k, v, ek, ev, r, e: fa.attn_decode(
        q, k, v, ek, ev, r, e, 5, 6, p=7, s=1, causal=True, window=0,
        stochastic=True, interpret=False, pallas=True),
        S((1, G, DH), I8), S((1, t, DH), I8), S((1, t, DH), I8),
        S((1, t, 1), I32), S((1, t, 1), I32), S((1, G, t), U32), e)


def test_chain_kernels_compile(one_chip):
    """The cross-op chains that plan FUSED at these widths: norm->QKV GEMM
    (a bias-free block of this width) and the GEMM epilogue with the
    in-kernel out-quantize."""
    S = _shape(one_chip)
    k, n = D_MODEL, (14 + 2 * HKV) * DH
    dec = D.plan_norm_gemm("qnorm_gemm", M, k, n, CFG, backend="tpu")
    assert dec.path == D.FUSED, dec
    _compile(lambda x, r1, r2, gm, wm, sw: fc.fused_norm_gemm_pallas(
        x, r1, r2, gm, -15, None, 0, wm, sw, n=k, p=7, bm=dec.bm,
        stochastic=True, interpret=False, emit_residuals=True),
        S((M, k), F32), S((M, k), U32), S((M, k), U32), S((1, k), I32),
        S((n, k), I8), S((1, n), I32))

    n = 1024
    dec = D.plan_epilogue("qmatmul_epi", M, k, n, CFG, kind="qi", cfg2=CFG,
                          act="silu_glu", out_q=True, backend="tpu")
    assert dec.path == D.FUSED, dec
    _compile(lambda a, ra, b, rq, e: fused_gemm_epi_pallas(
        a, ra, b, None, None, rq, e, e, kind="qi", bm=dec.bm,
        act="silu_glu", out_q=True, m_true=M, interpret=False),
        S((M, k), F32), S((M, k), U32), S((n, k), I8), S((M, n // 2), U32),
        S((), I32))


# (b, d, d_ff, hq, hkv, dh, t): whole decoder layers that fit the decode
# kernel's residency: a smoke-width block, and qwen2-0.5b's attention
# (7 query heads per kv head) with a narrower MLP.
DECODE_BLOCKS = [(2, 256, 256, 4, 2, 64, 64), (4, D_MODEL, 2048, 14, 2, DH, 256)]


@pytest.mark.parametrize("b,d,n_ff,hq,hkv,dh,t", DECODE_BLOCKS,
                         ids=[f"d{c[1]}-ff{c[2]}-h{c[3]}" for c in
                              DECODE_BLOCKS])
def test_decode_block_kernel_compiles(one_chip, b, d, n_ff, hq, hkv, dh, t):
    """The whole-layer decode kernel (norm, QKV, decode attention over the
    quantized cache with the fresh row spliced in, out-proj, gated MLP)."""
    dec = D.plan_decode_block("qdecode_block", b, d, n_ff, t, hq, hkv, dh,
                              dataclasses.replace(CFG, stochastic=False),
                              backend="tpu")
    assert dec.path == D.FUSED and not dec.interpret, dec
    S = _shape(one_chip)
    nqkv = (hq + 2 * hkv) * dh
    _compile(lambda *a: D.run_decode_block(
        *a, dec, n_d=d, n_ff=n_ff, hq=hq, hkv=hkv, dh=dh, window=32,
        se_g1=-15, se_g2=-15),
        S((b, d), F32), S((nqkv, d), I8), S((1, nqkv), I32),
        S((d, hq * dh), I8), S((1, d), I32), S((2 * n_ff, d), I8),
        S((1, 2 * n_ff), I32), S((d, n_ff), I8), S((1, d), I32),
        S((1, d), I32), S((1, d), I32),
        S((b, hkv, t, dh), I8), S((b, hkv, t, 1), I32),
        S((b, hkv, t, dh), I8), S((b, hkv, t, 1), I32),
        S((1, 2 * dh), F32), S((), I32))


def test_decode_step_compiles(one_chip, monkeypatch):
    """The full-width serving decode step (quantized weights and KV cache,
    24 layers) as one program, routed as on the chip."""
    from repro.configs import get_config
    from repro.core.policy import PAPER_INT8
    from repro.launch.steps import (cache_template, make_decode_step,
                                    quantized_params_template)

    # dispatch asks the local backend; steer it to plan for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2_0_5b")
    pol = dataclasses.replace(PAPER_INT8, qweights=True, qcache=True)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    S = _shape(one_chip)
    step = make_decode_step(cfg, pol)
    with D.record_decisions() as log:
        text = _compile(
            lambda p, c, t, pos, r: step(p, c, t, pos,
                                         jax.random.wrap_key_data(r)),
            on_chip(quantized_params_template(cfg, pol)),
            on_chip(cache_template(cfg, 1, 48, policy=pol)),
            S((1,), I32), S((), I32), S((2,), U32))
    assert {d.path for d in log} <= {D.FUSED, D.UNFUSED}, log
    assert not any(d.interpret for d in log)
    assert "attn_decode" in {d.op for d in log}
    assert text.count("tpu_custom_call") >= 5
