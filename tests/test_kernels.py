"""Pallas kernel sweeps (interpret=True on CPU) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bfp import QuantConfig, dequantize, pow2, quantize
from repro.kernels import ref
from repro.kernels.bfp_quant import bfp_quantize_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.ops import int8_matmul_op, quantize_op

KEY = jax.random.key(0)


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


# ---------------------------------------------------------------------------
# bfp_quantize kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (64, 256), (256, 128), (32, 512)])
@pytest.mark.parametrize("scale", [1e-4, 1.0, 1e4])
def test_bfp_quantize_kernel_matches_ref(shape, scale):
    x = _rand(shape, seed=shape[0] + shape[1], scale=scale)
    rand = jax.random.bits(KEY, shape, jnp.uint32)
    e = ref.max_biased_exp_ref(x)
    e_rows = jnp.broadcast_to(e, (shape[0], 1)).astype(jnp.int32)
    got = bfp_quantize_pallas(x, rand, e_rows, block_rows=8, interpret=True)
    want = ref.bfp_quantize_ref(x, rand, e_rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bfp_quantize_kernel_matches_core_library():
    """Kernel semantics == core.bfp.quantize per-tensor semantics (same rand
    source would be needed for bit equality; here check the value error
    bound and unbiasedness-grade agreement)."""
    x = _rand((64, 128), seed=3)
    m, e = quantize_op(x, KEY, per_tensor=True, use_pallas=True)
    deq = np.asarray(m, np.float64) * float(pow2(e[0] - 133))
    bound = float(jnp.abs(x).max()) / 64
    assert np.abs(deq - np.asarray(x, np.float64)).max() <= bound


@pytest.mark.parametrize("block_rows", [8, 16, 32])
def test_bfp_quantize_per_block_rows(block_rows):
    x = _rand((64, 128), seed=4)
    # per-row-block exponents: rows of very different magnitude
    x = x * jnp.repeat(jnp.float32(2.0) ** jnp.arange(64 // block_rows),
                       block_rows)[:, None]
    m, e_rows = quantize_op(x, KEY, per_tensor=False, use_pallas=True,
                            block_rows=block_rows)
    m_ref, e_ref = quantize_op(x, KEY, per_tensor=False, use_pallas=False,
                               block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_ref))
    np.testing.assert_array_equal(np.asarray(e_rows), np.asarray(e_ref))
    # per-block accuracy beats per-tensor on this construction
    deq = np.asarray(m, np.float64) * (2.0 ** (np.asarray(e_rows)[:, None] - 133.0))
    rel = np.abs(deq - np.asarray(x)) / np.abs(np.asarray(x)).max(axis=1, keepdims=True)
    assert rel.max() < 2 ** -5


def test_bfp_quantize_kernel_padding_path():
    x = _rand((13, 100), seed=5)  # deliberately unaligned
    m, e = quantize_op(x, KEY, per_tensor=True, use_pallas=True)
    m_ref, _ = quantize_op(x, KEY, per_tensor=True, use_pallas=False)
    assert m.shape == (13, 100)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_ref))


# ---------------------------------------------------------------------------
# int8 matmul kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 128),
                                   (128, 384, 256), (384, 256, 128)])
def test_int8_matmul_kernel_matches_ref(m, k, n):
    rng = np.random.RandomState(m + k + n)
    a = jnp.asarray(rng.randint(-127, 128, (m, k)).astype(np.int8))
    b = jnp.asarray(rng.randint(-127, 128, (k, n)).astype(np.int8))
    scale = jnp.float32(2.0 ** -12)
    got = int8_matmul_pallas(a, b, scale, bm=128, bn=128, bk=128, interpret=True)
    want = ref.int8_matmul_ref(a, b, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (256, 256, 256),
                                      (128, 256, 128)])
def test_int8_matmul_block_shape_sweep(bm, bn, bk):
    rng = np.random.RandomState(bm + bn)
    a = jnp.asarray(rng.randint(-127, 128, (512, 512)).astype(np.int8))
    b = jnp.asarray(rng.randint(-127, 128, (512, 512)).astype(np.int8))
    scale = jnp.float32(1.0)
    got = int8_matmul_pallas(a, b, scale, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.int8_matmul_ref(a, b, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_matmul_op_padding_and_scale():
    rng = np.random.RandomState(9)
    a = jnp.asarray(rng.randint(-127, 128, (100, 70)).astype(np.int8))
    b = jnp.asarray(rng.randint(-127, 128, (70, 30)).astype(np.int8))
    got = int8_matmul_op(a, b, jnp.int32(140), jnp.int32(120), use_pallas=True)
    want = int8_matmul_op(a, b, jnp.int32(140), jnp.int32(120), use_pallas=False)
    assert got.shape == (100, 30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("m,k,n", [(100, 70, 30), (13, 257, 9)])
def test_int8_matmul_zero_padding_exact_through_rescale(m, k, n):
    """ops.py pads mantissas with zeros but passes the *unpadded* scale:
    zero mantissas contribute nothing to the int32 accumulator, so the
    rescaled valid region must be BIT-identical to the unpadded reference
    (not merely close)."""
    rng = np.random.RandomState(m + k + n)
    a = jnp.asarray(rng.randint(-127, 128, (m, k)).astype(np.int8))
    b = jnp.asarray(rng.randint(-127, 128, (k, n)).astype(np.int8))
    ea, eb = jnp.int32(141), jnp.int32(118)
    got = int8_matmul_op(a, b, ea, eb, use_pallas=True)
    scale = np.float32(2.0 ** (141 - 133) * 2.0 ** (118 - 133))
    want = (np.asarray(a, np.int32) @ np.asarray(b, np.int32)
            ).astype(np.float32) * scale
    assert got.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_int8_matmul_scale_rides_in_smem_scalar_prefetch():
    """The kernel takes the combined scale through PrefetchScalarGridSpec
    (SMEM), not a (1, 1) VMEM block: a traced scalar must work and scale
    the whole output."""
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randint(-127, 128, (128, 128)).astype(np.int8))
    b = jnp.asarray(rng.randint(-127, 128, (128, 128)).astype(np.int8))

    @jax.jit
    def run(scale):
        return int8_matmul_pallas(a, b, scale, bm=128, bn=128, bk=128,
                                  interpret=True)

    y1 = run(jnp.float32(1.0))
    y2 = run(jnp.float32(0.25))
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1) * 0.25)


def test_end_to_end_kernel_pipeline_vs_core():
    """quantize -> int8 GEMM via kernels ~= core qmatmul-style contraction."""
    x = _rand((64, 128), seed=11)
    w = _rand((128, 64), seed=12)
    kx, kw = jax.random.split(KEY)
    mx, ex = quantize_op(x, kx, per_tensor=True, use_pallas=True)
    mw, ew = quantize_op(w.T, kw, per_tensor=True, use_pallas=True)  # (64,128)
    y = int8_matmul_op(mx, mw.T, ex[0], ew[0], use_pallas=True)
    ref_f = x @ w
    assert np.abs(np.asarray(y - ref_f)).max() <= 0.08 * float(jnp.abs(ref_f).max()) + 0.05


# ---------------------------------------------------------------------------
# shared tile helpers (kernels/tile.py): int32-only forms Mosaic legalizes
# ---------------------------------------------------------------------------

def _edge_values():
    """Zeros of both signs, sub-normals, the largest normals, values far
    below the shared exponent (shift >= 32) and plain randoms."""
    rng = np.random.RandomState(5)
    special = np.array([0.0, -0.0, 1e-45, -3e-39, 1.1754942e-38, 3.4e38,
                        -3.4e38, 1e-30, -2.0 ** -100, 1.0, -1.0, 0.5,
                        0.49999997, 127.0, -128.0, 65504.0], np.float32)
    x = rng.randn(8, 128).astype(np.float32) * 10.0 ** rng.randint(
        -20, 20, (8, 128))
    x.flat[:special.size] = special
    return jnp.asarray(x)


@pytest.mark.parametrize("stochastic", [True, False])
def test_tile_quantizer_matches_reference_on_edge_values(stochastic):
    from repro.kernels import tile
    x = _edge_values()
    e = ref.max_biased_exp_ref(x)
    rand = jax.random.bits(KEY, x.shape, jnp.uint32)
    got = tile.quantize_tile(x, rand if stochastic else None, e, 7,
                             stochastic)
    want = quantize(x, QuantConfig(8, stochastic=stochastic), KEY).m
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if stochastic:
        # also against the unsigned reference, with a shared exponent far
        # BELOW the large elements (negative shift: they map to 0)
        for e_sh in (e, e - 40):
            np.testing.assert_array_equal(
                np.asarray(tile.quantize_tile(x, rand, e_sh, 7, True)),
                np.asarray(ref.bfp_quantize_ref(x, rand, e_sh)))


def test_tile_unsigned_compare_and_pow2():
    from repro.kernels import tile
    a = jnp.asarray(np.array([0, 1, -1, 2 ** 31 - 1, -2 ** 31, 5],
                             np.int64).astype(np.int32))
    b = jnp.asarray(np.array([1, 0, 0, -2 ** 31, 2 ** 31 - 1, 5],
                             np.int64).astype(np.int32))
    want = (np.asarray(a).view(np.uint32) < np.asarray(b).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(tile.ult(a, b)), want)
    e = jnp.arange(-130, 130, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(tile.pow2_f32(e)),
                                  np.asarray(pow2(e)))
    # scalars come back as (1, 1) tiles (Mosaic bitcasts vectors only)
    assert tile.pow2_f32(jnp.int32(3)).shape == (1, 1)
    assert tile.eff_exp(jnp.float32(1.0)).shape == (1, 1)
    assert int(tile.eff_exp(jnp.float32(1.0))[0, 0]) == 127
