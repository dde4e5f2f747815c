"""Tests for integer GEMM ops: forward accuracy, integer backward (A.2),
unbiasedness, per-block variant, conv-as-im2col, embedding scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NumericPolicy, int_policy, qbmm, qconv, qembed, qmatmul
from repro.core.policy import FLOAT32


def _rand(shape, seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


P8 = NumericPolicy()
P8B = NumericPolicy(block=32)
P16 = int_policy(16)
KEY = jax.random.key(42)


# ---------------------------------------------------------------------------
# forward accuracy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [P8, P8B, P16], ids=["pt8", "blk8", "pt16"])
def test_qmatmul_forward_close_to_float(policy):
    x, w = _rand((16, 64), 1), _rand((64, 32), 2)
    y = qmatmul(x, w, KEY, policy)
    ref = x @ w
    # int8 per-tensor: relative error ~ 2^-6 per operand, averaged over K=64
    tol = 0.06 if policy.fwd_bits == 8 else 3e-4
    assert np.abs(np.asarray(y - ref)).max() <= tol * float(jnp.abs(ref).max()) + 0.05


def test_qmatmul_int16_near_exact():
    x, w = _rand((8, 128), 3), _rand((128, 16), 4)
    y = qmatmul(x, w, KEY, P16)
    ref = x @ w
    atol = 5e-4 * float(jnp.abs(ref).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=0, atol=atol)


def test_qmatmul_batched_leading_dims():
    x, w = _rand((2, 3, 5, 64), 5), _rand((64, 7), 6)
    y = qmatmul(x, w, KEY, P16)
    assert y.shape == (2, 3, 5, 7)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=5e-3, atol=5e-3)


def test_qmatmul_float_policy_is_exact():
    x, w = _rand((4, 8), 7), _rand((8, 4), 8)
    np.testing.assert_array_equal(np.asarray(qmatmul(x, w, None, FLOAT32)),
                                  np.asarray(x @ w))


def test_accum_chunking_matches_unchunked():
    x, w = _rand((4, 4096), 9), _rand((4096, 8), 10)
    pol_small = NumericPolicy(accum_chunk=512)
    y1 = qmatmul(x, w, KEY, pol_small)
    y2 = qmatmul(x, w, KEY, NumericPolicy())
    # identical quantization keys -> identical mantissas; chunked int32
    # accumulation then f32 combine vs single int32 accumulation are equal
    # as long as no overflow (values here are tiny).
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6, atol=1e-6)


def test_chunk_count_prime_k_regression():
    """The old divisor search (`while k % n: n += 1`) degenerated for prime
    K: ceil(509/128)=4 walked all the way to n=509, i.e. 509 chunks of ONE
    element.  _pt_dot now lets the last chunk run short instead, so the
    count stays ceil."""
    from repro.core.qops import _chunk_count
    assert _chunk_count(509, 128) == 4          # was 509 before the fix
    assert _chunk_count(509, 509) == 1
    assert _chunk_count(510, 128) == 4
    assert _chunk_count(128, 128) == 1
    assert _chunk_count(7, 2) == 4
    for k in (509, 521, 1031):                  # primes stay bounded
        n = _chunk_count(k, 128)
        assert n == -(-k // 128)
        assert n * (-(-k // n)) >= k            # padded chunks cover K


def _pt_dot_padded_reduce(am, bm, nbatch, nchunk):
    """The chunked dot as one chunk-batched contraction: K zero-padded to
    nchunk equal chunks, int32 partials summed in f32 by one reduce."""
    k = am.shape[-1]
    kc = -(-k // nchunk)
    widths = [(0, 0)] * (am.ndim - 1) + [(0, nchunk * kc - k)]
    a4 = jnp.moveaxis(jnp.pad(am, widths).reshape(*am.shape[:-1], nchunk, kc),
                      -2, nbatch)
    b4 = jnp.moveaxis(jnp.pad(bm, widths).reshape(*bm.shape[:-1], nchunk, kc),
                      -2, nbatch)
    acc = jax.lax.dot_general(
        a4, b4, (((a4.ndim - 1,), (b4.ndim - 1,)),
                 (tuple(range(nbatch + 1)), tuple(range(nbatch + 1)))),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32).sum(axis=nbatch)


@pytest.mark.parametrize("batch,k,nchunk", [((), 8191, 3), ((), 9000, 4),
                                            ((2,), 7001, 5)])
def test_pt_dot_chunks_bitwise_equal_padded_reduce(batch, k, nchunk):
    """_pt_dot's per-slice dots combined in chunk order equal, bit for bit,
    the padded chunk-batched dot reduced over its chunk axis.  Mantissas
    are large and positive, so each int32 partial exceeds 2^24 and the f32
    combine rounds: a different combine order would show."""
    from repro.core.qops import _pt_dot
    rng = np.random.RandomState(k)
    mk = lambda *s: rng.randint(100, 128, s).astype(np.int8)
    am, bm = mk(*batch, 5, k), mk(*batch, 7, k)
    nb = len(batch)
    got = jax.jit(_pt_dot, static_argnums=(2, 3))(am, bm, nb, nchunk)
    want = jax.jit(_pt_dot_padded_reduce, static_argnums=(2, 3))(am, bm, nb,
                                                                  nchunk)
    exact = np.einsum("...mk,...nk->...mn", am.astype(np.int64),
                      bm.astype(np.int64))
    assert np.any(np.asarray(want, np.float64) != exact)     # f32 rounded
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_accum_chunking_prime_k_matches_unchunked():
    x, w = _rand((4, 509), 33), _rand((509, 8), 34)   # prime K
    y1 = qmatmul(x, w, KEY, NumericPolicy(accum_chunk=128))
    y2 = qmatmul(x, w, KEY, NumericPolicy())
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# forward unbiasedness (Eq. 1)
# ---------------------------------------------------------------------------

def test_qmatmul_forward_unbiased():
    x, w = _rand((4, 32), 11), _rand((32, 4), 12)
    ref = np.asarray(x @ w, np.float64)
    n = 2048
    keys = jax.random.split(jax.random.key(0), n)
    ys = jax.vmap(lambda k: qmatmul(x, w, k, P8))(keys)
    mean = np.asarray(ys, np.float64).mean(axis=0)
    sd = np.asarray(ys, np.float64).std(axis=0).max()
    np.testing.assert_allclose(mean, ref, atol=6 * sd / np.sqrt(n))


# ---------------------------------------------------------------------------
# backward: integer gradients match float gradients (A.2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [P8, P8B], ids=["pt8", "blk8"])
def test_qmatmul_grads_close(policy):
    x, w = _rand((16, 48), 13), _rand((48, 24), 14)

    def loss_q(x, w):
        return (qmatmul(x, w, KEY, policy) ** 2).sum()

    def loss_f(x, w):
        return ((x @ w) ** 2).sum()

    gx_q, gw_q = jax.grad(loss_q, argnums=(0, 1))(x, w)
    gx_f, gw_f = jax.grad(loss_f, argnums=(0, 1))(x, w)
    for gq, gf in ((gx_q, gx_f), (gw_q, gw_f)):
        denom = float(jnp.abs(gf).max())
        assert np.abs(np.asarray(gq - gf)).max() <= 0.12 * denom


def test_qmatmul_grads_unbiased():
    x, w = _rand((6, 16), 15), _rand((16, 6), 16)

    def gw(key):
        return jax.grad(lambda w: (qmatmul(x, w, key, P8) ** 2).sum())(w)

    n = 2048
    keys = jax.random.split(jax.random.key(1), n)
    gws = jax.vmap(gw)(keys)
    ref = np.asarray(jax.grad(lambda w: ((x @ w) ** 2).sum())(w), np.float64)
    mean = np.asarray(gws, np.float64).mean(axis=0)
    sd = np.asarray(gws, np.float64).std(axis=0).max()
    # quadratic loss: E[grad] has a second-order term from Var(y) — allow a
    # small systematic component plus the statistical one.
    np.testing.assert_allclose(mean, ref, atol=6 * sd / np.sqrt(n) + 0.02 * np.abs(ref).max())


def test_gradient_variance_bound():
    """A.2 / Assumption 2(iii,b): Var of integer grads exceeds float grad Var
    by a bounded M^q term (scales with operand norms)."""
    x, w = _rand((8, 32), 17), _rand((32, 8), 18)
    gy = _rand((8, 8), 19)

    def dw(key):
        _, vjp = jax.vjp(lambda w: qmatmul(x, w, key, P8), w)
        return vjp(gy)[0]

    keys = jax.random.split(jax.random.key(2), 512)
    dws = np.asarray(jax.vmap(dw)(keys), np.float64)
    var = dws.var(axis=0).max()
    # M^q ~ sigma_G^2 E||X||^2 + K sigma_X^2 sigma_G^2 with sigma ~ (ulp)^2/4
    ulp_x = np.abs(np.asarray(x)).max() / 64
    ulp_g = np.abs(np.asarray(gy)).max() / 64
    K = x.shape[0]
    bound = (ulp_g ** 2) * (np.asarray(x) ** 2).sum(axis=1).max() \
        + (ulp_x ** 2) * (np.asarray(gy) ** 2).sum(axis=0).max() \
        + K * (ulp_x ** 2) * (ulp_g ** 2)
    assert var <= bound  # empirical variance within the analytic A.2 bound


# ---------------------------------------------------------------------------
# qbmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [P8, P8B], ids=["pt8", "blk8"])
def test_qbmm_forward_and_grads(policy):
    a, b = _rand((4, 8, 32), 20), _rand((4, 32, 16), 21)
    y = qbmm(a, b, KEY, policy)
    ref = a @ b
    assert np.abs(np.asarray(y - ref)).max() <= 0.08 * float(jnp.abs(ref).max()) + 0.05

    ga_q, gb_q = jax.grad(lambda a, b: (qbmm(a, b, KEY, policy) ** 2).sum(),
                          argnums=(0, 1))(a, b)
    ga_f, gb_f = jax.grad(lambda a, b: ((a @ b) ** 2).sum(), argnums=(0, 1))(a, b)
    for gq, gf in ((ga_q, ga_f), (gb_q, gb_f)):
        assert np.abs(np.asarray(gq - gf)).max() <= 0.15 * float(jnp.abs(gf).max())


def test_qbmm_multi_batch_dims():
    a, b = _rand((2, 3, 4, 32), 22), _rand((2, 3, 32, 8), 23)
    y = qbmm(a, b, KEY, P16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ b), rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# qembed
# ---------------------------------------------------------------------------

def test_qembed_forward_and_integer_scatter_grad():
    table = _rand((50, 16), 24)
    tok = jnp.asarray(np.random.RandomState(0).randint(0, 50, size=(4, 7)))
    y = qembed(tok, table, KEY, P16)
    ref = jnp.take(table, tok, axis=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=5e-3, atol=5e-3)

    gt_q = jax.grad(lambda t: (qembed(tok, t, KEY, P8) ** 2).sum())(table)
    gt_f = jax.grad(lambda t: (jnp.take(t, tok, axis=0) ** 2).sum())(table)
    assert np.abs(np.asarray(gt_q - gt_f)).max() <= 0.2 * float(jnp.abs(gt_f).max()) + 1e-3


def test_qembed_rows_never_looked_up_get_zero_grad():
    table = _rand((10, 8), 25)
    tok = jnp.asarray([0, 1, 2])
    g = jax.grad(lambda t: qembed(tok, t, KEY, P8).sum())(table)
    assert np.all(np.asarray(g)[3:] == 0)


# ---------------------------------------------------------------------------
# qconv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride,padding", [((1, 1), "SAME"), ((2, 2), "SAME"),
                                            ((1, 1), "VALID")])
def test_qconv_matches_float_conv(stride, padding):
    x = _rand((2, 8, 8, 3), 26)
    w = _rand((3, 3, 3, 5), 27)
    y = qconv(x, w, KEY, P16, stride=stride, padding=padding)
    ref = jax.lax.conv_general_dilated(
        x, w, stride, padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert y.shape == ref.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-2, atol=1e-2)


def test_qconv_grads_close_to_float():
    x = _rand((2, 6, 6, 3), 28)
    w = _rand((3, 3, 3, 4), 29)

    gq = jax.grad(lambda x, w: (qconv(x, w, KEY, P8) ** 2).sum(), argnums=(0, 1))(x, w)
    gf = jax.grad(lambda x, w: (jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) ** 2).sum(),
        argnums=(0, 1))(x, w)
    for q, f in zip(gq, gf):
        assert np.abs(np.asarray(q - f)).max() <= 0.15 * float(jnp.abs(f).max())


# ---------------------------------------------------------------------------
# jit / vmap composability
# ---------------------------------------------------------------------------

def test_qmatmul_jits_and_remats():
    x, w = _rand((8, 32), 30), _rand((32, 8), 31)

    @jax.jit
    def f(x, w, k):
        return jax.checkpoint(lambda x, w: (qmatmul(x, w, k, P8) ** 2).sum())(x, w)

    g = jax.jit(jax.grad(f))(x, w, KEY)
    assert np.isfinite(np.asarray(g)).all()
