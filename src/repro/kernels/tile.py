"""Tile-level building blocks shared by every Pallas kernel and its jnp mirror.

A leaf module: it imports nothing from the rest of the package, so any
kernel module (and ``kernels.dispatch``) can use it without an import
cycle through ``core``.

Everything here lowers through Mosaic (the TPU kernel compiler) as well as
through XLA, which constrains the forms used:

  * bit patterns are handled as int32 vectors.  Mosaic legalizes neither
    unsigned min/max nor a bitcast of a scalar, so unsigned comparisons are
    made signed by flipping the top bit of both sides, and a scalar is
    lifted to a (1, 1) tile before a bitcast (the result broadcasts against
    any 2-D tile exactly as the scalar would).
  * the results are bit-identical to ``core.bfp`` (``quantize``, ``pow2``):
    the same threshold-compare rounding on the same random bits.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["round_up", "scale_exp", "pow2_f32", "eff_exp", "quantize_tile",
           "int8_dot", "ult"]

_F32_EXP_BIAS = 127
_F32_MANT_BITS = 23
_SIGN = -(1 << 31)          # int32 with only the top bit set


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def scale_exp(e_biased, p):
    """Unbiased exponent of a p-magnitude-bit BFP scale (cf. core.bfp)."""
    return e_biased - _F32_EXP_BIAS - _F32_MANT_BITS + (24 - p)


def _as_tile(x):
    """Lift a scalar to a (1, 1) tile: Mosaic bitcasts vectors only."""
    return jnp.reshape(x, (1, 1)) if jnp.ndim(x) == 0 else x


def pow2_f32(e):
    """Exact 2^e for int32 e, flushing e < -126 to 0 (mirrors core.bfp.pow2).
    A scalar ``e`` gives a (1, 1) tile."""
    e = _as_tile(jnp.asarray(e, jnp.int32))
    e1 = jnp.clip(e, -126, 127)
    f = lax.bitcast_convert_type((e1 + _F32_EXP_BIAS) << _F32_MANT_BITS,
                                 jnp.float32)
    return jnp.where(e < -126, jnp.float32(0.0), f)


def eff_exp(x):
    """Effective biased exponent of f32 ``x`` (sub-normals clamp to 1).
    A scalar ``x`` gives a (1, 1) tile."""
    b = lax.bitcast_convert_type(_as_tile(x), jnp.int32)
    return jnp.maximum((b >> _F32_MANT_BITS) & 0xFF, 1)


def ult(a, b):
    """Unsigned ``a < b`` on int32 bit patterns, as a signed compare."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def quantize_tile(x, rand, e_shared, p, stochastic):
    """Linear fixed-point mapping of an f32 tile to int8 mantissas.

    Bit-identical to ``core.bfp.quantize`` given the same random bits
    (``rand``, uint32): unpack the IEEE-754 pattern, shift-align to the
    shared biased exponent ``e_shared`` (scalar or broadcastable int32),
    threshold-compare round (stochastic against ``rand``, or half-up when
    ``stochastic`` is False — then ``rand`` may be None), clamp the
    2^p - 1 rounding overflow of the e_max element, re-apply the sign.
    An element above the shared exponent (shift < 0) maps to 0, as the
    unsigned shift of the reference does.
    """
    b = lax.bitcast_convert_type(x, jnp.int32)
    bexp = (b >> _F32_MANT_BITS) & 0xFF
    frac = b & 0x7FFFFF
    mant24 = jnp.where(bexp > 0, frac | (1 << _F32_MANT_BITS), frac)
    eff = jnp.maximum(bexp, 1)

    s = (e_shared - eff) + (24 - p)
    s31 = jnp.clip(s, 0, 31)
    base = jnp.where((s >= 0) & (s < 32), mant24 >> s31, 0)
    m_lo = mant24 & ((1 << s31) - 1)
    left = jnp.clip(32 - s, 0, 31)
    over = jnp.clip(s - 32, 0, 31)
    thr = jnp.where(s <= 31, m_lo << left,
                    jnp.where(s == 32, mant24, mant24 >> over))
    if stochastic:
        up = ult(lax.bitcast_convert_type(rand, jnp.int32), thr) & (s > 0)
    else:
        # Half-up: dropped fraction >= 1/2  <=>  lifted threshold >= 2^31.
        up = (thr < 0) & (s > 0)
    mag = jnp.minimum(base + up.astype(jnp.int32), (1 << p) - 1)
    return jnp.where(b < 0, -mag, mag).astype(jnp.int8)


def int8_dot(am, bm):
    """(bm, K) int8 x (N, K) int8 -> (bm, N) int32 on the MXU."""
    return lax.dot_general(am, bm, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)
