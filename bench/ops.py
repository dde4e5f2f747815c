"""Operations and bytes, from shapes: the roofline and MFU denominators.

``bytes_moved`` and ``attention_bytes_moved`` are copies of the analytic
traffic models in the program's ``kernels/dispatch.py`` (a test holds the
copies equal to the originals at the cells' shapes).  They model what a
route moves.  A roofline needs the least the chip could move, so
``least_bytes`` counts each operand read once and the output written once
and nothing else; a kernel can only be slower than that.
"""

from __future__ import annotations

import math

FUSED, UNFUSED = "fused", "unfused"


def bytes_moved(path: str, m: int, k: int, n: int, *, stochastic: bool = True,
                bm: int = 128, bn: int = 128, bk: int = 128,
                kind: str = "qq") -> int:
    """HBM traffic of one quantize + contract (M, K) x (N, K)^T -> (M, N),
    as ``dispatch.bytes_moved`` models it."""
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    ni, nj = math.ceil(m / bm), math.ceil(n / bn)
    if path == "float":
        return f32 * (nj * m * k + ni * n * k + m * n)
    a_fresh = kind in ("qq", "qi")
    b_fresh = kind in ("qq", "iq")
    fresh = (m * k if a_fresh else 0) + (n * k if b_fresh else 0)
    pre = (m * k if not a_fresh else 0) + (n * k if not b_fresh else 0)
    scan = f32 * fresh
    quant_in = (f32 + r8) * fresh
    resid_out = i8 * fresh
    y_out = f32 * m * n
    if path == FUSED:
        return scan + quant_in + resid_out + i8 * pre + y_out
    gemm_reads = i8 * (nj * m * k + ni * n * k)
    unfused = scan + quant_in + resid_out + gemm_reads + y_out
    if path == UNFUSED:
        return unfused
    return unfused + 2 * f32 * fresh


def attention_bytes_moved(path: str, gs: int, t: int, d: int, *,
                          chunk: int = 1024, stochastic: bool = True,
                          op: str = "attn_fwd") -> int:
    """HBM traffic of one attention forward per (batch, KV-head) slice, as
    ``dispatch.attention_bytes_moved`` models it."""
    f32, r8, i8 = 4, (4 if stochastic else 0), 1
    fused_like = path == FUSED
    if op == "attn_decode":
        exp_rows = 2 * 4 * t
        if fused_like:
            return (i8 * gs * d + 2 * i8 * t * d + exp_rows + r8 * gs * t
                    + f32 * gs * d)
        qk = bytes_moved(FUSED, gs, d, t, stochastic=stochastic, kind="pp")
        pv = bytes_moved(FUSED, gs, t, d, stochastic=stochastic, kind="qi")
        return qk + pv + exp_rows + 2 * f32 * gs * t
    if fused_like:
        return (i8 * gs * d + 2 * i8 * t * d + r8 * gs * t
                + f32 * gs * d + 2 * f32 * gs)
    c = min(chunk, t)
    nc = math.ceil(t / c)
    per_chunk = (bytes_moved(FUSED, gs, d, c, stochastic=stochastic,
                             kind="pp")
                 + bytes_moved(FUSED, gs, c, d, stochastic=stochastic,
                               kind="qi")
                 + 2 * f32 * gs * c
                 + 2 * f32 * (gs * d + 2 * gs))
    return nc * per_chunk


def gemm_ops(m: int, k: int, n: int) -> int:
    """Integer operations of one (M, K) x (K, N) product: a multiply and
    an add per term."""
    return 2 * m * k * n


def least_bytes(m: int, k: int, n: int, kind: str = "qq") -> int:
    """The fewest HBM bytes a contraction of this kind can move: a float32
    operand (one quantized in the kernel) or an int8 one (pre-quantized)
    read once, the float32 output written once."""
    a = 4 if kind in ("qq", "qi") else 1
    b = 4 if kind in ("qq", "iq") else 1
    return a * m * k + b * n * k + 4 * m * n


def least_seconds(m: int, k: int, n: int, kind: str, peak_ops: float,
                  hbm_bytes_per_s: float):
    """(least time, the bound that sets it) of one contraction."""
    t_ops = gemm_ops(m, k, n) / peak_ops
    t_mem = least_bytes(m, k, n, kind) / hbm_bytes_per_s
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")


def gemm_weights(conf: dict) -> int:
    """Weight elements that pass through a GEMM per token: every layer's
    projections and the (tied) head; not the embedding gather, biases or
    norm gains."""
    from .model import dims
    g = dims(conf)
    d, hq, hkv = g["d"], g["h"] * g["hd"], g["kv"] * g["hd"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * g["ff"]
    return g["L"] * per_layer + d * g["V"]


def train_ops_per_token(conf: dict, seq: int) -> float:
    """Operations one token of training requires: 6 per GEMM weight (2
    forward, 4 backward) plus causal attention's QK^T and PV, forward (2
    products of 2 ops over the (seq + 1) / 2 positions a token sees on
    average) and backward (twice that).  Recomputation does not count."""
    from .model import dims
    g = dims(conf)
    attn_fwd = 2 * 2 * g["h"] * g["hd"] * (seq + 1) / 2 * g["L"]
    return 6 * gemm_weights(conf) + 3 * attn_fwd


def step_gemms(conf: dict, tokens: int) -> list:
    """The train step's projection and head GEMMs, as the router names
    them: (op, m, k, n, operand kind, times per step) for the forward
    (tokens x d_in x d_out, qq), the input gradient (tokens x d_out x
    d_in, qi) and the weight gradient (d_in x tokens x d_out, ii) of each
    layer's q, k, v, o, gate, up and down and of the tied head."""
    from .model import dims
    g = dims(conf)
    d, hq, hkv, ff = g["d"], g["h"] * g["hd"], g["kv"] * g["hd"], g["ff"]
    layer = [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, ff), (d, ff), (ff, d)]
    out = []
    for shapes, count in ((layer, g["L"]), ([(d, g["V"])], 1)):
        for din, dout in shapes:
            out += [("qmatmul_fwd", tokens, din, dout, "qq", count),
                    ("qmatmul_dx", tokens, dout, din, "qi", count),
                    ("qmatmul_dw", din, tokens, dout, "ii", count)]
    return out
