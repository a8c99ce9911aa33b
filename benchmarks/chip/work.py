"""Operations and bytes the algorithm needs, counted from the shapes.

These count the work a step has to do, whatever implements it: attention
reads the keys and values of the live positions only, and its operations
are taken at each slot's live context. A kernel that walks every page of
`max_len`, or copies the pool, does more than this and reads as a lower
share of its roofline.
"""
from __future__ import annotations

from refmodel import dims

BF16 = 2  # bytes per element of the served weights, activations and cache


def matmul_params(p: dict) -> int:
    """Weights a decode token multiplies by: every layer's projections and
    MLP, and the output head (the embedding is a gather)."""
    m = dims(p)
    d, H, KV, hd, f = m["d"], m["H"], m["KV"], m["hd"], m["f"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = (3 if m["gated"] else 2) * d * f
    return m["L"] * (attn + mlp) + d * m["V"]


def attn_flops(p: dict, ctx: int) -> int:
    """Single-token attention over `ctx` positions, all layers: QK^T and PV."""
    m = dims(p)
    return m["L"] * 4 * m["H"] * m["hd"] * ctx


def attn_bytes(p: dict, ctx: int) -> int:
    """Single-token attention, all layers: K and V of `ctx` positions, the
    query in and the output out."""
    m = dims(p)
    return m["L"] * BF16 * (2 * ctx * m["KV"] * m["hd"] + 2 * m["H"] * m["hd"])


def decode_flops(p: dict, ctx: int) -> int:
    """Model operations of one decoded token at context `ctx`."""
    return 2 * matmul_params(p) + attn_flops(p, ctx)


def contexts(slots):
    """Contexts of every decoded token of a step's (first context, tokens)
    slot records: a slot at context c decoding n tokens attends c .. c+n-1."""
    for c, n in slots:
        for i in range(n):
            yield c + i


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
