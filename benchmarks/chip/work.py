"""Operations and bytes the algorithm needs, counted from the shapes.

These count the work a step has to do, whatever implements it: attention
reads the keys and values of the live positions only, and its operations
are taken at each slot's live context. A kernel that walks every page of
`max_len`, or copies the pool, does more than this and reads as a lower
share of its roofline.

The counts belong to the block, so each comes from the cell's reference
module (its function of the same name, taking the program dict). Where the
reference defines no such function the count is None, and a reader built
on it reads nothing: a dense count never stands in for another block.
"""
from __future__ import annotations

from typing import Optional


def _count(cell, name: str, *args) -> Optional[int]:
    fn = getattr(cell.reference, name, None)
    return None if fn is None else fn(cell.program, *args)


def matmul_params(cell) -> Optional[int]:
    """Weights a decode token multiplies by."""
    return _count(cell, "matmul_params")


def attn_flops(cell, ctx: int) -> Optional[int]:
    """Single-token attention over `ctx` positions, all layers."""
    return _count(cell, "attn_flops", ctx)


def attn_bytes(cell, ctx: int) -> Optional[int]:
    """Bytes single-token attention over `ctx` positions moves, all layers."""
    return _count(cell, "attn_bytes", ctx)


def decode_flops(cell, ctx: int) -> Optional[int]:
    """Model operations of one decoded token at context `ctx`."""
    return _count(cell, "decode_flops", ctx)


def contexts(slots):
    """Contexts of every decoded token of a step's (first context, tokens)
    slot records: a slot at context c decoding n tokens attends c .. c+n-1."""
    for c, n in slots:
        for i in range(n):
            yield c + i


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
