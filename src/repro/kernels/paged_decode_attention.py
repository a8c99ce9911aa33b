"""Pallas TPU paged flash-decode: single-token attention over a block-pool
KV cache addressed through a page table.

The cache is not one contiguous (S, hd) buffer per sequence but a pool of
fixed-size pages shared by every sequence; a per-sequence page table maps
logical page i to its physical pool index. The page table rides in as a
scalar-prefetch operand so each grid step's BlockSpec index map can resolve
the physical page BEFORE the body runs — the HBM->VMEM DMA gathers exactly
the pages the sequence owns, never a densified copy of the pool.

Masking follows `decode_attention.py`: logical slots beyond `pos` are
invalid; padded page-table entries (null page 0) always fall past `pos` and
are therefore masked without special-casing. Online-softmax state lives in
VMEM scratch, carried across the page grid dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode

NEG_INF = -1e30


def _kernel(
    pt_ref,  # scalar prefetch: page table (B, n_pages)
    pos_ref,  # scalar prefetch: positions (B,)
    q_ref,  # (1, 1, groups, hd)
    k_ref,  # (1, 1, page, hd) — physical page picked by the index map
    v_ref,  # (1, 1, page, hd)
    o_ref,  # (1, 1, groups, hd)
    m_scr, l_scr, acc_scr,  # (groups,1),(groups,1),(groups,hd)
    *,
    scale: float,
    page: int,
    num_pages: int,
    window: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (groups, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (groups, page)

    # logical slot index of each entry in this page; invalid slots (past
    # pos, incl. everything behind a padded null-page entry) are masked
    idx = i * page + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], page), 1)
    valid = idx <= pos
    if window > 0:
        # shared (prefix-cache) layouts page sliding-window layers through
        # the dynamic table; the window is a position mask, not a ring
        valid = valid & (idx > pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)  # (page, hd)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(i == num_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_decode_attention(
    q,
    k_pool,
    v_pool,
    page_table,
    pos,
    *,
    window: int = 0,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """q: (B, H, hd); k/v_pool: (P, page, KV, hd); page_table: (B, n_pages)
    int32 physical page per logical page; pos: scalar or (B,) last valid
    logical slot. `window` > 0 masks logical slots older than
    ``pos - window`` (sliding-window layers under a shared/prefix layout).
    Returns (B, H, hd).

    The per-KV-head grid dim shares gathered pages across the q-head group
    (GQA); the page grid dim carries the online-softmax state.
    """
    B, H, hd = q.shape
    P, page, KV, _ = k_pool.shape
    n = page_table.shape[1]
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, dtype=jnp.int32), (B,))
    # layout: (P, KV, page, hd) so a gathered page block is (seq, head_dim)-minor
    kt = k_pool.transpose(0, 2, 1, 3)
    vt = v_pool.transpose(0, 2, 1, 3)
    qg = q.reshape(B, KV, groups, hd)

    kernel = functools.partial(
        _kernel, scale=scale, page=page, num_pages=n, window=int(window or 0)
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n),
        in_specs=[
            pl.BlockSpec((1, 1, groups, hd), lambda b, h, i, pt, ps: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, i, pt, ps: (pt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, i, pt, ps: (pt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, hd), lambda b, h, i, pt, ps: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, groups, hd), q.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(page_table.astype(jnp.int32), pos_arr, qg, kt, vt)
    return out.reshape(B, H, hd)
