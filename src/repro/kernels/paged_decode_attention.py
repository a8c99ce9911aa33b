"""Pallas TPU paged flash-decode: single-token attention over a block-pool
KV cache addressed through a page table.

The cache is not one contiguous (S, hd) buffer per sequence but a pool of
fixed-size pages shared by every sequence; a per-sequence page table maps
logical page i to its physical pool index. The page table and the
positions ride in as scalar-prefetch operands, so the kernel gathers
exactly the pages a sequence owns, never a densified copy of the pool.

The walk is bounded by each slot's live range, derived from ``pos``:
logical pages ``first .. last`` with ``last = pos // page`` and ``first``
0, or with ``window`` > 0 the page holding ``pos - window + 1``. A slot
with ``pos < 0`` walks nothing and returns zeros. Table entries outside
the live range are never read, so padded (null page 0) or stale entries
need no special-casing. Inside the range, positions past ``pos`` (and,
windowed, before the window) are masked; the online-softmax state is f32.

The pool is one layer's (P, page, KV, hd), or the whole stack's (L, P,
page, KV, hd) with ``layer`` naming the layer: the layer index rides in as
a third scalar-prefetch operand and every page copy (or the per-page
grid's index map) reads ``pool[layer, page]``, so the decode step can
carry the stacked pool through its layer loop and update it in place,
never slicing a layer out.

One grid step per (slot, KV head) walks the slot's live pages in blocks of
``ppb`` pages (about `BLOCK_BYTES` of K, set from ``page`` and ``hd``),
gathered by its own page copies into a double-buffered VMEM block: block
j+1's copies are in flight while block j is computed. Mosaic slices a
page out of the pool only when ``hd`` is a multiple of 128; other head
widths take a per-page grid whose index map clamps to the live range, so
pages past it are neither copied nor computed (`_page_grid_kernel`).
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
BLOCK_BYTES = 64 * 1024  # K per block of pages: 256 tokens at hd 128 in bf16


def pages_per_block(page: int, hd: int, itemsize: int, n: int) -> int:
    """Pages per block: a block's K is about `BLOCK_BYTES`, at least one
    page and at most the table's `n` pages."""
    return max(1, min(n, BLOCK_BYTES // (page * hd * itemsize)))


def live_pages(pos, page: int, n: int, window: int = 0):
    """(first, last) logical page of the live range ending at `pos` in a
    table of `n` pages; empty (last < first) for ``pos < 0``."""
    last = jnp.minimum(pos // page, n - 1)
    first = jnp.maximum(0, (pos - window + 1) // page) if window > 0 else 0
    return first, last


def _online_softmax_step(q, k, v, idx, pos, window, carry):
    """Fold one block of keys and values (positions `idx`) into the
    running (max, denominator, accumulator)."""
    m_prev, l_prev, acc = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (groups, T)
    valid = idx <= pos
    if window > 0:
        valid = valid & (idx > pos - window)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l_new, acc * alpha + jax.lax.dot(p, v)


def _init_state(groups: int, hd: int):
    return (
        jnp.full((groups, 1), NEG_INF, jnp.float32),
        jnp.zeros((groups, 1), jnp.float32),
        jnp.zeros((groups, hd), jnp.float32),
    )


def _walk_kernel(
    pt_ref,  # scalar prefetch: page table (B, n_pages)
    pos_ref,  # scalar prefetch: positions (B,)
    layer_ref,  # scalar prefetch: (1,) layer of the stacked pool
    q_ref,  # (1, 1, groups, hd)
    k_hbm,  # (L, P, KV, page, hd), left where it lies
    v_hbm,  # (L, P, KV, page, hd)
    o_ref,  # (1, 1, groups, hd)
    k_buf, v_buf,  # (2, ppb, page, hd): double-buffered blocks of pages
    sem,  # DMA semaphores, (k or v, buffer)
    *,
    scale: float,
    page: int,
    ppb: int,
    window: int,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    pos = pos_ref[b]
    first, last = live_pages(pos, page, pt_ref.shape[1], window)
    n_blocks = jnp.where(pos >= 0, (last - first) // ppb + 1, 0)
    groups, hd = q_ref.shape[2], q_ref.shape[3]
    tokens = ppb * page

    def copies(block, slot):
        """(live, copy) for each page copy of one block; a page past `last`
        is not live and is neither started nor waited for."""
        base = first + block * ppb
        layer = layer_ref[0]
        out = []
        for i in range(ppb):
            phys = pt_ref[b, jnp.minimum(base + i, last)]
            for kv, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(src.at[layer, phys, h], dst.at[slot, i],
                                             sem.at[kv, slot])
                out.append((base + i <= last, copy))
        return out

    def start(block, slot):
        for live, copy in copies(block, slot):
            pl.when(live)(copy.start)

    def wait(block, slot):
        for live, copy in copies(block, slot):
            pl.when(live)(copy.wait)

    @pl.when(n_blocks > 0)
    def _prime():
        start(0, 0)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (groups, hd)

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            start(j + 1, 1 - slot)

        wait(j, slot)
        base = (first + j * ppb) * page
        k = k_buf[slot].reshape(tokens, hd).astype(jnp.float32)
        v = v_buf[slot].reshape(tokens, hd).astype(jnp.float32)
        # rows of pages not copied hold whatever the buffer held before: zero
        # them, so a non-finite leftover cannot reach the output through p = 0
        row = base + jax.lax.broadcasted_iota(jnp.int32, (tokens, hd), 0)
        v = jnp.where(row <= pos, v, 0.0)
        idx = base + jax.lax.broadcasted_iota(jnp.int32, (groups, tokens), 1)
        return _online_softmax_step(q, k, v, idx, pos, window, carry)

    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, _init_state(groups, hd))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _page_grid_kernel(
    pt_ref,  # scalar prefetch: page table (B, n_pages)
    pos_ref,  # scalar prefetch: positions (B,)
    layer_ref,  # scalar prefetch: (1,) layer of the stacked pool
    q_ref,  # (1, 1, groups, hd)
    k_ref,  # (1, 1, 1, page, hd): the index map's page, clamped to the live range
    v_ref,  # (1, 1, 1, page, hd)
    o_ref,  # (1, 1, groups, hd)
    m_scr, l_scr, acc_scr,  # (groups,1),(groups,1),(groups,hd)
    *,
    scale: float,
    page: int,
    window: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    pos = pos_ref[b]
    first, last = live_pages(pos, page, pt_ref.shape[1], window)
    groups, hd = q_ref.shape[2], q_ref.shape[3]

    @pl.when(i == 0)
    def _init():
        m_scr[...], l_scr[...], acc_scr[...] = _init_state(groups, hd)

    @pl.when((i >= first) & (i <= last))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0, 0].astype(jnp.float32)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        idx = i * page + jax.lax.broadcasted_iota(jnp.int32, (groups, page), 1)
        carry = (m_scr[...], l_scr[...], acc_scr[...])
        m_scr[...], l_scr[...], acc_scr[...] = _online_softmax_step(
            q, k, v, idx, pos, window, carry)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_decode_attention(
    q,
    k_pool,
    v_pool,
    page_table,
    pos,
    layer=None,
    *,
    window: int = 0,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """q: (B, H, hd); k/v_pool: (P, page, KV, hd), or (L, P, page, KV, hd)
    with `layer` (an int32 scalar, may be traced) naming the layer read;
    page_table: (B, n_pages) int32 physical page per logical page; pos:
    scalar or (B,) last valid logical slot, or negative for a slot that
    attends nothing (zeros out). `window` > 0 masks logical slots older than
    ``pos - window`` and skips the pages wholly before them (sliding-window
    layers). Returns (B, H, hd).

    The pages one KV head gathers are shared across its q-head group (GQA).
    """
    B, H, hd = q.shape
    if layer is None:  # one layer's pool: a stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    _, P, page, KV, _ = k_pool.shape
    if KV > 1:
        # the pool's own (page, KV, hd) tiling cannot be sliced per head by a
        # copy, so XLA relays the pool out below: relay only the layer read
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer, 0)
        v_pool = jax.lax.dynamic_index_in_dim(v_pool, layer, 0)
        layer = 0
    n = page_table.shape[1]
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    window = int(window or 0)

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, dtype=jnp.int32), (B,))
    layer_arr = jnp.reshape(jnp.asarray(layer, dtype=jnp.int32), (1,))
    qg = q.reshape(B, KV, groups, hd)
    # (L, P, KV, page, hd): one head's page is one (page, hd) tile, a
    # bitcast of the pool at KV=1
    kt = k_pool.transpose(0, 1, 3, 2, 4)
    vt = v_pool.transpose(0, 1, 3, 2, 4)
    q_spec = pl.BlockSpec((1, 1, groups, hd), lambda b, h, *_: (b, h, 0, 0))

    if hd % 128 == 0:
        ppb = pages_per_block(page, hd, k_pool.dtype.itemsize, n)
        kernel = functools.partial(_walk_kernel, scale=scale, page=page, ppb=ppb, window=window)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, hd), k_pool.dtype),
                pltpu.VMEM((2, ppb, page, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
    else:
        def page_map(b, h, i, pt, ps, ly):
            first, last = live_pages(ps[b], page, n, window)
            return (ly[0], pt[b, jnp.clip(i, first, jnp.maximum(first, last))], h, 0, 0)

        kernel = functools.partial(_page_grid_kernel, scale=scale, page=page, window=window)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV, n),
            in_specs=[q_spec, pl.BlockSpec((1, 1, 1, page, hd), page_map),
                      pl.BlockSpec((1, 1, 1, page, hd), page_map)],
            out_specs=q_spec,
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
    )(page_table.astype(jnp.int32), pos_arr, layer_arr, qg, kt, vt)
    return out.reshape(B, H, hd)
