"""Attention layers: GQA/MQA self-attention (full, sliding-window,
prefix-LM), cross-attention, and KV-cache decode paths.

Weight shapes keep heads as an explicit dimension so tensor parallelism can
shard them over the `model` mesh axis via logical axes:

    wq: (d, H, hd)      ("embed", "heads", "head_dim")
    wk: (d, KV, hd)     ("embed", "kv_heads", "head_dim")
    wv: (d, KV, hd)
    wo: (H, hd, d)      ("heads", "head_dim", "embed")

When stacked for scan-over-layers a leading ("layers",) axis is prepended.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.kernels import ops
from .common import ParamBuilder, apply_rope


def init_attention(pb: ParamBuilder, cfg: ArchConfig, n_layers: Optional[int] = None):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lead = () if n_layers is None else (n_layers,)
    lax = () if n_layers is None else ("layers",)
    return {
        "wq": pb.normal(lead + (d, H, hd), lax + ("embed", "heads", "head_dim"), fan_in=d),
        "wk": pb.normal(lead + (d, KV, hd), lax + ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": pb.normal(lead + (d, KV, hd), lax + ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": pb.normal(lead + (H, hd, d), lax + ("heads", "head_dim", "embed"), fan_in=H * hd),
    }


def _project_qkv(cfg: ArchConfig, p, x, positions):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd), RoPE'd."""
    cd = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if positions is not None:
        q = apply_rope(q, positions, theta=cfg.rope_theta)
        k = apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def self_attention(
    cfg: ArchConfig,
    p,
    x,
    *,
    positions=None,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
) -> jax.Array:
    """Full-sequence self-attention (training / prefill)."""
    B, S, d = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    impl = _impl(cfg)
    out = ops.attention(q, k, v, causal=causal, window=window, prefix_len=prefix_len, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def _impl(cfg: ArchConfig) -> str:
    # training/prefill attention stays on jnp on every platform (see
    # ops.decode_impl); only the decode kernels follow the platform
    return "blocked" if cfg.attention_impl == "blocked" else "ref"


def prefill_attention(
    cfg: ArchConfig, p, x, cache: Tuple[jax.Array, jax.Array], *, window: int = 0, prefix_len: int = 0
):
    """Prefill: full-seq attention that also fills the KV cache.

    cache: (k_cache, v_cache) each (B, S_buf, KV, hd); for windowed layers
    S_buf == window (ring buffer), else S_buf >= S.
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    impl = _impl(cfg)
    out = ops.attention(q, k, v, causal=True, window=window, prefix_len=prefix_len, impl=impl)
    k_cache, v_cache = cache
    S_buf = k_cache.shape[1]
    if window and S_buf == window:
        # ring buffer: keep the last `window` entries at slots pos % window
        take = min(window, S)
        tail_pos = jnp.arange(S - take, S)
        slots = tail_pos % window
        k_cache = k_cache.at[:, slots].set(k[:, S - take :].astype(k_cache.dtype))
        v_cache = v_cache.at[:, slots].set(v[:, S - take :].astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, 0, 0, 0))
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, (k_cache, v_cache)


def tail_prefill_attention(
    cfg: ArchConfig, p, x, cache: Tuple[jax.Array, jax.Array], offset, *, window: int = 0
):
    """Prefill of a sequence *tail* against a cache whose first ``offset``
    positions are already filled (a shared prefix gathered from pool pages).

    x: (B, S_tail, d) — the uncached tail tokens, living at absolute
    positions [offset, offset + S_tail); cache: (k, v) each (B, S_buf, KV, hd)
    full-depth buffers (no ring — the paged layout keeps every position).
    New K/V is written at the tail's absolute positions — overwriting from
    the divergence point on, which is what makes a copied boundary page
    copy-on-WRITE — and the tail attends over the whole cache with causal
    masking at absolute positions (``q_offset``), so prefix keys are read
    without being recomputed. `offset` may be traced: one compiled unit
    serves every matched-prefix length of a given tail length.
    Returns (out (B, S_tail, d), new_cache).
    """
    B, S, d = x.shape
    offset = jnp.asarray(offset, dtype=jnp.int32)
    positions = offset + jnp.arange(S)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    k_cache, v_cache = cache
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, offset, 0, 0))
    # oracle impl always: blocked/flash assume a static q_offset and equal
    # q/kv lengths; the tail runs once per admission, not in the decode loop
    out = ops.attention(
        q, k_cache, v_cache, causal=True, window=window, q_offset=offset, impl="ref"
    )
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return proj, (k_cache, v_cache)


def decode_self_attention(
    cfg: ArchConfig,
    p,
    x,
    cache: Tuple[jax.Array, jax.Array],
    pos,
    *,
    window: int = 0,
):
    """One-token decode step. x: (B, 1, d); pos: scalar current position.
    Returns (out (B,1,d), new_cache)."""
    B, S1, d = x.shape
    positions = jnp.full((B, 1), pos)
    q, k, v = _project_qkv(cfg, p, x, positions)  # (B,1,H,hd)/(B,1,KV,hd)
    k_cache, v_cache = cache
    S_buf = k_cache.shape[1]
    slot = (pos % window) if window and S_buf == window else pos
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    # For ring buffers every slot holds an in-window position; validity is
    # handled by `pos` (ref.decode_attention masks slots > pos only when the
    # buffer is longer than the written range).
    eff_pos = jnp.minimum(pos, S_buf - 1)
    out = ops.decode_attention(q[:, 0], k_cache, v_cache, eff_pos, window=window)
    proj = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(x.dtype))
    return proj[:, None, :], (k_cache, v_cache)


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def init_cross_attention(pb: ParamBuilder, cfg: ArchConfig, n_layers: Optional[int] = None):
    return init_attention(pb, cfg, n_layers)


def cross_attention_kv(cfg: ArchConfig, p, enc_out):
    """Precompute encoder K/V once per sequence. enc_out: (B, T, d)."""
    cd = enc_out.dtype
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"].astype(cd))
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"].astype(cd))
    return k, v


def cross_attention(cfg: ArchConfig, p, x, enc_kv):
    """x: (B,S,d) attends to precomputed encoder K/V (no mask, no RoPE)."""
    cd = x.dtype
    k, v = enc_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    impl = _impl(cfg)
    out = ops.attention(q, k, v, causal=False, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, *, window: int = 0, dtype=jnp.bfloat16):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    S_buf = min(window, max_len) if window else max_len
    shape = (batch, S_buf, KV, hd)
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


# ---------------------------------------------------------------------------
# paged KV cache: block-pool layout + paged decode attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of a paged KV cache.

    Every layer shares one growing page table (``n_pages_seq`` logical
    pages per slot) over a pool of ``num_pages`` physical pages; physical
    page 0 is the *null page* — never allocated, it absorbs the masked
    writes of inactive slots and pads unallocated table entries.

    Sliding-window layers page exactly like full layers: every position of
    every layer lives in dynamically-tabled pool pages, so one page-table
    row describes a whole prefix and matched prefixes can be forked by
    reference. The decode attention enforces ``window`` by *masking* (the
    usual price of prefix caching on sliding-window models: local-layer KV
    is kept for all positions, not just the last ``window``).
    """

    max_slots: int
    page_size: int
    cache_len: int  # max_len rounded up to a page multiple
    n_pages_seq: int  # page-table width (logical pages per slot)
    num_pages: int  # physical pages, null page included
    window: int

    def pages_for(self, n_positions: int) -> int:
        """Table pages needed to hold `n_positions` cache positions."""
        return -(-min(n_positions, self.cache_len) // self.page_size)


def paged_layout(
    cfg: ArchConfig,
    *,
    max_slots: int,
    max_len: int,
    page_size: int,
    num_pages: Optional[int] = None,
) -> PagedLayout:
    cache_len = -(-max_len // page_size) * page_size
    n_pages_seq = cache_len // page_size
    if num_pages is None:
        # default: every slot can hold a full-length sequence (same ceiling
        # as the dense cache) + the null page
        num_pages = max_slots * n_pages_seq + 1
    return PagedLayout(
        max_slots=max_slots,
        page_size=page_size,
        cache_len=cache_len,
        n_pages_seq=n_pages_seq,
        num_pages=int(num_pages),
        window=cfg.sliding_window or 0,
    )


def init_paged_kv_pool(cfg: ArchConfig, n_pages: int, page_size: int, *, dtype=jnp.bfloat16):
    """One layer's (k, v) block-pool tensors: (n_pages, page, KV, hd)."""
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_pages, page_size, KV, hd)
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


def paged_decode_self_attention(
    cfg: ArchConfig,
    p,
    x,
    pool_k,
    pool_v,
    table,
    pos,
    active,
    *,
    page_size: int,
    window: int = 0,
    layer=None,
):
    """One-token decode step against a paged KV pool, natively batched.

    x: (B, 1, d); pool_k/v: (P, page, KV, hd) — this layer's block pool,
    shared by all slots — or, with `layer` (may be traced), the stacked
    (L, P, page, KV, hd) pools of a layer loop, written and read at that
    layer in place; table: (B, n_pages) logical->physical page map;
    pos: (B,) per-slot positions; active: (B,) bool — inactive slots have
    their K/V writes routed to the null page, so they can never corrupt a
    live slot's cache.

    Writes go straight through the table, one slot per position. `window`
    > 0 (a sliding-window layer) masks logical slots older than
    ``pos - window`` inside the attention: the attended set of a ring
    buffer, with every position kept addressable so prefixes can be shared.
    Returns (out (B,1,d), (pool_k, pool_v)).
    """
    B = x.shape[0]
    positions = pos[:, None]  # (B, 1) — RoPE at each slot's own position
    q, k, v = _project_qkv(cfg, p, x, positions)  # (B,1,H,hd)/(B,1,KV,hd)

    cache_pos = jnp.where(active, pos, 0)
    page_idx = cache_pos // page_size
    offset = cache_pos % page_size
    phys = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    # inactive slots write the null page (their table rows may reference
    # pages since freed and reallocated)
    phys = jnp.where(active, phys, 0)
    rows = (phys, offset) if layer is None else (layer, phys, offset)
    pool_k = pool_k.at[rows].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[rows].set(v[:, 0].astype(pool_v.dtype))

    # inactive slots pass -1, so the Pallas kernel walks none of their pages
    S_eff = table.shape[1] * page_size
    eff_pos = jnp.where(active, jnp.minimum(pos, S_eff - 1), -1)
    out = ops.paged_decode_attention(
        q[:, 0], pool_k, pool_v, table, eff_pos, layer, window=window
    )
    proj = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(x.dtype))
    return proj[:, None, :], (pool_k, pool_v)
