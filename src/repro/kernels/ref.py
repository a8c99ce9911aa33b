"""Pure-jnp reference oracles for every Pallas kernel.

These are the semantics of record: each Pallas kernel in this package must
match its oracle to tolerance across shape/dtype sweeps (tests/test_kernels).
They are also the implementation used on CPU (tests, smoke runs) and inside
the dry-run lowering (the XLA path — kernels swap in on real TPU).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN from all-masked rows


def _build_mask(
    q_len: int,
    kv_len: int,
    *,
    causal: bool,
    window: int,
    prefix_len: int,
    q_offset,
):
    """Boolean (q_len, kv_len) mask. True = attend.

    * causal: key_pos <= query_pos (query_pos = q_offset + i)
    * window > 0: additionally query_pos - key_pos < window
    * prefix_len > 0: positions < prefix_len attend bidirectionally within
      the prefix (prefix-LM, used by the VLM vision prefix)
    """
    q_pos = q_offset + jnp.arange(q_len)[:, None]  # (q,1)
    k_pos = jnp.arange(kv_len)[None, :]  # (1,k)
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        mask = k_pos <= q_pos
        if prefix_len > 0:
            both_prefix = (q_pos < prefix_len) & (k_pos < prefix_len)
            mask = mask | both_prefix
    # `window` may be a traced scalar (per-layer window array under
    # scan-over-layers); window <= 0 means "no window".
    if window is not None and not (isinstance(window, int) and window <= 0):
        w = jnp.asarray(window)
        mask = mask & ((w <= 0) | (q_pos - k_pos < w))
    return mask


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    q_offset=0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
):
    """Masked multi-head attention with GQA.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H % KV == 0.
    Returns (B, Sq, H, hd). Softmax in fp32.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads for GQA
    qg = qf.reshape(B, Sq, KV, groups, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kf)  # (B,KV,g,Sq,Skv)

    mask = _build_mask(Sq, Skv, causal=causal, window=window, prefix_len=prefix_len, q_offset=q_offset)
    if kv_valid_len is not None:
        valid = jnp.arange(Skv)[None, :] < jnp.asarray(kv_valid_len).reshape(-1, 1)  # (B,Skv)
        mask = mask[None, :, :] & valid[:, None, :]
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(
    q,
    k_cache,
    v_cache,
    pos,
    *,
    window: int = 0,
    scale: Optional[float] = None,
):
    """Single-token attention over a KV cache.

    q: (B, H, hd); k_cache, v_cache: (B, S, KV, hd); pos: scalar or (B,)
    current position (the cache holds entries for positions <= pos).

    For windowed layers the cache is a ring buffer of size S == window and
    every entry is in-window by construction; validity is slots <= pos.
    Returns (B, H, hd).
    """
    return _masked_decode(q, k_cache, v_cache, pos, window=0, scale=scale)


def _masked_decode(q, k_cache, v_cache, pos, *, window, scale):
    """Shared single-token GQA decode body: slot-validity masking
    (slot <= pos), plus an optional position-window mask (slot > pos -
    window) for dynamically-tabled sliding-window layers. One copy of the
    scaled-dot-product/softmax/einsum oracle serves both the dense ring path
    (window=0 — validity only) and the paged path."""
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))

    qf = q.astype(jnp.float32).reshape(B, KV, groups, hd) * scale
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf)  # (B,KV,g,S)

    slot = jnp.arange(S)[None, :]  # (1,S)
    valid = slot <= pos[:, None]
    if window is not None and not (isinstance(window, int) and window <= 0):
        w = jnp.asarray(window)
        valid = valid & ((w <= 0) | (slot > pos[:, None] - w))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vf)
    return out.reshape(B, H, hd).astype(q.dtype)


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
):
    """Single-token attention over a paged (block-pool) KV cache.

    q: (B, H, hd); k_pool, v_pool: (P, page, KV, hd) — pages shared by every
    sequence — or (L, P, page, KV, hd) with `layer` naming the layer read;
    page_table: (B, n_pages) int32 physical page per logical page;
    pos: scalar or (B,) last valid logical slot.

    `window` > 0 additionally masks logical slots older than
    ``pos - window`` — the paged layout pages every position of a
    sliding-window layer through the table instead of a ring, so the window
    must be enforced by position masking here.

    Semantics of record for the Pallas paged kernel: gather each sequence's
    pages into a dense (n_pages*page) view, then run the dense decode oracle
    with slot-validity masking — padded table entries (null page 0) sit past
    `pos` and mask away, so no special-casing is needed. Returns (B, H, hd).
    """
    B = q.shape[0]
    if layer is not None:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    _, page, KV, hd = k_pool.shape
    k_eff = k_pool[page_table].reshape(B, -1, KV, hd)
    v_eff = v_pool[page_table].reshape(B, -1, KV, hd)
    return _masked_decode(q, k_eff, v_eff, pos, window=window, scale=scale)


def gated_linear_scan(
    q,
    k,
    v,
    log_a,
    *,
    chunk: int = 128,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunkwise gated linear recurrence (SSD / mLSTM matrix-memory core).

        S_t = a_t * S_{t-1} + k_t^T v_t          (state: (dk, dv))
        y_t = q_t @ S_t

    q,k: (B, H, S, dk); v: (B, H, S, dv); log_a: (B, H, S) per-step log decay
    (a_t = exp(log_a_t), log_a <= 0 for stability).
    Returns (y: (B,H,S,dv), final_state: (B,H,dk,dv)).

    The mLSTM normalizer track n_t = a_t n_{t-1} + k_t is obtained by calling
    this with v = ones(..., 1) (models/ssm.py does so).
    """
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    assert S % chunk == 0, f"seq {S} must be divisible by chunk {chunk}"
    C = S // chunk

    qf = q.astype(jnp.float32).reshape(B, H, C, chunk, dk)
    kf = k.astype(jnp.float32).reshape(B, H, C, chunk, dk)
    vf = v.astype(jnp.float32).reshape(B, H, C, chunk, dv)
    la = log_a.astype(jnp.float32).reshape(B, H, C, chunk)

    # within-chunk cumulative decay: A[i] = sum_{t<=i} log_a[t]
    A = jnp.cumsum(la, axis=-1)  # (B,H,C,L)
    A_total = A[..., -1]  # (B,H,C)

    # intra-chunk: y_intra[i] = sum_{j<=i} exp(A_i - A_j) (q_i.k_j) v_j
    decay_ij = A[..., :, None] - A[..., None, :]  # (B,H,C,L,L)
    tri = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    gates = jnp.where(tri, jnp.exp(decay_ij), 0.0)
    scores = jnp.einsum("bhcid,bhcjd->bhcij", qf, kf) * gates
    y_intra = jnp.einsum("bhcij,bhcjv->bhciv", scores, vf)

    # per-chunk outer-product contribution to the carried state:
    #   S_chunk = sum_j exp(A_total - A_j) k_j^T v_j
    k_scaled = kf * jnp.exp(A_total[..., None] - A)[..., None]
    chunk_states = jnp.einsum("bhcjd,bhcjv->bhcdv", k_scaled, vf)  # (B,H,C,dk,dv)

    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), dtype=jnp.float32)
    else:
        initial_state = initial_state.astype(jnp.float32)

    def step(carry, xs):
        S_prev = carry
        chunk_state, a_tot = xs
        S_new = jnp.exp(a_tot)[..., None, None] * S_prev + chunk_state
        return S_new, S_prev

    # scan over chunks: move chunk axis first
    xs = (
        jnp.moveaxis(chunk_states, 2, 0),  # (C,B,H,dk,dv)
        jnp.moveaxis(A_total, 2, 0),  # (C,B,H)
    )
    final_state, prev_states = jax.lax.scan(step, initial_state, xs)
    prev_states = jnp.moveaxis(prev_states, 0, 2)  # (B,H,C,dk,dv)

    # inter-chunk: y_inter[i] = exp(A_i) q_i @ S_prev(chunk)
    q_scaled = qf * jnp.exp(A)[..., None]
    y_inter = jnp.einsum("bhcid,bhcdv->bhciv", q_scaled, prev_states)

    y = (y_intra + y_inter).reshape(B, H, S, dv)
    return y.astype(q.dtype), final_state


def gated_linear_step(q_t, k_t, v_t, log_a_t, state):
    """Single decode step of the gated linear recurrence.

    q_t,k_t: (B,H,dk); v_t: (B,H,dv); log_a_t: (B,H); state: (B,H,dk,dv).
    Returns (y_t: (B,H,dv), new_state).
    """
    a = jnp.exp(log_a_t.astype(jnp.float32))[..., None, None]
    new_state = a * state.astype(jnp.float32) + jnp.einsum(
        "bhd,bhv->bhdv", k_t.astype(jnp.float32), v_t.astype(jnp.float32)
    )
    y = jnp.einsum("bhd,bhdv->bhv", q_t.astype(jnp.float32), new_state)
    return y.astype(q_t.dtype), new_state
