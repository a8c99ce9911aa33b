"""Pallas TPU flash-decode: single-token attention over a (possibly very
long) KV cache.

Decode is memory-bound (the whole cache streams HBM→VMEM once per step);
the kernel therefore tiles the cache sequence dimension and keeps the
online-softmax state in VMEM scratch, touching each cache byte exactly once.
Slots beyond `pos` are masked (ring buffers for windowed layers are fully
valid by construction once warm). Positions ride in as a scalar-prefetch
operand, read from SMEM by the body, as in `paged_decode_attention.py`.
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


def _block_len(S: int, target: int) -> int:
    """Largest divisor of S that is at most `target` and a multiple of 16
    (the bf16 sublane tile); S itself when it has none (a full dimension
    is always a legal block)."""
    for bs in range(min(target, S), 15, -1):
        if S % bs == 0 and bs % 16 == 0:
            return bs
    return S


def _kernel(
    pos_ref,  # scalar prefetch: positions (B,)
    q_ref,  # (1, 1, groups, hd)
    k_ref,  # (1, 1, bs, hd)
    v_ref,  # (1, 1, bs, hd)
    o_ref,  # (1, 1, groups, hd)
    m_scr, l_scr, acc_scr,  # (groups,1),(groups,1),(groups,hd)
    *,
    scale: float,
    groups: int,
    block_s: int,
    num_s_blocks: int,
):
    b = pl.program_id(0)
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (groups, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (groups, bs)

    slot = isb * block_s + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], block_s), 1)
    s = jnp.where(slot <= pos, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(isb == num_s_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(
    q,
    k_cache,
    v_cache,
    pos,
    *,
    scale: Optional[float] = None,
    block_s: int = 512,
    interpret: Optional[bool] = None,
):
    """q: (B, H, hd); k/v_cache: (B, S, KV, hd); pos: scalar or (B,).
    Returns (B, H, hd). The per-KV-head grid dim lets GQA share cache blocks
    across the q-head group without replication. The cache is walked in
    blocks of the largest 16-aligned divisor of S up to `block_s`."""
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    block_s = _block_len(S, block_s)
    ns = S // block_s

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, dtype=jnp.int32), (B,))
    # layout: (B, KV, S, hd) so cache blocks are (seq, head_dim)-minor
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    # group q heads by kv head: (B, KV, groups, hd)
    qg = q.reshape(B, KV, groups, hd)

    kernel = functools.partial(
        _kernel, scale=scale, groups=groups, block_s=block_s, num_s_blocks=ns
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec((1, 1, groups, hd), lambda b, h, i, ps: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, i, ps: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_s, hd), lambda b, h, i, ps: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, hd), lambda b, h, i, ps: (b, h, 0, 0)),
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
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode() if interpret is None else interpret,
    )(pos_arr, qg, kt, vt)
    return out.reshape(B, H, hd)
