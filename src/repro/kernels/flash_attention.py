"""Pallas TPU flash attention with causal / sliding-window / prefix-LM
masking and native GQA.

TPU-native design (DESIGN.md §7):
* Online-softmax accumulation over K blocks; the K-block grid dimension is
  sequential ("arbitrary") so the running (max, denom, acc) live in VMEM
  scratch across iterations — the HBM→VMEM→MXU dataflow analogue of the
  GPU kernel's shared-memory tiling.
* Block shapes default to (128, 128): MXU-aligned on the matmul dims.
* GQA is handled in the K/V BlockSpec index_map (kv head = q head //
  group); the repeated-KV tensor is never materialized.
* q is laid out (B, H, S, hd) so the block minor dims are (seq, head_dim).

Validated in interpret mode against `ref.attention` (tests/test_kernels.py).
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
    q_ref, k_ref, v_ref,  # blocks: (1,1,bq,hd), (1,1,bk,hd), (1,1,bk,hd)
    o_ref,  # (1,1,bq,hd)
    m_scr, l_scr, acc_scr,  # VMEM scratch: (bq,1), (bq,1), (bq,hd)
    *,
    scale: float,
    causal: bool,
    window: int,
    prefix_len: int,
    q_offset: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    # ---- masking -----------------------------------------------------------
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        mask = k_pos <= q_pos
        if prefix_len > 0:
            mask = mask | ((q_pos < prefix_len) & (k_pos < prefix_len))
    if window > 0:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    # ---- online softmax ------------------------------------------------------
    m_prev = m_scr[...]  # (bq,1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq,1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq,bk)
    alpha = jnp.exp(m_prev - m_new)  # (bq,1)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0, :, :] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "prefix_len", "q_offset", "scale",
        "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """q: (B, Sq, H, hd); k,v: (B, Skv, KV, hd) -> (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    groups = H // KV
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    # (B, H, S, hd) layout: seq × head_dim minor
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        prefix_len=prefix_len,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // groups, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // groups, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode() if interpret is None else interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
