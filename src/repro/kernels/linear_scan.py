"""Pallas TPU chunkwise gated linear recurrence (SSD / mLSTM matrix memory).

    S_t = a_t * S_{t-1} + k_t^T v_t ;  y_t = q_t @ S_t

TPU-native formulation: the sequence is tiled into chunks; within a chunk
the recurrence is expanded into two MXU matmuls (intra-chunk "attention
score" path and inter-chunk state read), while the carried (dk, dv) state
matrix lives in VMEM scratch across the sequential chunk grid dimension.
This replaces the GPU parallel-scan/warp-shuffle formulation with a
systolic-array-friendly one (DESIGN.md §7).

All decay math is done in log space in fp32; the state accumulates in fp32.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode


def _kernel(
    q_ref, k_ref, v_ref, la_ref,  # (1,1,L,dk) x2, (1,1,L,dv), (1,1,L,1)
    y_ref, sfin_ref,  # (1,1,L,dv), (1,1,dk,dv)
    state_scr,  # VMEM (dk, dv) fp32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (L, dv)
    la = la_ref[0, 0, :, 0].astype(jnp.float32)  # (L,)

    A = jnp.cumsum(la)  # (L,)
    a_tot = A[-1]

    # intra-chunk: scores_ij = (q_i . k_j) * exp(A_i - A_j), j <= i
    decay = A[:, None] - A[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (chunk, chunk), 1
    )
    gates = jnp.where(tri, jnp.exp(decay), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * gates  # (L, L)
    y = jax.lax.dot(scores, v)  # (L, dv)

    # inter-chunk: y_i += exp(A_i) * q_i @ S_prev
    S_prev = state_scr[...]
    y = y + jnp.exp(A)[:, None] * jax.lax.dot(q, S_prev)

    # state update: S = exp(a_tot) * S_prev + sum_j exp(a_tot - A_j) k_j^T v_j
    k_scaled = k * jnp.exp(a_tot - A)[:, None]
    state_scr[...] = jnp.exp(a_tot) * S_prev + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ()))
    )

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _finish():
        sfin_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gated_linear_scan(
    q,
    k,
    v,
    log_a,
    *,
    chunk: int = 128,
    initial_state=None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q,k: (B,H,S,dk); v: (B,H,S,dv); log_a: (B,H,S).
    Returns (y (B,H,S,dv), final_state (B,H,dk,dv) fp32).

    The Pallas path covers zero initial state (training/prefill-from-zero);
    ops.py falls back to the jnp oracle when carrying in a state."""
    if initial_state is not None:
        from . import ref

        return ref.gated_linear_scan(q, k, v, log_a, chunk=chunk, initial_state=initial_state)
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    la4 = log_a[..., None]  # (B,H,S,1)

    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode() if interpret is None else interpret,
    )(q, k, v, la4)
    return y, s_fin
