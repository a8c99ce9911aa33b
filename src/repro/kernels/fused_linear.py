"""Pallas TPU fused linear layer: y = act(x @ W + b).

The compute hot-spot of the paper's Test Case 2 (heterogeneous inference):
each HiCR backend supplies its own kernel implementation (OpenBLAS / ACL /
naive OpenCL in the paper; XLA-jnp vs Pallas here). Tiled (bm × bn × bk)
with an fp32 VMEM accumulator carried across the sequential K grid dim —
MXU-aligned 128-multiples by default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import interpret_mode


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_scr, *, act: str, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32)
    )

    @pl.when(ik == nk - 1)
    def _finish():
        y = acc_scr[...] + b_ref[...].astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        elif act == "gelu":
            y = jax.nn.gelu(y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_m", "block_n", "block_k", "interpret"))
def fused_linear(
    x, w, b, *, act: str = "none",
    block_m: int = 128, block_n: int = 128, block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """x: (M, K); w: (K, N); b: (N,) -> (M, N)."""
    M, K = x.shape
    _, N = w.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (x.shape, w.shape, bm, bn, bk)
    nk = K // bk

    kernel = functools.partial(_kernel, act=act, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode() if interpret is None else interpret,
    )(x, w, b.reshape(1, N))


def fused_linear_ref(x, w, b, *, act: str = "none"):
    y = x.astype(jnp.float32) @ w.astype(jnp.float32) + b.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "gelu":
        y = jax.nn.gelu(y)
    return y.astype(x.dtype)
