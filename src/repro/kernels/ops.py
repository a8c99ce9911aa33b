"""Dispatching wrappers for the kernel layer.

Models call these entry points; `impl` selects the implementation:

* ``"ref"``    — the pure-jnp oracle.
* ``"pallas"`` — the Pallas TPU kernel: compiled on a TPU, run by the
  Pallas interpreter on any other backend (`interpret_mode`).
* ``"blocked"`` / ``"sequential"`` — jnp variants that bound memory.

Where the serve path runs the Pallas kernels is decided here, from the
platform, by `decode_impl`; tests and benchmarks pass `impl` explicitly.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas kernels run compiled on a TPU and interpreted everywhere else."""
    return not on_tpu()


def decode_impl() -> str:
    """Single-token decode attention, dense and paged: the compiled Pallas
    kernels on a TPU, the jnp oracle elsewhere. Training and prefill
    attention stay on jnp on every platform: `flash_attention` (and the
    SSM `linear_scan` kernel) define no VJP, and prefill runs once per
    admission, not in the decode loop."""
    return "pallas" if on_tpu() else "ref"


def attention(q, k, v, *, causal=True, window=0, prefix_len=0, q_offset=0,
              kv_valid_len=None, scale=None, impl: str = "ref"):
    if impl == "pallas":
        from . import flash_attention

        # The Pallas kernel covers the self-attention fast path (no
        # kv_valid_len ragged masking); fall back to ref otherwise.
        if kv_valid_len is None:
            return flash_attention.flash_attention(
                q, k, v, causal=causal, window=window, prefix_len=prefix_len,
                q_offset=q_offset, scale=scale,
            )
    if impl == "blocked":
        from . import blocked

        # blocked path needs a static window; traced windows (scan-stacked
        # per-layer window arrays) and ragged kv fall back to the oracle.
        if kv_valid_len is None and isinstance(window, int):
            return blocked.attention_blocked(
                q, k, v, causal=causal, window=window, prefix_len=prefix_len,
                q_offset=q_offset, scale=scale,
            )
    return ref.attention(
        q, k, v, causal=causal, window=window, prefix_len=prefix_len,
        q_offset=q_offset, kv_valid_len=kv_valid_len, scale=scale,
    )


def decode_attention(q, k_cache, v_cache, pos, *, window=0, scale=None,
                     impl: Optional[str] = None):
    if (impl or decode_impl()) == "pallas":
        from . import decode_attention as da

        return da.decode_attention(q, k_cache, v_cache, pos, scale=scale)
    return ref.decode_attention(q, k_cache, v_cache, pos, window=window, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, layer=None, *, window=0,
                           scale=None, impl: Optional[str] = None):
    """`layer` given: the pools are the stacked (L, P, page, KV, hd) and the
    layer is read in place; else one layer's (P, page, KV, hd)."""
    if (impl or decode_impl()) == "pallas":
        from . import paged_decode_attention as pda

        return pda.paged_decode_attention(
            q, k_pool, v_pool, page_table, pos, layer, window=window, scale=scale
        )
    return ref.paged_decode_attention(
        q, k_pool, v_pool, page_table, pos, layer, window=window, scale=scale
    )


def gated_linear_scan(q, k, v, log_a, *, chunk: int = 128, initial_state=None, impl: str = "ref"):
    if impl == "pallas":
        from . import linear_scan

        return linear_scan.gated_linear_scan(
            q, k, v, log_a, chunk=chunk, initial_state=initial_state
        )
    if impl == "sequential":
        from . import blocked

        return blocked.gated_linear_scan_sequential(
            q, k, v, log_a, chunk=chunk, initial_state=initial_state
        )
    return ref.gated_linear_scan(q, k, v, log_a, chunk=chunk, initial_state=initial_state)


def gated_linear_step(q_t, k_t, v_t, log_a_t, state):
    return ref.gated_linear_step(q_t, k_t, v_t, log_a_t, state)
