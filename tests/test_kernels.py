"""Pallas kernels vs pure-jnp oracles (interpreted off a TPU).

Per instructions: sweep shapes/dtypes per kernel and assert_allclose against
the ref.py oracle; hypothesis drives randomized shape/value generation for
the system's numeric invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # missing dep: property tests skip, the rest still run
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def assert_close(a, b, dtype):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# flash attention (fused online-softmax; causal / window / GQA / prefix)
# ---------------------------------------------------------------------------


ATTN_SHAPES = [
    # (B, S, H, KV, hd)
    (1, 128, 1, 1, 64),
    (2, 256, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA 4:1
    (1, 512, 4, 1, 128),   # MQA, MXU-aligned head_dim
    (1, 384, 6, 2, 32),    # non-pow2 seq multiple of block
]


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,hd", ATTN_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_oracle(self, B, S, H, KV, hd, dtype):
        k = jax.random.PRNGKey(hash((B, S, H, KV, hd)) % 2**31)
        q = _rand(k, (B, S, H, hd), dtype)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, KV, hd), dtype)
        v = _rand(jax.random.fold_in(k, 2), (B, S, KV, hd), dtype)
        got = ops.attention(q, kk, v, causal=True, impl="pallas")
        want = ref.attention(q, kk, v, causal=True)
        assert_close(got, want, dtype)

    @pytest.mark.parametrize("window", [64, 128, 256])
    def test_sliding_window(self, window):
        """gemma3's local layers: query attends to the last `window` keys."""
        k = jax.random.PRNGKey(0)
        B, S, H, hd = 1, 512, 4, 64
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        got = ops.attention(q, kk, v, causal=True, window=window, impl="pallas")
        want = ref.attention(q, kk, v, causal=True, window=window)
        assert_close(got, want, jnp.float32)

    def test_window_equals_full_when_large(self):
        k = jax.random.PRNGKey(3)
        B, S, H, hd = 1, 128, 2, 32
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        full = ref.attention(q, kk, v, causal=True)
        windowed = ref.attention(q, kk, v, causal=True, window=S + 10)
        assert_close(windowed, full, jnp.float32)

    def test_prefix_lm_mask(self):
        """VLM prefix: positions < prefix_len attend bidirectionally."""
        k = jax.random.PRNGKey(4)
        B, S, H, hd = 1, 256, 2, 64
        P = 64
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        got = ops.attention(q, kk, v, causal=True, prefix_len=P, impl="pallas")
        want = ref.attention(q, kk, v, causal=True, prefix_len=P)
        assert_close(got, want, jnp.float32)
        # prefix really is bidirectional: output at pos 0 differs from causal
        causal_only = ref.attention(q, kk, v, causal=True)
        assert not np.allclose(np.asarray(want[:, 0]), np.asarray(causal_only[:, 0]))

    def test_q_offset_chunked_equals_full(self):
        """Chunked prefill invariant: attending with q_offset must equal the
        corresponding rows of the full computation."""
        k = jax.random.PRNGKey(5)
        B, S, H, hd = 1, 256, 2, 64
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        full = ref.attention(q, kk, v, causal=True)
        half = S // 2
        part = ref.attention(q[:, half:], kk, v, causal=True, q_offset=half)
        assert_close(part, full[:, half:], jnp.float32)

    @settings(max_examples=20, deadline=None)
    @given(
        S=st.sampled_from([128, 256]),
        H=st.sampled_from([2, 4]),
        groups=st.sampled_from([1, 2]),
        seed=st.integers(0, 2**16),
    )
    def test_rows_are_convex_combinations(self, S, H, groups, seed):
        """Property: each attention output is a convex combination of value
        rows -> bounded by [min(v), max(v)] per feature."""
        KV = H // groups
        k = jax.random.PRNGKey(seed)
        q = _rand(k, (1, S, H, 32), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (1, S, KV, 32), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (1, S, KV, 32), jnp.float32)
        out = np.asarray(ref.attention(q, kk, v, causal=True))
        vmin, vmax = np.asarray(v).min(), np.asarray(v).max()
        assert out.min() >= vmin - 1e-4 and out.max() <= vmax + 1e-4


# ---------------------------------------------------------------------------
# decode attention (flash-decode over a KV cache)
# ---------------------------------------------------------------------------


DECODE_SHAPES = [
    # (B, S, H, KV, hd)
    (1, 512, 4, 4, 64),
    (2, 1024, 8, 2, 64),
    (4, 2048, 8, 1, 128),
]


class TestDecodeAttention:
    @pytest.mark.parametrize("B,S,H,KV,hd", DECODE_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, S, H, KV, hd, dtype):
        k = jax.random.PRNGKey(hash((B, S, H)) % 2**31)
        q = _rand(k, (B, H, hd), dtype)
        kc = _rand(jax.random.fold_in(k, 1), (B, S, KV, hd), dtype)
        vc = _rand(jax.random.fold_in(k, 2), (B, S, KV, hd), dtype)
        pos = jnp.int32(S // 2)
        got = ops.decode_attention(q, kc, vc, pos, impl="pallas")
        want = ref.decode_attention(q, kc, vc, pos)
        assert_close(got, want, dtype)

    def test_per_batch_positions(self):
        B, S, H, hd = 3, 512, 4, 64
        k = jax.random.PRNGKey(9)
        q = _rand(k, (B, H, hd), jnp.float32)
        kc = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        vc = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        pos = jnp.array([10, 200, 511], jnp.int32)
        got = ops.decode_attention(q, kc, vc, pos, impl="pallas")
        want = ref.decode_attention(q, kc, vc, pos)
        assert_close(got, want, jnp.float32)

    def test_masking_is_effective(self):
        """Entries beyond pos must not affect the result."""
        B, S, H, hd = 1, 256, 2, 32
        k = jax.random.PRNGKey(11)
        q = _rand(k, (B, H, hd), jnp.float32)
        kc = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        vc = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        pos = jnp.int32(100)
        base = ref.decode_attention(q, kc, vc, pos)
        kc2 = kc.at[:, 101:].set(999.0)
        vc2 = vc.at[:, 101:].set(-999.0)
        poisoned = ref.decode_attention(q, kc2, vc2, pos)
        assert_close(poisoned, base, jnp.float32)

    def test_decode_consistent_with_full_attention(self):
        """The decode step at position p equals row p of full causal
        attention (the serving-path correctness invariant)."""
        B, S, H, hd = 1, 128, 2, 32
        k = jax.random.PRNGKey(12)
        q_full = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        full = ref.attention(q_full, kk, v, causal=True)
        p = S - 1
        dec = ref.decode_attention(q_full[:, p], kk, vc_cache := v, jnp.int32(p))
        assert_close(dec, full[:, p], jnp.float32)


# ---------------------------------------------------------------------------
# gated linear scan (SSD / mLSTM chunkwise recurrence)
# ---------------------------------------------------------------------------


SCAN_SHAPES = [
    # (B, H, S, dk, dv, chunk)
    (1, 1, 128, 32, 32, 64),
    (2, 4, 256, 64, 64, 128),
    (1, 2, 256, 16, 64, 64),   # dk != dv (Mamba2 shape)
    (2, 2, 512, 32, 16, 128),
]


class TestGatedLinearScan:
    @pytest.mark.parametrize("B,H,S,dk,dv,chunk", SCAN_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, H, S, dk, dv, chunk, dtype):
        k = jax.random.PRNGKey(hash((B, H, S, dk, dv)) % 2**31)
        q = _rand(k, (B, H, S, dk), dtype, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), dtype, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), dtype, 0.5)
        la = -jax.nn.softplus(
            jax.random.normal(jax.random.fold_in(k, 3), (B, H, S), jnp.float32)
        )
        y1, s1 = ops.gated_linear_scan(q, kk, v, la, chunk=chunk, impl="pallas")
        y2, s2 = ref.gated_linear_scan(q, kk, v, la, chunk=chunk)
        assert_close(y1, y2, dtype)
        assert_close(s1, s2, dtype)

    def test_chunk_size_invariance(self):
        """The chunk size is a performance knob; results must not change."""
        B, H, S, dk, dv = 1, 2, 256, 32, 32
        k = jax.random.PRNGKey(21)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5)
        la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 3), (B, H, S)))
        y64, s64 = ref.gated_linear_scan(q, kk, v, la, chunk=64)
        y128, s128 = ref.gated_linear_scan(q, kk, v, la, chunk=128)
        assert_close(y64, y128, jnp.float32)
        assert_close(s64, s128, jnp.float32)

    def test_chunked_equals_stepwise(self):
        """The chunkwise kernel must equal the naive per-step recurrence —
        the train/decode consistency invariant for SSM archs."""
        B, H, S, dk, dv = 1, 2, 64, 16, 16
        k = jax.random.PRNGKey(22)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5)
        la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 3), (B, H, S)))
        y_chunk, s_chunk = ref.gated_linear_scan(q, kk, v, la, chunk=32)
        state = jnp.zeros((B, H, dk, dv))
        ys = []
        for t in range(S):
            y_t, state = ref.gated_linear_step(q[:, :, t], kk[:, :, t], v[:, :, t], la[:, :, t], state)
            ys.append(y_t)
        y_step = jnp.stack(ys, axis=2)
        assert_close(y_chunk, y_step, jnp.float32)
        assert_close(s_chunk, state, jnp.float32)

    def test_initial_state_continuation(self):
        """Splitting a sequence and carrying the state must equal one scan —
        the chunked-prefill invariant."""
        B, H, S, dk, dv = 1, 1, 128, 16, 16
        k = jax.random.PRNGKey(23)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5)
        la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 3), (B, H, S)))
        y_full, s_full = ref.gated_linear_scan(q, kk, v, la, chunk=32)
        h = S // 2
        y1, s1 = ref.gated_linear_scan(q[:, :, :h], kk[:, :, :h], v[:, :, :h], la[:, :, :h], chunk=32)
        y2, s2 = ref.gated_linear_scan(
            q[:, :, h:], kk[:, :, h:], v[:, :, h:], la[:, :, h:], chunk=32, initial_state=s1
        )
        assert_close(jnp.concatenate([y1, y2], axis=2), y_full, jnp.float32)
        assert_close(s2, s_full, jnp.float32)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), decay=st.floats(0.0, 5.0))
    def test_state_norm_bounded_under_decay(self, seed, decay):
        """Property: with log_a <= -decay and bounded inputs, the state norm
        is bounded by ||k||·||v||/(1-exp(-decay)) — no unbounded growth."""
        B, H, S, dk, dv = 1, 1, 64, 8, 8
        k = jax.random.PRNGKey(seed)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.1)
        kk = jnp.clip(_rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5), -1, 1)
        v = jnp.clip(_rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5), -1, 1)
        la = jnp.full((B, H, S), -max(decay, 1e-2))
        _, state = ref.gated_linear_scan(q, kk, v, la, chunk=32)
        per_step_max = float(np.sqrt(dk * dv))  # |k_t^T v_t| bound, entries in [-1,1]
        geo = 1.0 / (1.0 - np.exp(-max(decay, 1e-2)))
        assert float(jnp.linalg.norm(state)) <= per_step_max * geo + 1e-3


# ---------------------------------------------------------------------------
# fused_linear kernel (if present in kernels/): matmul+bias+act fusion
# ---------------------------------------------------------------------------


class TestFusedLinear:
    def test_matches_jnp(self):
        from repro.kernels import fused_linear

        k = jax.random.PRNGKey(31)
        M, K, N = 256, 128, 256
        x = _rand(k, (M, K), jnp.float32, 0.3)
        w = _rand(jax.random.fold_in(k, 1), (K, N), jnp.float32, 0.3)
        b = _rand(jax.random.fold_in(k, 2), (N,), jnp.float32, 0.3)
        got = fused_linear.fused_linear(x, w, b, act="gelu", interpret=True)
        want = fused_linear.fused_linear_ref(x, w, b, act="gelu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("act", ["none", "relu", "gelu"])
    @pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128)])
    def test_activations_and_tilings(self, act, shape):
        from repro.kernels import fused_linear

        M, K, N = shape
        k = jax.random.PRNGKey(32)
        x = _rand(k, (M, K), jnp.float32, 0.3)
        w = _rand(jax.random.fold_in(k, 1), (K, N), jnp.float32, 0.3)
        b = _rand(jax.random.fold_in(k, 2), (N,), jnp.float32, 0.3)
        got = fused_linear.fused_linear(x, w, b, act=act, interpret=True)
        want = fused_linear.fused_linear_ref(x, w, b, act=act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# blocked (flash-style XLA) attention — the beyond-paper §Perf kernel
# ---------------------------------------------------------------------------


class TestBlockedAttention:
    @pytest.mark.parametrize("B,S,H,KV,hd", [
        (1, 256, 2, 2, 32),
        (2, 1024, 8, 2, 64),
        (1, 384, 6, 2, 32),   # non-pow2 seq: chunk divisor fallback
    ])
    def test_causal_matches_oracle(self, B, S, H, KV, hd):
        k = jax.random.PRNGKey(hash((B, S, H)) % 2**31)
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, KV, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, KV, hd), jnp.float32)
        got = ops.attention(q, kk, v, causal=True, impl="blocked")
        want = ref.attention(q, kk, v, causal=True)
        assert_close(got, want, jnp.float32)

    @pytest.mark.parametrize("window", [64, 250, 512, 1000])
    def test_sliding_window_band(self, window):
        k = jax.random.PRNGKey(7)
        B, S, H, hd = 1, 1024, 4, 32
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        got = ops.attention(q, kk, v, causal=True, window=window, impl="blocked")
        want = ref.attention(q, kk, v, causal=True, window=window)
        assert_close(got, want, jnp.float32)

    def test_prefix_and_noncausal(self):
        k = jax.random.PRNGKey(8)
        B, S, H, hd = 1, 512, 2, 32
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        for kwargs in (dict(causal=True, prefix_len=96), dict(causal=False)):
            got = ops.attention(q, kk, v, impl="blocked", **kwargs)
            want = ref.attention(q, kk, v, **kwargs)
            assert_close(got, want, jnp.float32)

    def test_gradients_match_oracle(self):
        """The checkpointed backward (recompute blocks) must be exact."""
        k = jax.random.PRNGKey(9)
        B, S, H, hd = 1, 512, 2, 32
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)
        for wargs in (dict(), dict(window=128)):
            g1 = jax.grad(lambda q: ops.attention(q, kk, v, causal=True, impl="blocked", **wargs).sum())(q)
            g2 = jax.grad(lambda q: ref.attention(q, kk, v, causal=True, **wargs).sum())(q)
            assert_close(g1, g2, jnp.float32)

    def test_traced_window_falls_back_to_oracle(self):
        """Scan-stacked per-layer windows are traced values: the dispatcher
        must fall back to ref (blocked needs static bands)."""
        k = jax.random.PRNGKey(10)
        B, S, H, hd = 1, 128, 2, 32
        q = _rand(k, (B, S, H, hd), jnp.float32)
        kk = _rand(jax.random.fold_in(k, 1), (B, S, H, hd), jnp.float32)
        v = _rand(jax.random.fold_in(k, 2), (B, S, H, hd), jnp.float32)

        def f(w):
            return ops.attention(q, kk, v, causal=True, window=w, impl="blocked")

        got = jax.jit(f)(jnp.int32(64))  # traced -> oracle path
        want = ref.attention(q, kk, v, causal=True, window=64)
        assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# sequential-chunk SSD scan — the zamba2 §Perf kernel
# ---------------------------------------------------------------------------


class TestSequentialSSD:
    @pytest.mark.parametrize("B,H,S,dk,dv,chunk", [
        (1, 2, 128, 16, 16, 64),
        (2, 3, 256, 16, 32, 64),
        (1, 1, 512, 32, 64, 128),
    ])
    def test_matches_oracle(self, B, H, S, dk, dv, chunk):
        k = jax.random.PRNGKey(hash((B, H, S, dk)) % 2**31)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5)
        la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 3), (B, H, S)))
        y1, s1 = ops.gated_linear_scan(q, kk, v, la, chunk=chunk, impl="sequential")
        y2, s2 = ops.gated_linear_scan(q, kk, v, la, chunk=chunk, impl="ref")
        assert_close(y1, y2, jnp.float32)
        assert_close(s1, s2, jnp.float32)

    def test_initial_state_and_gradients(self):
        B, H, S, dk, dv = 1, 2, 128, 16, 16
        k = jax.random.PRNGKey(42)
        q = _rand(k, (B, H, S, dk), jnp.float32, 0.5)
        kk = _rand(jax.random.fold_in(k, 1), (B, H, S, dk), jnp.float32, 0.5)
        v = _rand(jax.random.fold_in(k, 2), (B, H, S, dv), jnp.float32, 0.5)
        la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 3), (B, H, S)))
        s0 = _rand(jax.random.fold_in(k, 4), (B, H, dk, dv), jnp.float32, 0.1)
        y1, f1 = ops.gated_linear_scan(q, kk, v, la, chunk=32, initial_state=s0, impl="sequential")
        y2, f2 = ops.gated_linear_scan(q, kk, v, la, chunk=32, initial_state=s0, impl="ref")
        assert_close(y1, y2, jnp.float32)
        assert_close(f1, f2, jnp.float32)
        g1 = jax.grad(lambda v: ops.gated_linear_scan(q, kk, v, la, chunk=32, impl="sequential")[0].sum())(v)
        g2 = jax.grad(lambda v: ops.gated_linear_scan(q, kk, v, la, chunk=32, impl="ref")[0].sum())(v)
        assert_close(g1, g2, jnp.float32)


# ---------------------------------------------------------------------------
# ambient sharding constraints (no-op without a mesh; divisibility guard)
# ---------------------------------------------------------------------------


class TestAmbientConstrain:
    def test_noop_without_mesh(self):
        from repro.sharding.ambient import constrain

        x = jnp.ones((4, 4))
        assert constrain(x, "data") is x

    def test_respects_divisibility_with_mesh(self):
        import numpy as np
        from jax.sharding import Mesh

        from repro.sharding.ambient import active_mesh, constrain

        mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
        with active_mesh(mesh):
            x = jnp.ones((6, 4))
            y = constrain(x, ("pod", "data"), "model")  # pod absent -> dropped
            assert y.shape == x.shape
