"""Paged decode-attention: Pallas kernel parity vs the dense reference
(interpret mode — runs on CPU CI), the paged/dense oracle equivalence, the
page-pool accounting, and one-step paged-vs-dense model parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.managers import MemorySlotPool
from repro.kernels import ops, ref
from repro.models.attention import paged_layout


def _pool_case(key, *, B, H, KV, hd, page, n_pages, pool_pages, dtype):
    """Random pool + per-row page tables (distinct non-null pages)."""
    rng = np.random.default_rng(key)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((pool_pages, page, KV, hd)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((pool_pages, page, KV, hd)), dtype)
    table = np.stack(
        [rng.permutation(pool_pages - 1)[:n_pages] + 1 for _ in range(B)]
    ).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(table)


class TestPagedKernelParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref_uneven_lengths_partial_pages(self, dtype):
        """Per-row positions ending mid-page (partial last page) and at page
        boundaries, fp32 and bf16, GQA head grouping."""
        B, H, KV, hd, page, n = 4, 8, 2, 16, 8, 4
        q, kp, vp, tbl = _pool_case(0, B=B, H=H, KV=KV, hd=hd, page=page,
                                    n_pages=n, pool_pages=24, dtype=dtype)
        pos = jnp.asarray([0, 7, 12, 31], jnp.int32)  # 1 slot / boundary / mid / full
        got = ops.paged_decode_attention(q, kp, vp, tbl, pos, impl="pallas")
        want = ref.paged_decode_attention(q, kp, vp, tbl, pos)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
        )

    def test_matches_dense_reference_on_gathered_layout(self):
        """Paging is a layout change only: gathering a row's pages into a
        dense cache and running the dense oracle gives the same output."""
        B, H, KV, hd, page, n = 3, 4, 1, 16, 16, 3
        q, kp, vp, tbl = _pool_case(1, B=B, H=H, KV=KV, hd=hd, page=page,
                                    n_pages=n, pool_pages=16, dtype=jnp.float32)
        pos = jnp.asarray([5, 20, 47], jnp.int32)
        k_dense = kp[tbl].reshape(B, n * page, KV, hd)
        v_dense = vp[tbl].reshape(B, n * page, KV, hd)
        dense = ref.decode_attention(q, k_dense, v_dense, pos)
        for impl in ("ref", "pallas"):
            got = ops.paged_decode_attention(q, kp, vp, tbl, pos, impl=impl)
            np.testing.assert_allclose(np.asarray(got), np.asarray(dense), atol=1e-5)

    def test_null_page_padding_is_masked(self):
        """Table entries past the allocation are padded with the null page
        (0); whatever garbage it holds must never leak into the output."""
        B, H, KV, hd, page = 1, 4, 1, 16, 8
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((6, page, KV, hd)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((6, page, KV, hd)), jnp.float32)
        kp = kp.at[0].set(1e9)  # poison the null page
        vp = vp.at[0].set(1e9)
        tbl = jnp.asarray([[2, 4, 0, 0]], jnp.int32)  # 2 real pages, 2 padded
        pos = jnp.asarray([11], jnp.int32)
        for impl in ("ref", "pallas"):
            out = np.asarray(ops.paged_decode_attention(q, kp, vp, tbl, pos, impl=impl))
            assert np.all(np.isfinite(out)) and np.max(np.abs(out)) < 1e3, impl

    def test_scalar_pos_broadcasts(self):
        B, H, KV, hd, page, n = 2, 4, 2, 8, 8, 2
        q, kp, vp, tbl = _pool_case(3, B=B, H=H, KV=KV, hd=hd, page=page,
                                    n_pages=n, pool_pages=8, dtype=jnp.float32)
        a = ops.paged_decode_attention(q, kp, vp, tbl, 9, impl="pallas")
        b = ops.paged_decode_attention(q, kp, vp, tbl, jnp.asarray([9, 9]), impl="pallas")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_windowed_masking_matches_explicit_slice(self):
        """`window` > 0 (shared/prefix layouts: sliding-window layers paged
        through the dynamic table) attends exactly the last `window` logical
        slots up to pos — the same set a ring buffer would hold — in both
        the oracle and the Pallas kernel."""
        B, H, KV, hd, page, n, w = 3, 4, 2, 16, 8, 4, 8
        q, kp, vp, tbl = _pool_case(4, B=B, H=H, KV=KV, hd=hd, page=page,
                                    n_pages=n, pool_pages=24, dtype=jnp.float32)
        pos = jnp.asarray([5, 13, 27], jnp.int32)  # warm-up / mid / deep
        # explicit reference: gather the row densely, slice the window, run
        # the dense oracle on just those slots
        k_dense = np.asarray(kp)[np.asarray(tbl)].reshape(B, n * page, KV, hd)
        v_dense = np.asarray(vp)[np.asarray(tbl)].reshape(B, n * page, KV, hd)
        want = []
        for b in range(B):
            p = int(pos[b])
            lo = max(0, p - w + 1)
            ks = jnp.asarray(k_dense[b : b + 1, lo : p + 1])
            vs = jnp.asarray(v_dense[b : b + 1, lo : p + 1])
            want.append(np.asarray(ref.decode_attention(q[b : b + 1], ks, vs, p - lo)))
        want = np.concatenate(want, axis=0)
        for impl in ("ref", "pallas"):
            got = np.asarray(
                ops.paged_decode_attention(q, kp, vp, tbl, pos, window=w, impl=impl)
            )
            np.testing.assert_allclose(got, want, atol=1e-5, err_msg=impl)

    def test_window_zero_unchanged(self):
        """window=0 must be byte-for-byte the pre-existing full-validity
        path (ring layouts keep passing 0)."""
        B, H, KV, hd, page, n = 2, 4, 1, 8, 8, 3
        q, kp, vp, tbl = _pool_case(5, B=B, H=H, KV=KV, hd=hd, page=page,
                                    n_pages=n, pool_pages=12, dtype=jnp.float32)
        pos = jnp.asarray([7, 20], jnp.int32)
        base = np.asarray(ref.paged_decode_attention(q, kp, vp, tbl, pos))
        got = np.asarray(ref.paged_decode_attention(q, kp, vp, tbl, pos, window=0))
        np.testing.assert_array_equal(base, got)


# -- the bounded walk: only each slot's live pages ------------------------------
# hd 128 takes the block walk with the kernel's own page copies; hd 64 the
# per-page grid clamped to the live range (Mosaic slices pages out of the
# pool only at head widths that are multiples of 128).
WALK_HD = [pytest.param(128, id="block-walk"), pytest.param(64, id="page-grid")]
PAGE = 8


def _ppb(hd, n):
    from repro.kernels.paged_decode_attention import pages_per_block

    return pages_per_block(PAGE, hd, 4, n)


def _pallas(q, kp, vp, tbl, pos, window=0):
    return np.asarray(ops.paged_decode_attention(q, kp, vp, tbl, jnp.asarray(pos, jnp.int32),
                                                 window=window, impl="pallas"))


def _oracle(q, kp, vp, tbl, pos, window=0):
    return np.asarray(ref.paged_decode_attention(q, kp, vp, tbl, jnp.asarray(pos, jnp.int32),
                                                 window=window))


class TestBoundedWalk:
    @pytest.mark.parametrize("hd", WALK_HD)
    @pytest.mark.parametrize("extra", [pytest.param(5, id="n-not-multiple-of-ppb"),
                                       pytest.param(0, id="n-multiple-of-ppb")])
    def test_positions_at_page_and_block_edges_match_the_oracle(self, hd, extra):
        """One batch with pos at 0, page-1, page, ppb*page-1, ppb*page and
        n*page-1: every edge of a page and of a block of pages."""
        ppb = _ppb(hd, 64)
        n = 2 * ppb + extra
        assert _ppb(hd, n) == ppb and (n % ppb != 0) == (extra != 0)
        pos = [0, PAGE - 1, PAGE, ppb * PAGE - 1, ppb * PAGE, n * PAGE - 1]
        q, kp, vp, tbl = _pool_case(10 + extra, B=len(pos), H=4, KV=2, hd=hd, page=PAGE,
                                    n_pages=n, pool_pages=len(pos) * n + 1, dtype=jnp.float32)
        np.testing.assert_allclose(_pallas(q, kp, vp, tbl, pos), _oracle(q, kp, vp, tbl, pos),
                                   atol=1e-5)

    @pytest.mark.parametrize("hd", WALK_HD)
    def test_table_entries_past_the_live_range_are_never_read(self, hd):
        """Entries past each slot's last live page point at other slots'
        pages, or at pages outside every table holding NaN; the output is
        bit-for-bit what the clean table gives."""
        B, n = 4, 12
        q, kp, vp, tbl = _pool_case(11, B=B, H=4, KV=1, hd=hd, page=PAGE, n_pages=n,
                                    pool_pages=B * n + 3, dtype=jnp.float32)
        pos = [3, 17, 40, 90]
        used = set(np.asarray(tbl).ravel().tolist())
        spare = [p for p in range(1, B * n + 3) if p not in used]
        kp = kp.at[jnp.asarray(spare)].set(jnp.nan)
        vp = vp.at[jnp.asarray(spare)].set(jnp.nan)
        clean = _pallas(q, kp, vp, tbl, pos)
        dirty = np.asarray(tbl).copy()
        for b, p in enumerate(pos):
            for i in range(p // PAGE + 1, n):
                dirty[b, i] = spare[i % len(spare)] if i % 2 else dirty[(b + 1) % B, i]
        got = _pallas(q, kp, vp, jnp.asarray(dirty), pos)
        np.testing.assert_array_equal(got, clean)
        np.testing.assert_allclose(clean, _oracle(q, kp, vp, tbl, pos), atol=1e-5)

    @pytest.mark.parametrize("hd", WALK_HD)
    def test_negative_pos_walks_nothing_and_returns_zeros(self, hd):
        """A slot at pos -1 (inactive or frozen in the decode step) gives
        exact zeros; its neighbours are untouched by it."""
        B, n = 3, 6
        q, kp, vp, tbl = _pool_case(12, B=B, H=4, KV=2, hd=hd, page=PAGE, n_pages=n,
                                    pool_pages=B * n + 1, dtype=jnp.float32)
        got = _pallas(q, kp, vp, tbl, [20, -1, 45])
        base = _pallas(q, kp, vp, tbl, [20, 7, 45])
        np.testing.assert_array_equal(got[1], np.zeros_like(got[1]))
        np.testing.assert_array_equal(got[[0, 2]], base[[0, 2]])
        np.testing.assert_allclose(got[[0, 2]], _oracle(q, kp, vp, tbl, [20, 7, 45])[[0, 2]],
                                   atol=1e-5)

    @pytest.mark.parametrize("hd", WALK_HD)
    def test_window_starts_the_walk_past_page_zero(self, hd):
        """With a window, the walk starts at the page holding pos-window+1:
        pages wholly before it hold NaN and the output still matches the
        oracle over the window."""
        B, n, w = 3, 20, 12
        q, kp, vp, tbl = _pool_case(13, B=B, H=4, KV=1, hd=hd, page=PAGE, n_pages=n,
                                    pool_pages=B * n + 1, dtype=jnp.float32)
        t = 1 + np.random.default_rng(13).permutation(B * n).reshape(B, n).astype(np.int32)
        tbl = jnp.asarray(t)  # no page in two rows: a stale page is no one's live page
        pos = [5, 77, 159]  # first page 0, 8 and 18
        want = _oracle(q, kp, vp, tbl, pos, window=w)
        stale = [int(t[b, i]) for b, p in enumerate(pos) for i in range(max(0, p - w + 1) // PAGE)]
        assert stale  # some slot starts past page 0
        kp = kp.at[jnp.asarray(stale)].set(jnp.nan)
        vp = vp.at[jnp.asarray(stale)].set(jnp.nan)
        np.testing.assert_allclose(_pallas(q, kp, vp, tbl, pos, window=w), want, atol=1e-5)

    @pytest.mark.parametrize("hd", WALK_HD)
    def test_bf16_pool_matches_the_oracle(self, hd):
        B, n = 4, 40
        q, kp, vp, tbl = _pool_case(14, B=B, H=8, KV=2, hd=hd, page=16, n_pages=n,
                                    pool_pages=B * n + 1, dtype=jnp.bfloat16)
        pos = [0, 255, 256, 639]
        np.testing.assert_allclose(
            _pallas(q, kp, vp, tbl, pos).astype(np.float32),
            _oracle(q, kp, vp, tbl, pos).astype(np.float32), atol=5e-2)


class TestLayerOfAStackedPool:
    """A stacked (L, P, page, KV, hd) pool with a `layer`: the kernel reads
    that layer in place (the decode step's layer loop carries the stack)."""

    @pytest.mark.parametrize("window", [pytest.param(0, id="unwindowed"),
                                        pytest.param(12, id="windowed")])
    @pytest.mark.parametrize("kv", [1, 2])
    @pytest.mark.parametrize("hd", [pytest.param(128, id="block-walk"),
                                    pytest.param(160, id="page-grid")])
    def test_matches_the_oracle_on_that_layer(self, hd, kv, window):
        """The other layers hold NaN, so a read outside the named layer
        would show; the layer is traced, as in the layer loop."""
        L, layer, B, n = 3, 1, 4, 12
        q, kp, vp, tbl = _pool_case(20 + kv, B=B, H=4, KV=kv, hd=hd, page=PAGE, n_pages=n,
                                    pool_pages=B * n + 1, dtype=jnp.float32)
        nan = jnp.full((L,) + kp.shape, jnp.nan, kp.dtype)
        ks, vs = nan.at[layer].set(kp), nan.at[layer].set(vp)
        pos = jnp.asarray([0, 17, 60, n * PAGE - 1], jnp.int32)  # first page, mid, deep

        def run(impl):
            fn = jax.jit(lambda ly: ops.paged_decode_attention(q, ks, vs, tbl, pos, ly,
                                                                window=window, impl=impl))
            return np.asarray(fn(jnp.int32(layer)))

        want = _oracle(q, kp, vp, tbl, pos, window=window)
        np.testing.assert_array_equal(run("ref"), want)
        got = run("pallas")
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_array_equal(got, _pallas(q, kp, vp, tbl, pos, window=window))


class TestPagedLayout:
    @pytest.mark.parametrize("max_len, page, slots, n_seq, probe", [
        pytest.param(37, 16, 4, 3, (17, 2), id="window-inside-cache"),
        pytest.param(16, 4, 2, 4, (5, 2), id="window-equals-cache"),
        pytest.param(12, 4, 2, 3, (13, 3), id="window-beyond-cache"),
        pytest.param(64, 12, 2, 6, (13, 2), id="page-not-dividing-window"),
    ])
    def test_one_layout_pages_every_layer(self, max_len, page, slots, n_seq, probe):
        """Sliding-window layers page through the same table as full
        layers, whatever the window's size against the cache or the page:
        the window survives only as a mask, and every layer's pool holds
        the layout's `num_pages` pages."""
        from repro.configs import get_config
        from repro.models.transformer import init_paged_caches

        cfg = get_config("gemma3-1b", reduced=True)  # sliding_window=16
        lay = paged_layout(cfg, max_slots=slots, max_len=max_len, page_size=page)
        assert lay.window == 16
        assert lay.n_pages_seq == n_seq and lay.num_pages == slots * n_seq + 1
        assert lay.pages_for(1) == 1 and lay.pages_for(probe[0]) == probe[1]
        pools = init_paged_caches(cfg, lay)
        assert {leaf.shape[-4] for leaf in jax.tree_util.tree_leaves(pools)} == {lay.num_pages}


class TestMemorySlotPool:
    def test_reserve_draw_free_cycle(self):
        pool = MemorySlotPool(64, 8, reserved_blocks=(0,))
        assert pool.capacity == 7 and pool.blocks_free == 7
        assert pool.reserve(5)
        assert pool.blocks_available == 2
        assert not pool.reserve(3)  # over-reserve refused, no side effect
        assert pool.blocks_available == 2
        drawn = pool.draw(3)
        assert 0 not in drawn and len(set(drawn)) == 3
        assert pool.blocks_used == 3
        pool.free(drawn, )
        pool.unreserve(2)
        assert pool.blocks_available == 7 and pool.blocks_used == 0

    def test_draw_beyond_reservation_raises(self):
        pool = MemorySlotPool(64, 4)
        pool.reserve(1)
        with pytest.raises(ValueError, match="exceeds reservation"):
            pool.draw(2)

    def test_block_slot_views_offset_into_backing(self):
        from repro.core.stateful import LocalMemorySlot
        from repro.core.stateless import MemorySpace

        space = MemorySpace(kind="ram", index=0, device_id="host-0", size_bytes=1024)
        backing = LocalMemorySlot(space, 256, bytearray(256))
        pool = MemorySlotPool(64, 4, backing=(backing,))
        view = pool.block_slot(0, 2)
        assert (view.offset, view.size_bytes, view.registered) == (128, 64, True)


class TestPagedModelStepParity:
    def test_one_step_matches_dense_decode(self):
        """lm_paged_decode_step == lm_decode_step for a freshly committed
        prefill, on the homogeneous-stack arch (units arch covered end-to-end
        in test_serve.py's paged identity test)."""
        from repro.configs import get_config
        from repro.models import build

        cfg = get_config("granite-20b", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        po = model.paged_ops
        layout = po.layout(max_slots=2, max_len=20, page_size=8)
        pools = po.init_pools(layout)
        prompt = [3, 1, 4, 1, 5]
        prefill = model.make_prefill(layout.cache_len)
        logits, state = prefill(params, {"tokens": jnp.asarray([prompt], jnp.int32)})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

        dense_logits, _ = model.decode_step(
            params, state, {"tokens": tok[:, None], "pos": jnp.int32(len(prompt))}
        )
        row = np.zeros((layout.n_pages_seq,), np.int32)
        row[: layout.pages_for(len(prompt) + 1)] = [1, 2][: layout.pages_for(len(prompt) + 1)]
        pools = po.commit_prefill(layout, pools, state, jnp.asarray(row))
        table = jnp.asarray(np.stack([row, np.zeros_like(row)]))
        paged_logits, _ = po.decode_step(
            layout, params, pools, table,
            jnp.asarray([int(tok[0]), 0], jnp.int32),
            jnp.asarray([len(prompt), 0], jnp.int32),
            jnp.asarray([True, False]),
        )
        np.testing.assert_allclose(
            np.asarray(dense_logits[0]), np.asarray(paged_logits[0]), atol=1e-5
        )
