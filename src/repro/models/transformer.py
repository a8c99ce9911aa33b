"""Decoder-only LM assembly: dense and MoE transformers with optional
sliding-window/global interleaving (gemma3) and prefix-LM attention (VLM).

Layers are stacked and executed with `jax.lax.scan` (compile time O(1) in
depth; MaxText-style), with activation rematerialization policies applied to
the scan body. Sliding-window archs scan over *repeating units* (e.g.
gemma3's 5-local+1-global) so per-layer KV caches stay shape-uniform within
a scan while local layers keep ring buffers of only `window` entries —
essential for honest long_500k memory.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from .attention import (
    PagedLayout,
    decode_self_attention,
    init_attention,
    init_kv_cache,
    init_paged_kv_pool,
    paged_decode_self_attention,
    paged_layout,
    prefill_attention,
    self_attention,
    tail_prefill_attention,
)
from .common import (
    ParamBuilder,
    maybe_scan,
    dtype_of,
    embed,
    init_embedding,
    moe_load_balance_loss,
    rms_norm,
    softmax_cross_entropy,
    split_tree,
    unembed,
)
from .ffn import ffn, init_ffn
from .moe import init_moe, moe_ffn


# ---------------------------------------------------------------------------
# layer structure helpers
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig) -> list[int]:
    """Static per-layer window sizes. 0 = global (full) attention."""
    if not cfg.sliding_window:
        return [0] * cfg.num_layers
    g = cfg.global_interval
    return [0 if (i + 1) % g == 0 else cfg.sliding_window for i in range(cfg.num_layers)]


def has_units(cfg: ArchConfig) -> bool:
    """Sliding-window archs scan over repeating (local*, global) units."""
    return bool(cfg.sliding_window and cfg.global_interval)


def unit_structure(cfg: ArchConfig) -> Tuple[int, int, int]:
    """(unit_len, n_units, n_tail) for the scan grouping."""
    if not has_units(cfg):
        return cfg.num_layers, 1, 0  # one homogeneous scan over all layers
    g = cfg.global_interval
    return g, cfg.num_layers // g, cfg.num_layers % g


def _init_layer_stack(pb: ParamBuilder, cfg: ArchConfig, n: int):
    d = cfg.d_model
    tree = {
        "ln1": pb.zeros((n, d), ("layers", "norm")),
        "ln2": pb.zeros((n, d), ("layers", "norm")),
        "attn": init_attention(pb, cfg, n),
    }
    if cfg.is_moe:
        tree["moe"] = init_moe(pb, cfg, n)
    else:
        tree["ffn"] = init_ffn(pb, cfg, n)
    return tree


def init_lm(cfg: ArchConfig, key: jax.Array):
    """Returns (params, logical_axes) trees."""
    pb = ParamBuilder(key, dtype_of(cfg.param_dtype))
    unit_len, n_units, n_tail = unit_structure(cfg)
    tree = {
        "embed": init_embedding(pb, cfg.vocab_size, cfg.d_model, tie=cfg.tie_embeddings),
        "final_norm": pb.zeros((cfg.d_model,), ("norm",)),
    }
    if not has_units(cfg):
        tree["layers"] = _init_layer_stack(pb, cfg, cfg.num_layers)
    else:
        # units: every leaf gets a leading (n_units,) scan dim on top of the
        # per-unit (unit_len,) layer dim; independently initialized per unit.
        units = []
        for _ in range(n_units):
            units.append(_init_layer_stack(pb, cfg, unit_len))
        is_pair = lambda x: isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "dtype")
        tree["units"] = jax.tree_util.tree_map(
            lambda *leaves: (jnp.stack([l[0] for l in leaves]), ("units",) + leaves[0][1]),
            *units,
            is_leaf=is_pair,
        )
        if n_tail:
            tree["tail"] = _init_layer_stack(pb, cfg, n_tail)
    return split_tree(tree)


# ---------------------------------------------------------------------------
# forward (training): full sequence, loss-ready hidden states
# ---------------------------------------------------------------------------


def _layer_body(cfg: ArchConfig, p_l, h, *, window, prefix_len: int = 0):
    """One transformer layer. Returns (h, aux_loss)."""
    attn_in = rms_norm(h, p_l["ln1"], eps=cfg.norm_eps)
    h = h + self_attention(cfg, p_l["attn"], attn_in, window=window, prefix_len=prefix_len)
    ffn_in = rms_norm(h, p_l["ln2"], eps=cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_ffn(cfg, p_l["moe"], ffn_in)
        aux_loss = moe_load_balance_loss(
            aux["router_probs"], aux["expert_indices"], cfg.num_experts
        )
    else:
        y = ffn(cfg, p_l["ffn"], ffn_in)
        aux_loss = jnp.float32(0.0)
    return h + y, aux_loss


def _remat(cfg: ArchConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "minimal":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save nothing


def _scan_stack(cfg: ArchConfig, stack, h, *, windows, prefix_len: int = 0):
    """Scan `h` through a (L, ...) parameter stack.

    `windows`: (L,) array of per-layer window sizes, or None when every
    layer is global — then the window stays a STATIC 0 so the blocked
    attention path can engage (it needs static windows)."""

    if windows is None:
        def body(carry, p_l):
            new_h, aux = _layer_body(cfg, p_l, carry, window=0, prefix_len=prefix_len)
            return new_h, aux

        body = _remat(cfg, body)
        h, auxs = maybe_scan(cfg, body, h, stack)
        return h, jnp.sum(auxs)

    def body(carry, xs):
        p_l, window = xs
        new_h, aux = _layer_body(cfg, p_l, carry, window=window, prefix_len=prefix_len)
        return new_h, aux

    body = _remat(cfg, body)
    h, auxs = maybe_scan(cfg, body, h, (stack, windows))
    return h, jnp.sum(auxs)


def _unit_forward(cfg: ArchConfig, p_unit, h, *, prefix_len: int = 0):
    """One sliding-window unit: (g-1) local layers then 1 global layer."""
    g = cfg.global_interval
    aux_total = jnp.float32(0.0)
    for i in range(g):
        window = cfg.sliding_window if (i + 1) % g != 0 else 0
        p_l = jax.tree_util.tree_map(lambda x: x[i], p_unit)
        h, aux = _layer_body(cfg, p_l, h, window=window, prefix_len=prefix_len)
        aux_total = aux_total + aux
    return h, aux_total


def backbone_forward(cfg: ArchConfig, params, h, *, prefix_len: int = 0):
    """Run embedded inputs h: (B,S,d) through all layers + final norm.
    Returns (h, aux_loss). Used directly by the VLM (vision-prefix inputs)."""
    unit_len, n_units, n_tail = unit_structure(cfg)
    if "layers" in params:
        windows = None  # static 0 window -> blocked attention can engage
        if cfg.sliding_window:
            windows = jnp.asarray(layer_windows(cfg), dtype=jnp.int32)
        h, aux = _scan_stack(cfg, params["layers"], h, windows=windows, prefix_len=prefix_len)
    else:
        def unit_body(carry, p_unit):
            new_h, aux = _unit_forward(cfg, p_unit, carry, prefix_len=prefix_len)
            return new_h, aux

        unit_body = _remat(cfg, unit_body)
        h, auxs = maybe_scan(cfg, unit_body, h, params["units"])
        aux = jnp.sum(auxs)
        if "tail" in params:
            windows = jnp.full((n_tail,), cfg.sliding_window, dtype=jnp.int32)
            h, aux_tail = _scan_stack(cfg, params["tail"], h, windows=windows, prefix_len=prefix_len)
            aux = aux + aux_tail
    h = rms_norm(h, params["final_norm"], eps=cfg.norm_eps)
    return h, aux


def lm_forward(cfg: ArchConfig, params, tokens, *, prefix_len: int = 0):
    """tokens: (B, S) -> (logits (B,S,V), aux_loss)."""
    cd = dtype_of(cfg.compute_dtype)
    h = embed(params["embed"], tokens, compute_dtype=cd)
    h, aux = backbone_forward(cfg, params, h, prefix_len=prefix_len)
    logits = unembed(params["embed"], h, tie=cfg.tie_embeddings)
    return logits, aux


def lm_loss(cfg: ArchConfig, params, tokens, labels, *, prefix_len: int = 0,
            z_loss: float = 1e-4, moe_aux_weight: float = 1e-2):
    logits, aux = lm_forward(cfg, params, tokens, prefix_len=prefix_len)
    loss = softmax_cross_entropy(logits, labels, z_loss=z_loss)
    return loss + moe_aux_weight * aux, {"ce_loss": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# KV-cache serving: prefill + decode
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    """Stacked per-layer KV caches matching the scan grouping."""
    cd = dtype_of(cfg.compute_dtype)
    unit_len, n_units, n_tail = unit_structure(cfg)
    windows = layer_windows(cfg)

    def kv(window):
        return init_kv_cache(cfg, batch, max_len, window=window, dtype=cd)

    if not has_units(cfg):
        w = windows[0]
        k0, v0 = kv(w)
        L = cfg.num_layers
        return {
            "k": jnp.broadcast_to(k0[None], (L,) + k0.shape),
            "v": jnp.broadcast_to(v0[None], (L,) + v0.shape),
        }
    g = cfg.global_interval
    kl, vl = kv(cfg.sliding_window)
    kg, vg = kv(0)
    caches = {
        "units": {
            "k_local": jnp.broadcast_to(kl[None, None], (n_units, g - 1) + kl.shape),
            "v_local": jnp.broadcast_to(vl[None, None], (n_units, g - 1) + vl.shape),
            "k_global": jnp.broadcast_to(kg[None], (n_units,) + kg.shape),
            "v_global": jnp.broadcast_to(vg[None], (n_units,) + vg.shape),
        }
    }
    if n_tail:
        caches["tail"] = {
            "k": jnp.broadcast_to(kl[None], (n_tail,) + kl.shape),
            "v": jnp.broadcast_to(vl[None], (n_tail,) + vl.shape),
        }
    return caches


# ---------------------------------------------------------------------------
# paged KV-cache serving: block-pool caches + page-table decode
# ---------------------------------------------------------------------------


def make_paged_layout(cfg: ArchConfig, **kwargs) -> PagedLayout:
    return paged_layout(cfg, **kwargs)


def init_paged_caches(cfg: ArchConfig, layout: PagedLayout):
    """Per-layer block-pool tensors matching the scan grouping, shared by
    every slot (no batch dim — the page table is the slot axis).

    Every layer, sliding-window layers included, pools `layout.num_pages`
    pages addressed by the scheduler's page table."""
    cd = dtype_of(cfg.compute_dtype)
    unit_len, n_units, n_tail = unit_structure(cfg)
    k_full, v_full = init_paged_kv_pool(cfg, layout.num_pages, layout.page_size, dtype=cd)
    if not has_units(cfg):
        L = cfg.num_layers
        return {
            "k": jnp.broadcast_to(k_full[None], (L,) + k_full.shape),
            "v": jnp.broadcast_to(v_full[None], (L,) + v_full.shape),
        }
    g = cfg.global_interval
    pools = {
        "units": {
            "k_local": jnp.broadcast_to(k_full[None, None], (n_units, g - 1) + k_full.shape),
            "v_local": jnp.broadcast_to(v_full[None, None], (n_units, g - 1) + v_full.shape),
            "k_global": jnp.broadcast_to(k_full[None], (n_units,) + k_full.shape),
            "v_global": jnp.broadcast_to(v_full[None], (n_units,) + v_full.shape),
        }
    }
    if n_tail:
        pools["tail"] = {
            "k": jnp.broadcast_to(k_full[None], (n_tail,) + k_full.shape),
            "v": jnp.broadcast_to(v_full[None], (n_tail,) + v_full.shape),
        }
    return pools


def _split_pages(cache, page_size: int):
    """(..., B=1, S, KV, hd) dense cache -> (..., S//page, page, KV, hd)."""
    c = jnp.squeeze(cache, axis=-4)
    n = c.shape[-3] // page_size
    return c.reshape(c.shape[:-3] + (n, page_size) + c.shape[-2:])


def commit_prefill_paged(cfg: ArchConfig, layout: PagedLayout, pools, dense_caches, full_row):
    """Scatter one slot's B=1 full-depth prefill caches into its pool pages.

    full_row: (n_pages_seq,) physical pages, 0-padded past the allocation —
    the padded writes land on the null page."""
    p = layout.page_size
    if "k" in pools:
        return {
            "k": pools["k"].at[:, full_row].set(_split_pages(dense_caches["k"], p)),
            "v": pools["v"].at[:, full_row].set(_split_pages(dense_caches["v"], p)),
        }
    du, pu = dense_caches["units"], pools["units"]
    new_pools = {
        "units": {
            "k_local": pu["k_local"].at[:, :, full_row].set(_split_pages(du["k_local"], p)),
            "v_local": pu["v_local"].at[:, :, full_row].set(_split_pages(du["v_local"], p)),
            "k_global": pu["k_global"].at[:, full_row].set(_split_pages(du["k_global"], p)),
            "v_global": pu["v_global"].at[:, full_row].set(_split_pages(du["v_global"], p)),
        }
    }
    if "tail" in pools:
        new_pools["tail"] = {
            "k": pools["tail"]["k"].at[:, full_row].set(_split_pages(dense_caches["tail"]["k"], p)),
            "v": pools["tail"]["v"].at[:, full_row].set(_split_pages(dense_caches["tail"]["v"], p)),
        }
    return new_pools


def _gather_pages(pool, row, cache_len: int):
    """(..., P, ps, KV, hd) pool -> (..., 1, cache_len, KV, hd) dense cache
    holding the pages `row` names, in logical order (null-page padding
    gathers garbage that sits past every valid position)."""
    got = jnp.take(pool, row, axis=-4)  # (..., n_pages_seq, ps, KV, hd)
    flat = got.reshape(got.shape[:-4] + (cache_len,) + got.shape[-2:])
    return jnp.expand_dims(flat, axis=-4)


def gather_paged_caches(cfg: ArchConfig, layout: PagedLayout, pools, row):
    """Densify one slot's pool pages into full-depth B=1 caches — the read
    half of copy-on-write (every layer's pages are addressed by the same
    row)."""
    g = lambda pool: _gather_pages(pool, row, layout.cache_len)
    if "k" in pools:
        return {"k": g(pools["k"]), "v": g(pools["v"])}
    out = {"units": {name: g(leaf) for name, leaf in pools["units"].items()}}
    if "tail" in pools:
        out["tail"] = {"k": g(pools["tail"]["k"]), "v": g(pools["tail"]["v"])}
    return out


def _tail_prefill_layer(cfg, p_l, h, cache_kv, off, *, window):
    attn_in = rms_norm(h, p_l["ln1"], eps=cfg.norm_eps)
    attn_out, new_cache = tail_prefill_attention(
        cfg, p_l["attn"], attn_in, cache_kv, off, window=window
    )
    h = h + attn_out
    ffn_in = rms_norm(h, p_l["ln2"], eps=cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_ffn(cfg, p_l["moe"], ffn_in)
    else:
        y = ffn(cfg, p_l["ffn"], ffn_in)
    return h + y, new_cache


def lm_prefix_prefill(cfg: ArchConfig, layout: PagedLayout, params, pools, row, tokens, off):
    """Prefill only a prompt's uncached *tail* against a shared prefix.

    `row`: (n_pages_seq,) gather row — the matched prefix's physical pages
    (including the boundary page being copy-on-write-forked), 0-padded;
    `tokens`: (1, S_tail) uncached tail tokens at absolute positions
    [off, off + S_tail). Gathers the prefix K/V out of pool pages into
    full-depth dense caches (local layers too), runs transformer layers
    over the tail only — the FLOP savings of a prefix hit — and returns
    (last-position logits (1, V), dense caches) ready for
    `commit_prefill_paged`. `off` may be traced; compiles per tail length.
    """
    caches = gather_paged_caches(cfg, layout, pools, row)
    cd = dtype_of(cfg.compute_dtype)
    h = embed(params["embed"], tokens, compute_dtype=cd)

    if "layers" in params:
        def body(carry, xs):
            p_l, k, v = xs
            new_h, (nk, nv) = _tail_prefill_layer(cfg, p_l, carry, (k, v), off, window=0)
            return new_h, (nk, nv)

        h, (nk, nv) = maybe_scan(cfg, body, h, (params["layers"], caches["k"], caches["v"]))
        new_caches = {"k": nk, "v": nv}
    else:
        g = cfg.global_interval

        def unit_body(carry, xs):
            p_unit, c = xs
            hh = carry
            nk_l, nv_l = [], []
            for i in range(g - 1):
                p_l = jax.tree_util.tree_map(lambda x: x[i], p_unit)
                hh, (nk, nv) = _tail_prefill_layer(
                    cfg, p_l, hh, (c["k_local"][i], c["v_local"][i]), off,
                    window=cfg.sliding_window,
                )
                nk_l.append(nk)
                nv_l.append(nv)
            p_l = jax.tree_util.tree_map(lambda x: x[g - 1], p_unit)
            hh, (nkg, nvg) = _tail_prefill_layer(
                cfg, p_l, hh, (c["k_global"], c["v_global"]), off, window=0
            )
            new_c = {
                "k_local": jnp.stack(nk_l), "v_local": jnp.stack(nv_l),
                "k_global": nkg, "v_global": nvg,
            }
            return hh, new_c

        h, new_unit_caches = maybe_scan(cfg, unit_body, h, (params["units"], caches["units"]))
        new_caches = {"units": new_unit_caches}
        if "tail" in params:
            def tail_body(carry, xs):
                p_l, k, v = xs
                new_h, (nk, nv) = _tail_prefill_layer(
                    cfg, p_l, carry, (k, v), off, window=cfg.sliding_window
                )
                return new_h, (nk, nv)

            h, (nk, nv) = maybe_scan(
                cfg, tail_body, h, (params["tail"], caches["tail"]["k"], caches["tail"]["v"])
            )
            new_caches["tail"] = {"k": nk, "v": nv}

    h = rms_norm(h[:, -1:], params["final_norm"], eps=cfg.norm_eps)
    logits = unembed(params["embed"], h[:, 0], tie=cfg.tie_embeddings)
    return logits, new_caches


def _paged_decode_layer(cfg, layout, p_l, h, pool_kv, table, pos, active, *, window,
                        layer=None):
    attn_in = rms_norm(h, p_l["ln1"], eps=cfg.norm_eps)
    attn_out, new_kv = paged_decode_self_attention(
        cfg, p_l["attn"], attn_in, pool_kv[0], pool_kv[1], table, pos, active,
        page_size=layout.page_size, window=window, layer=layer,
    )
    h = h + attn_out
    ffn_in = rms_norm(h, p_l["ln2"], eps=cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_ffn(cfg, p_l["moe"], ffn_in)
    else:
        y = ffn(cfg, p_l["ffn"], ffn_in)
    return h + y, new_kv


def _paged_decode_stack(cfg, layout, stack, h, pools, table, pos, active, *, window):
    """Decode `h` through a (L, ...) layer stack whose stacked (L, P, page,
    KV, hd) pools ride in the layer loop's carry beside `h`: each layer
    writes its new K/V rows into the carried pools and reads its own layer
    of them in place, so no layer's pool is sliced out or stacked anew.
    Returns (h, {"k", "v"})."""

    def body(carry, xs):
        h, k, v = carry
        p_l, layer = xs
        h, (k, v) = _paged_decode_layer(
            cfg, layout, p_l, h, (k, v), table, pos, active,
            window=window, layer=layer,
        )
        return (h, k, v), None

    layers = jnp.arange(pools["k"].shape[0], dtype=jnp.int32)
    (h, k, v), _ = maybe_scan(cfg, body, (h, pools["k"], pools["v"]), (stack, layers))
    return h, {"k": k, "v": v}


def lm_paged_decode_step(cfg: ArchConfig, layout: PagedLayout, params, pools, full_table, tokens, pos, active):
    """One batched decode tick over paged caches.

    tokens: (B,) last tokens; pos: (B,) per-slot positions; active: (B,)
    bool (inactive slots compute garbage that never escapes: K/V writes are
    null-routed, callers mask sampled tokens). Returns (logits (B,V), pools).
    """
    cd = dtype_of(cfg.compute_dtype)
    h = embed(params["embed"], tokens[:, None], compute_dtype=cd)  # (B,1,d)

    if "layers" in params:
        h, new_pools = _paged_decode_stack(
            cfg, layout, params["layers"], h, pools, full_table, pos, active, window=0
        )
    else:
        g = cfg.global_interval

        def unit_body(carry, xs):
            p_unit, c = xs
            hh = carry
            nk_l, nv_l = [], []
            for i in range(g - 1):
                p_l = jax.tree_util.tree_map(lambda x: x[i], p_unit)
                hh, (nk, nv) = _paged_decode_layer(
                    cfg, layout, p_l, hh, (c["k_local"][i], c["v_local"][i]),
                    full_table, pos, active, window=layout.window,
                )
                nk_l.append(nk)
                nv_l.append(nv)
            p_l = jax.tree_util.tree_map(lambda x: x[g - 1], p_unit)
            hh, (nkg, nvg) = _paged_decode_layer(
                cfg, layout, p_l, hh, (c["k_global"], c["v_global"]),
                full_table, pos, active, window=0,
            )
            new_c = {
                "k_local": jnp.stack(nk_l), "v_local": jnp.stack(nv_l),
                "k_global": nkg, "v_global": nvg,
            }
            return hh, new_c

        h, new_unit_pools = maybe_scan(cfg, unit_body, h, (params["units"], pools["units"]))
        new_pools = {"units": new_unit_pools}
        if "tail" in params:
            h, new_pools["tail"] = _paged_decode_stack(
                cfg, layout, params["tail"], h, pools["tail"], full_table, pos, active,
                window=layout.window,
            )

    h = rms_norm(h, params["final_norm"], eps=cfg.norm_eps)
    logits = unembed(params["embed"], h[:, 0], tie=cfg.tie_embeddings)
    return logits, new_pools


def _prefill_layer(cfg, p_l, h, cache_kv, *, window, prefix_len=0):
    attn_in = rms_norm(h, p_l["ln1"], eps=cfg.norm_eps)
    attn_out, new_cache = prefill_attention(
        cfg, p_l["attn"], attn_in, cache_kv, window=window, prefix_len=prefix_len
    )
    h = h + attn_out
    ffn_in = rms_norm(h, p_l["ln2"], eps=cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_ffn(cfg, p_l["moe"], ffn_in)
    else:
        y = ffn(cfg, p_l["ffn"], ffn_in)
    return h + y, new_cache


def _decode_layer(cfg, p_l, h, cache_kv, pos, *, window):
    attn_in = rms_norm(h, p_l["ln1"], eps=cfg.norm_eps)
    attn_out, new_cache = decode_self_attention(
        cfg, p_l["attn"], attn_in, cache_kv, pos, window=window
    )
    h = h + attn_out
    ffn_in = rms_norm(h, p_l["ln2"], eps=cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe_ffn(cfg, p_l["moe"], ffn_in)
    else:
        y = ffn(cfg, p_l["ffn"], ffn_in)
    return h + y, new_cache


def backbone_prefill(cfg: ArchConfig, params, h, caches, *, prefix_len: int = 0):
    """h: (B,S,d) embedded inputs. Returns (h_full, new_caches)."""
    windows_list = layer_windows(cfg)

    if "layers" in params:
        # homogeneous stack => every layer is global (window handling for
        # sliding-window archs goes through the units path)
        def body(carry, xs):
            p_l, k, v = xs
            new_h, (nk, nv) = _prefill_layer(cfg, p_l, carry, (k, v), window=0, prefix_len=prefix_len)
            return new_h, (nk, nv)

        h, (nk, nv) = maybe_scan(cfg, body, h, (params["layers"], caches["k"], caches["v"]))
        new_caches = {"k": nk, "v": nv}
    else:
        g = cfg.global_interval

        def unit_body(carry, xs):
            p_unit, c = xs
            hh = carry
            nk_l, nv_l = [], []
            for i in range(g - 1):
                p_l = jax.tree_util.tree_map(lambda x: x[i], p_unit)
                hh, (nk, nv) = _prefill_layer(
                    cfg, p_l, hh, (c["k_local"][i], c["v_local"][i]),
                    window=cfg.sliding_window, prefix_len=prefix_len,
                )
                nk_l.append(nk)
                nv_l.append(nv)
            p_l = jax.tree_util.tree_map(lambda x: x[g - 1], p_unit)
            hh, (nkg, nvg) = _prefill_layer(
                cfg, p_l, hh, (c["k_global"], c["v_global"]), window=0, prefix_len=prefix_len
            )
            new_c = {
                "k_local": jnp.stack(nk_l), "v_local": jnp.stack(nv_l),
                "k_global": nkg, "v_global": nvg,
            }
            return hh, new_c

        h, new_unit_caches = maybe_scan(cfg, unit_body, h, (params["units"], caches["units"]))
        new_caches = {"units": new_unit_caches}
        if "tail" in params:
            def tail_body(carry, xs):
                p_l, k, v = xs
                new_h, (nk, nv) = _prefill_layer(
                    cfg, p_l, carry, (k, v), window=cfg.sliding_window, prefix_len=prefix_len
                )
                return new_h, (nk, nv)

            h, (nk, nv) = maybe_scan(
                cfg, tail_body, h, (params["tail"], caches["tail"]["k"], caches["tail"]["v"])
            )
            new_caches["tail"] = {"k": nk, "v": nv}

    return h, new_caches


def lm_prefill(cfg: ArchConfig, params, tokens, caches, *, prefix_len: int = 0):
    """tokens: (B,S). Returns (last-position logits (B,V), caches)."""
    cd = dtype_of(cfg.compute_dtype)
    h = embed(params["embed"], tokens, compute_dtype=cd)
    h, new_caches = backbone_prefill(cfg, params, h, caches, prefix_len=prefix_len)
    h = rms_norm(h[:, -1:], params["final_norm"], eps=cfg.norm_eps)
    logits = unembed(params["embed"], h[:, 0], tie=cfg.tie_embeddings)
    return logits, new_caches


def lm_decode_step(cfg: ArchConfig, params, caches, tokens, pos):
    """tokens: (B,1); pos: scalar. Returns (logits (B,V), caches)."""
    cd = dtype_of(cfg.compute_dtype)
    h = embed(params["embed"], tokens, compute_dtype=cd)

    if "layers" in params:
        def body(carry, xs):
            p_l, k, v = xs
            new_h, (nk, nv) = _decode_layer(cfg, p_l, carry, (k, v), pos, window=0)
            return new_h, (nk, nv)

        h, (nk, nv) = maybe_scan(cfg, body, h, (params["layers"], caches["k"], caches["v"]))
        new_caches = {"k": nk, "v": nv}
    else:
        g = cfg.global_interval

        def unit_body(carry, xs):
            p_unit, c = xs
            hh = carry
            nk_l, nv_l = [], []
            for i in range(g - 1):
                p_l = jax.tree_util.tree_map(lambda x: x[i], p_unit)
                hh, (nk, nv) = _decode_layer(
                    cfg, p_l, hh, (c["k_local"][i], c["v_local"][i]), pos, window=cfg.sliding_window
                )
                nk_l.append(nk)
                nv_l.append(nv)
            p_l = jax.tree_util.tree_map(lambda x: x[g - 1], p_unit)
            hh, (nkg, nvg) = _decode_layer(cfg, p_l, hh, (c["k_global"], c["v_global"]), pos, window=0)
            new_c = {
                "k_local": jnp.stack(nk_l), "v_local": jnp.stack(nv_l),
                "k_global": nkg, "v_global": nvg,
            }
            return hh, new_c

        h, new_unit_caches = maybe_scan(cfg, unit_body, h, (params["units"], caches["units"]))
        new_caches = {"units": new_unit_caches}
        if "tail" in params:
            def tail_body(carry, xs):
                p_l, k, v = xs
                new_h, (nk, nv) = _decode_layer(
                    cfg, p_l, carry, (k, v), pos, window=cfg.sliding_window
                )
                return new_h, (nk, nv)

            h, (nk, nv) = maybe_scan(
                cfg, tail_body, h, (params["tail"], caches["tail"]["k"], caches["tail"]["v"])
            )
            new_caches["tail"] = {"k": nk, "v": nv}

    h = rms_norm(h, params["final_norm"], eps=cfg.norm_eps)
    logits = unembed(params["embed"], h[:, 0], tie=cfg.tie_embeddings)
    return logits, new_caches
