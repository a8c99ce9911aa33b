"""Model zoo: one uniform bundle per architecture family.

`build(cfg)` returns a ModelBundle of pure functions with a uniform batch
protocol so the training/serving/dry-run layers are family-agnostic:

    train batch:   {"tokens": (B,S), "labels": (B,S)[, "frames"|"patches"]}
    prefill batch: {"tokens": (B,S)[, "frames"|"patches"]}
    decode batch:  {"tokens": (B,1), "pos": ()}  + recurrent/KV state

`input_specs(shape)` yields jax.ShapeDtypeStruct stand-ins for every input
(dry-run lowering: weak-type-correct, shardable, no device allocation).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, ShapeConfig
from .common import dtype_of
from . import encdec, hybrid, transformer, vlm, xlstm_model


@dataclasses.dataclass(frozen=True)
class PagedOps:
    """Paged KV-cache entry points for families whose decode state is a pure
    KV cache (vLLM-style block-pool serving; serve/batching.py's paged path).

    layout(max_slots=..., max_len=..., page_size=..., num_pages=None)
        -> PagedLayout (static cache geometry)
    init_pools(layout) -> per-layer block-pool pytree (no batch dim)
    commit_prefill(layout, pools, dense_state, full_row) -> pools
        scatter one slot's B=1 full-depth prefill cache into its pages
    decode_step(layout, params, pools, full_table, tokens, pos, active)
        -> (logits (B,V), pools): one batched decode tick over the pool
    prefix_prefill(layout, params, pools, row, tokens, off)
        -> (logits (1,V), dense_caches): prefill only a prompt's uncached
        tail against a prefix gathered from pool pages (every admission;
        on a miss `off` is 0 and `row` names only the null page)
    """

    layout: Callable
    init_pools: Callable
    commit_prefill: Callable
    decode_step: Callable
    prefix_prefill: Callable


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init: Callable  # (key) -> (params, axes)
    loss: Callable  # (params, batch) -> (loss, metrics)
    init_state: Callable  # (batch_size, max_len) -> state (allocates!)
    prefill: Callable  # (params, batch) -> (logits, state)    [creates state inside]
    decode_step: Callable  # (params, state, batch) -> (logits, state)
    input_specs: Callable  # (ShapeConfig) -> dict of ShapeDtypeStruct
    make_batch: Callable  # (key, ShapeConfig) -> dict of concrete arrays
    #: (max_len) -> prefill fn whose state has headroom for `max_len`
    #: positions — serving paths MUST use this so decode steps never write
    #: past the cache (the default `prefill` sizes the cache to the prompt).
    make_prefill: Callable = None
    #: Paged KV-cache ops, or None for families without a paged decode path
    #: (recurrent/hybrid states are O(1) or mixed; VLM needs prefix plumbing).
    paged_ops: PagedOps = None

    def state_specs(self, shape: ShapeConfig):
        """Abstract state pytree for decode dry-runs (no allocation)."""
        return jax.eval_shape(lambda: self.init_state(shape.global_batch, shape.seq_len))


def _text_specs(cfg: ArchConfig, shape: ShapeConfig, *, extra: Dict[str, Any] | None = None):
    B, S = shape.global_batch, shape.seq_len
    cd = dtype_of(cfg.compute_dtype)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    else:  # decode: one new token against a seq_len-deep state
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
    if extra and shape.kind != "decode":
        specs.update(extra)
    return specs


def _make_text_batch(cfg: ArchConfig, shape: ShapeConfig, key, *, extra_fn=None):
    B, S = shape.global_batch, shape.seq_len
    k1, k2 = jax.random.split(key)
    if shape.kind == "train":
        batch = {
            "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size, dtype=jnp.int32),
            "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size, dtype=jnp.int32),
        }
    elif shape.kind == "prefill":
        batch = {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size, dtype=jnp.int32)}
    else:
        batch = {
            "tokens": jax.random.randint(k1, (B, 1), 0, cfg.vocab_size, dtype=jnp.int32),
            "pos": jnp.int32(S - 1),
        }
    if extra_fn and shape.kind != "decode":
        batch.update(extra_fn(k2))
    return batch


def build(cfg: ArchConfig) -> ModelBundle:
    fam = cfg.family
    if fam in ("dense", "moe"):
        return _build_transformer(cfg)
    if fam == "vlm":
        return _build_vlm(cfg)
    if fam == "audio":
        return _build_encdec(cfg)
    if fam == "ssm":
        return _build_xlstm(cfg)
    if fam == "hybrid":
        return _build_hybrid(cfg)
    raise KeyError(fam)


# ---------------------------------------------------------------------------


def _build_transformer(cfg: ArchConfig) -> ModelBundle:
    def loss(params, batch):
        return transformer.lm_loss(cfg, params, batch["tokens"], batch["labels"])

    def make_prefill(max_len=None):
        def prefill(params, batch):
            B, S = batch["tokens"].shape
            caches = transformer.init_caches(cfg, B, max_len or S)
            return transformer.lm_prefill(cfg, params, batch["tokens"], caches)

        return prefill

    def decode_step(params, state, batch):
        return transformer.lm_decode_step(cfg, params, state, batch["tokens"], batch["pos"])

    return ModelBundle(
        cfg=cfg,
        init=functools.partial(transformer.init_lm, cfg),
        loss=loss,
        init_state=functools.partial(transformer.init_caches, cfg),
        prefill=make_prefill(),
        decode_step=decode_step,
        input_specs=functools.partial(_text_specs, cfg),
        make_batch=lambda key, shape: _make_text_batch(cfg, shape, key),
        make_prefill=make_prefill,
        paged_ops=PagedOps(
            layout=functools.partial(transformer.make_paged_layout, cfg),
            init_pools=functools.partial(transformer.init_paged_caches, cfg),
            commit_prefill=functools.partial(transformer.commit_prefill_paged, cfg),
            decode_step=functools.partial(transformer.lm_paged_decode_step, cfg),
            prefix_prefill=functools.partial(transformer.lm_prefix_prefill, cfg),
        ),
    )


def _build_vlm(cfg: ArchConfig) -> ModelBundle:
    cd = dtype_of(cfg.compute_dtype)
    P, E = cfg.vision_tokens, cfg.vision_embed_dim

    def patch_specs(shape: ShapeConfig):
        return {"patches": jax.ShapeDtypeStruct((shape.global_batch, P, E), cd)}

    def input_specs(shape: ShapeConfig):
        # total assigned seq_len = vision prefix + text
        text = shape.seq_len - P if shape.kind != "decode" else shape.seq_len
        eff = dataclasses.replace(shape, seq_len=text)
        return _text_specs(cfg, eff, extra=patch_specs(shape) if shape.kind != "decode" else None)

    def make_batch(key, shape: ShapeConfig):
        text = shape.seq_len - P if shape.kind != "decode" else shape.seq_len
        eff = dataclasses.replace(shape, seq_len=text)
        extra = lambda k: {"patches": jax.random.normal(k, (shape.global_batch, P, E), dtype=cd)}
        return _make_text_batch(cfg, eff, key, extra_fn=extra)

    def loss(params, batch):
        return vlm.lm_loss(cfg, params, batch["tokens"], batch["labels"], batch["patches"])

    def make_prefill(max_len=None):
        def prefill(params, batch):
            B, S_text = batch["tokens"].shape
            caches = vlm.init_states(cfg, B, max_len or (P + S_text))
            return vlm.lm_prefill(cfg, params, batch["tokens"], caches, batch["patches"])

        return prefill

    def decode_step(params, state, batch):
        return vlm.lm_decode_step(cfg, params, state, batch["tokens"], batch["pos"])

    return ModelBundle(
        cfg=cfg,
        init=functools.partial(vlm.init_lm, cfg),
        loss=loss,
        init_state=functools.partial(vlm.init_states, cfg),
        prefill=make_prefill(),
        decode_step=decode_step,
        input_specs=input_specs,
        make_batch=make_batch,
        make_prefill=make_prefill,
    )


def _build_encdec(cfg: ArchConfig) -> ModelBundle:
    cd = dtype_of(cfg.compute_dtype)
    T, d = cfg.encoder_context, cfg.d_model

    def frame_specs(shape: ShapeConfig):
        return {"frames": jax.ShapeDtypeStruct((shape.global_batch, T, d), cd)}

    def input_specs(shape: ShapeConfig):
        return _text_specs(cfg, shape, extra=frame_specs(shape) if shape.kind != "decode" else None)

    def make_batch(key, shape: ShapeConfig):
        extra = lambda k: {"frames": jax.random.normal(k, (shape.global_batch, T, d), dtype=cd) * 0.02}
        return _make_text_batch(cfg, shape, key, extra_fn=extra)

    def loss(params, batch):
        return encdec.lm_loss(cfg, params, batch["tokens"], batch["labels"], batch["frames"])

    def make_prefill(max_len=None):
        def prefill(params, batch):
            B, S = batch["tokens"].shape
            states = encdec.init_states(cfg, B, max_len or S)
            return encdec.lm_prefill(cfg, params, batch["tokens"], states, batch["frames"])

        return prefill

    def decode_step(params, state, batch):
        return encdec.lm_decode_step(cfg, params, state, batch["tokens"], batch["pos"])

    return ModelBundle(
        cfg=cfg,
        init=functools.partial(encdec.init_lm, cfg),
        loss=loss,
        init_state=functools.partial(encdec.init_states, cfg),
        prefill=make_prefill(),
        decode_step=decode_step,
        input_specs=input_specs,
        make_batch=make_batch,
        make_prefill=make_prefill,
    )


def _build_xlstm(cfg: ArchConfig) -> ModelBundle:
    def loss(params, batch):
        return xlstm_model.lm_loss(cfg, params, batch["tokens"], batch["labels"])

    def make_prefill(max_len=None):  # recurrent state is O(1): max_len unused
        def prefill(params, batch):
            B = batch["tokens"].shape[0]
            states = xlstm_model.init_states(cfg, B)
            return xlstm_model.lm_prefill(cfg, params, batch["tokens"], states)

        return prefill

    def decode_step(params, state, batch):
        return xlstm_model.lm_decode_step(cfg, params, state, batch["tokens"], batch["pos"])

    return ModelBundle(
        cfg=cfg,
        init=functools.partial(xlstm_model.init_lm, cfg),
        loss=loss,
        init_state=lambda batch, max_len: xlstm_model.init_states(cfg, batch),
        prefill=make_prefill(),
        decode_step=decode_step,
        input_specs=functools.partial(_text_specs, cfg),
        make_batch=lambda key, shape: _make_text_batch(cfg, shape, key),
        make_prefill=make_prefill,
    )


def _build_hybrid(cfg: ArchConfig) -> ModelBundle:
    def loss(params, batch):
        return hybrid.lm_loss(cfg, params, batch["tokens"], batch["labels"])

    def make_prefill(max_len=None):
        def prefill(params, batch):
            B, S = batch["tokens"].shape
            states = hybrid.init_states(cfg, B, max_len or S)
            return hybrid.lm_prefill(cfg, params, batch["tokens"], states)

        return prefill

    def decode_step(params, state, batch):
        return hybrid.lm_decode_step(cfg, params, state, batch["tokens"], batch["pos"])

    return ModelBundle(
        cfg=cfg,
        init=functools.partial(hybrid.init_lm, cfg),
        loss=loss,
        init_state=functools.partial(hybrid.init_states, cfg),
        prefill=make_prefill(),
        decode_step=decode_step,
        input_specs=functools.partial(_text_specs, cfg),
        make_batch=lambda key, shape: _make_text_batch(cfg, shape, key),
        make_prefill=make_prefill,
    )


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
