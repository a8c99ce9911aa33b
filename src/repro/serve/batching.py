"""Batched decode core: per-request decoder states packed into fixed slots.

`SlotDecoder` owns `max_slots` state slots sized for `max_len` positions.
Admission prefetches one request at a time (B=1 prefill with cache headroom)
and scatters the resulting state into a free slot; every tick then runs ONE
vmapped decode step over all slots — shapes never change as requests of
different lengths join and leave, so the decode execution unit compiles
exactly once and stays jit-stable for the lifetime of the server.

`PagedSlotDecoder` is the paged, device-resident variant: the KV caches live
in a shared block pool (`serve/kv_pool.py`) addressed through a page table,
and the decode loop is fused — `sync_interval` decode+sample ticks run as
ONE execution unit with tokens, positions, and done-flags staying on device
throughout; the host sees a small (slots, sync_interval) token buffer and
the done mask once per interval instead of a device round-trip per token.

All computation is dispatched through a HiCR compute manager obtained from a
`Runtime` facade (registry-built, backend-agnostic): prefill, the batched
decode step, and the state scatter are execution units; the decoder itself
only moves small host-side arrays (last tokens, positions).

Text-only protocol: requests supply token prompts; families that need extra
prefill inputs (VLM patches, audio frames) are out of scope here.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime import Runtime
from repro.models.model_zoo import ModelBundle

from .kv_pool import PagedKVPool


class SlotDecoder:
    def __init__(
        self,
        model: ModelBundle,
        params,
        *,
        max_slots: int = 8,
        max_len: int = 256,
        runtime: Optional[Runtime] = None,
    ):
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.rt = runtime or Runtime("jaxdev")
        cm = self.rt.compute_manager

        prefill_fn = model.make_prefill(max_len) if model.make_prefill else model.prefill
        self._prefill_unit = cm.create_execution_unit(
            lambda p, b: prefill_fn(p, b), name="prefill", jit=True
        )

        def batched_decode(p, states, tokens, pos):
            # states: leaves (max_slots, 1, ...); tokens (max_slots, 1, 1);
            # pos (max_slots,). vmap maps the slot axis so each slot decodes
            # as an independent B=1 request at its own position.
            def one(state, tok, position):
                logits, new_state = model.decode_step(
                    p, state, {"tokens": tok, "pos": position}
                )
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)[0], new_state

            return jax.vmap(one, in_axes=(0, 0, 0))(states, tokens, pos)

        self._decode_unit = cm.create_execution_unit(
            batched_decode, name="batched_decode", jit=True
        )

        def pack(bufs, state, slot):
            return jax.tree_util.tree_map(
                lambda b, leaf: jax.lax.dynamic_update_index_in_dim(b, leaf, slot, 0),
                bufs,
                state,
            )

        self._pack_unit = cm.create_execution_unit(pack, name="pack_slot", jit=True)

        self._states = None  # stacked state pytree, lazily sized from prefill
        self._cache_capacity: Optional[int] = None
        self.last_tokens = np.zeros((max_slots,), dtype=np.int32)
        self.pos = np.zeros((max_slots,), dtype=np.int32)

    @property
    def cache_capacity(self) -> int:
        """Cache positions a slot can actually hold, derived from the
        allocated state buffers (the scheduler's eviction ceiling) — not a
        separately-tracked token budget that could drift from them."""
        if self._cache_capacity is not None:
            return self._cache_capacity
        if self._states is not None and self.model.cfg.family in ("dense", "moe", "vlm"):
            # KV leaves are (..., S_buf, KV, hd); the deepest buffer (global
            # layers; ring layers are shorter) is the real ceiling
            self._cache_capacity = max(
                leaf.shape[-3]
                for leaf in jax.tree_util.tree_leaves(self._states)
                if leaf.ndim >= 4
            )
            return self._cache_capacity
        return self.max_len

    # -- admission ----------------------------------------------------------
    def prefill(self, prompt: Sequence[int]):
        """B=1 prefill with max_len cache headroom. Returns (first greedy
        token, decoder state). Compiles once per distinct prompt length."""
        tokens = jnp.asarray(np.asarray(prompt, dtype=np.int32)[None, :])
        logits, state = self.rt.run(self._prefill_unit, self.params, {"tokens": tokens})
        first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
        return first, state

    def load(self, slot: int, state, last_token: int, pos: int) -> None:
        """Scatter a prefilled B=1 state into `slot` of the packed buffers."""
        if not 0 <= slot < self.max_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.max_slots})")
        if self._states is None:
            self._states = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros((self.max_slots,) + leaf.shape, leaf.dtype),
                state,
            )
        self._states = self.rt.run(
            self._pack_unit, self._states, state, jnp.int32(slot)
        )
        self.last_tokens[slot] = last_token
        self.pos[slot] = pos

    # -- one decode tick ----------------------------------------------------
    def step(self) -> np.ndarray:
        """Advance every slot one token. Returns the (max_slots,) array of
        new greedy tokens; values in slots without a live request are
        garbage and must be ignored by the caller."""
        if self._states is None:
            raise RuntimeError("no request was ever loaded into the decoder")
        tokens = jnp.asarray(self.last_tokens)[:, None, None]
        new_tokens, self._states = self.rt.run(
            self._decode_unit,
            self.params,
            self._states,
            tokens,
            jnp.asarray(self.pos),
        )
        new_tokens = np.asarray(new_tokens)
        self.last_tokens = new_tokens.copy()
        self.pos = self.pos + 1
        return new_tokens


class PagedSlotDecoder:
    """Paged, device-resident decode core.

    KV state lives in a shared block pool (one `(pages, page, KV, hd)`
    tensor per layer, allocated once through the HiCR MemoryManager); each
    slot addresses its pages through the scheduler-owned page table. An
    admission is `prefill_prefix` (the prompt's uncached tail against pages
    gathered from the pool; all of it against null pages on a miss) and
    `load` (its commit into the slot's pages). Decode control state — last
    tokens, positions, done flags, per-slot budgets — stays on device:
    `run_interval()` executes `sync_interval` fused decode+sample ticks as
    ONE execution unit and transfers only the per-interval token buffer and
    done mask back to the host. A slot that finishes mid-interval freezes
    in place (its writes are routed to the null page) and is harvested at
    the next sync point, so outputs are token-identical to the per-tick
    dense path.

    The pool is written in place: each tick writes only the new K/V rows.
    `commit_and_arm` takes the pools and the control table donated, so a
    reference passed to it is dead; `kv.pools` and `ctl` are rebound from
    every unit's result. The fused unit leaves the pools it is given intact
    (a caller may keep them across an interval), at one copy of the pool
    per interval.
    """

    def __init__(
        self,
        model: ModelBundle,
        params,
        *,
        max_slots: int = 8,
        max_len: int = 256,
        page_size: int = 16,
        pool_pages: Optional[int] = None,
        sync_interval: int = 8,
        runtime: Optional[Runtime] = None,
    ):
        if model.paged_ops is None:
            raise ValueError(
                f"model family {model.cfg.family!r} has no paged KV-cache path; "
                "use kv_mode='dense'"
            )
        if sync_interval < 1:
            raise ValueError("sync_interval must be >= 1")
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.sync_interval = sync_interval
        self.rt = runtime or Runtime("jaxdev")
        po = model.paged_ops
        self.layout = po.layout(
            max_slots=max_slots, max_len=max_len, page_size=page_size,
            num_pages=pool_pages,
        )
        self.kv = PagedKVPool(self.rt, model, self.layout)

        cm = self.rt.compute_manager
        layout = self.layout

        def prefix_prefill(p, pools, row, tokens, off):
            # greedy pick fused into the unit: admission transfers one
            # int32, not a logits row, and dispatches no eager argmax op
            logits, state = po.prefix_prefill(layout, p, pools, row, tokens, off)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), state

        self._prefix_unit = cm.create_execution_unit(
            prefix_prefill, name="prefix_prefill", jit=True
        )

        # control columns of the (slots, 6) device-resident table
        TOK, POS, DONE, STEPS, EOS, CAP = range(6)

        def commit_and_arm(pools, state, full_row, ctl, arm):
            """One dispatch per admission: scatter the prefilled dense cache
            into the slot's pages AND arm the slot's control row. `arm` is
            [slot, token, pos, steps_left, eos, cap] — a single int32 upload."""
            pools = po.commit_prefill(layout, pools, state, full_row)
            row = jnp.stack([arm[1], arm[2], jnp.int32(0), arm[3], arm[4], arm[5]])
            return pools, ctl.at[arm[0]].set(row)

        # the pools and the control table are donated: the unit writes them
        # in place, and the decoder keeps only the references it returns
        self._commit_unit = cm.create_execution_unit(
            commit_and_arm, name="commit_and_arm", jit=True, donate_argnums=(0, 3)
        )

        K = sync_interval

        def fused_ticks(p, pools, table, ctl):
            """K decode+sample ticks, device-resident. Emits a (slots, K)
            buffer of sampled tokens (-1 where the slot was already done);
            freezes a slot the tick it hits eos / budget / capacity."""
            out0 = jnp.full((ctl.shape[0], K), -1, jnp.int32)

            def tick(i, carry):
                pools, ctl, out = carry

                def run(c):
                    pools, ctl, out = c
                    tokens, pos = ctl[:, TOK], ctl[:, POS]
                    active = ctl[:, DONE] == 0
                    logits, pools = po.decode_step(
                        layout, p, pools, table, tokens, pos, active
                    )
                    new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    tok = jnp.where(active, new_tok, tokens)
                    out = out.at[:, i].set(jnp.where(active, tok, -1))
                    live = active.astype(jnp.int32)
                    steps_left = ctl[:, STEPS] - live
                    pos = pos + live
                    done = ~active | (
                        active
                        & ((tok == ctl[:, EOS]) | (steps_left <= 0) | (pos >= ctl[:, CAP]))
                    )
                    ctl = jnp.stack(
                        [tok, pos, done.astype(jnp.int32), steps_left,
                         ctl[:, EOS], ctl[:, CAP]], axis=1,
                    )
                    return pools, ctl, out

                # batch fully drained mid-interval: skip the model entirely
                return jax.lax.cond(jnp.all(ctl[:, DONE] == 1), lambda c: c, run, carry)

            pools, ctl, out = jax.lax.fori_loop(0, K, tick, (pools, ctl, out0))
            # single host-transfer payload: [tokens x K | done | pos] per slot
            summary = jnp.concatenate([out, ctl[:, [DONE, POS]]], axis=1)
            return pools, ctl, summary

        self._fused_unit = cm.create_execution_unit(
            fused_ticks, name=f"fused_decode_x{K}", jit=True
        )
        self._unit_memory: Optional[Dict[str, int]] = None

        # device-resident control table (host reads a summary per interval);
        # DONE=1 everywhere: free slots never decode
        ctl0 = np.zeros((max_slots, 6), np.int32)
        ctl0[:, DONE] = 1
        ctl0[:, EOS] = -1  # -1: no eos (real tokens are >= 0)
        self.ctl = jnp.asarray(ctl0)

    # -- admission ----------------------------------------------------------
    def prefill_prefix(self, tail: Sequence[int], gather_row: np.ndarray, offset: int):
        """Prefill only the uncached `tail` of a prompt against the cached
        prefix whose pages `gather_row` names (null-padded); `offset` is the
        matched prefix length in tokens (0 on a miss or with no prefix
        cache: the whole prompt is the tail, the row all null). Returns
        (first greedy token, full-depth dense state ready to commit into
        pages). Compiles once per tail length; `offset` is traced, so match
        depth never recompiles."""
        tokens = jnp.asarray(np.asarray(tail, dtype=np.int32)[None, :])
        first, state = self.rt.run(
            self._prefix_unit, self.params, self.kv.pools,
            jnp.asarray(np.asarray(gather_row, dtype=np.int32)),
            tokens, jnp.int32(offset),
        )
        return int(np.asarray(first)[0]), state

    def load(
        self,
        slot: int,
        state,
        last_token: int,
        pos: int,
        *,
        steps_left: int,
        eos_id: Optional[int],
        capacity: int,
        full_row: np.ndarray,
    ) -> None:
        """Commit a prefilled dense state into `slot`'s pool pages and arm
        its device-side control row. `full_row` is the slot's page-table row
        (0-padded past the pages drawn so far); `capacity` is the position
        ceiling implied by the slot's page reservation."""
        if not 0 <= slot < self.max_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.max_slots})")
        arm = np.asarray(
            [slot, last_token, pos, steps_left,
             eos_id if eos_id is not None else -1, capacity],
            dtype=np.int32,
        )
        self.kv.pools, self.ctl = self.rt.run(
            self._commit_unit, self.kv.pools, state,
            jnp.asarray(full_row, jnp.int32), self.ctl, jnp.asarray(arm),
        )

    # -- one fused interval --------------------------------------------------
    def run_interval(self, full_table: np.ndarray):
        """Run `sync_interval` fused ticks against the current page table.
        Returns (token_buffer (slots, K) with -1 for inactive ticks,
        done mask (slots,), positions (slots,)) as host arrays — the only
        device->host traffic of the interval."""
        self.kv.pools, self.ctl, summary = self.rt.run(
            self._fused_unit,
            self.params, self.kv.pools, jnp.asarray(full_table, jnp.int32), self.ctl,
        )
        summary = np.asarray(summary)  # the interval's only device->host copy
        K = self.sync_interval
        return summary[:, :K], summary[:, K].astype(bool), summary[:, K + 1]

    def stats(self) -> Dict[str, int]:
        """What the fused unit's executable reports, read once and kept:
        `pool_alias_bytes`, the bytes of its inputs that its outputs reuse
        in place, and `unit_temp_bytes`, its scratch. The unit is staged for
        the live pools and control table, as `run_interval` stages it, so
        after an interval it is found compiled. Empty on a backend whose
        units are not staged."""
        fn = self._fused_unit.fn
        if self._unit_memory is None and not hasattr(fn, "lower"):  # not staged
            self._unit_memory = {}
        if self._unit_memory is None:
            table = np.zeros((self.max_slots, self.layout.n_pages_seq), np.int32)
            with jax.default_device(self.rt.processing_unit.context):
                lowered = fn.lower(self.params, self.kv.pools, jnp.asarray(table), self.ctl)
                memory = lowered.compile().memory_analysis()
            self._unit_memory = {
                "pool_alias_bytes": int(memory.alias_size_in_bytes),
                "unit_temp_bytes": int(memory.temp_size_in_bytes),
            }
        return dict(self._unit_memory)
