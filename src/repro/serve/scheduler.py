"""Continuous-batching scheduler: slot-based request table over SlotDecoder.

The scheduler decouples request admission from kernel scheduling (the Specx
/ runtime-support-layer split): requests are admitted whenever a slot is
free — including mid-decode of other requests — decode ticks interleave all
active requests in one jit-stable batched step, and slots are evicted the
moment a request hits EOS, its token budget, or the cache ceiling. Freed
slots are immediately reusable by the next admission, so the server sustains
a full batch under a steady request stream.

Two KV-cache modes:

* ``kv_mode="dense"`` — every slot owns a `max_len`-deep cache
  (`SlotDecoder`); one vmapped decode step per scheduler tick, tokens
  synced to host every tick.
* ``kv_mode="paged"`` — slots share a block-pool cache addressed through a
  scheduler-owned page table (`PagedSlotDecoder`): pages are reserved at
  admission (admission control is page availability, not a slot-count
  proxy), drawn as a request grows, and freed at eviction. Every admission
  prefills the prompt's uncached tail against pages gathered from the pool
  (the whole prompt against null pages when nothing is cached). Each
  scheduler tick runs `sync_interval` fused decode+sample ticks
  device-side, so tokens/positions/done-flags only cross to the host at
  sync points.

``prefix_cache=True`` (paged mode only) adds the radix cache's policy:
admission first asks a refcounted `RadixCache` (serve/prefix_cache.py) for
the longest cached prefix of the prompt: fully-matched pages are forked by
reference into the slot's page table (worst-case reservation shrinks by the
shared pages), a partially-matched boundary page is copy-on-write forked
through the tail prefill's gather, and only the uncached tail runs through
the model. Completion *returns* pages to the cache instead of freeing them;
page pressure LRU-evicts unreferenced cache pages before refusing
admission. The pool's layout and the prefill are the same either way.

With the Runtime's tracer on (``runtime.tracer.enable()``), admission
records ``serve.admit`` with its phases and each step records
``serve.step`` with the slots it decoded and the tokens they gave, and
its ``serve.step.interval`` the KV pages the decode kernel walks against
the table pages (ARCHITECTURE.md, "Tracing"); off, each span costs one
attribute check.

Token semantics match the serial `ServeEngine.generate` exactly in both
modes: the first emitted token is the greedy pick from the prefill logits;
each subsequent token comes from one decode step at the request's own
position.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from repro.core.runtime import Runtime
from repro.models.model_zoo import ModelBundle

from .batching import PagedSlotDecoder, SlotDecoder
from .prefix_cache import RadixCache


@dataclasses.dataclass
class Request:
    """One generation request. `max_new_tokens` bounds the decode length;
    `eos_id` (optional) triggers early eviction."""

    rid: str
    prompt: Sequence[int]
    max_new_tokens: int
    eos_id: Optional[int] = None


@dataclasses.dataclass
class FinishedRequest:
    rid: str
    prompt: List[int]
    tokens: List[int]
    finish_reason: str  # "length" | "eos" | "max_len"


@dataclasses.dataclass
class SchedulerProgress:
    """Snapshot for the streaming front door: tokens emitted so far per
    *active* request (copies), plus the KV-pool occupancy in paged mode
    (None/None in dense mode — there is no shared pool to meter).
    `free_slots` is the admission headroom a fleet router load-balances on
    (reported upstream over the control channel). `prefix` carries the
    radix cache's counters (lookups/hits/hit_rate/cached_pages/...) when
    the prefix cache is enabled, else None."""

    requests: Dict[str, List[int]]
    pages_free: Optional[int] = None
    pages_used: Optional[int] = None
    free_slots: int = 0
    prefix: Optional[dict] = None


@dataclasses.dataclass
class _Active:
    """Request-table row: one admitted request bound to a decoder slot."""

    request: Request
    slot: int
    emitted: List[int]
    pages: List[int] = dataclasses.field(default_factory=list)  # drawn pages
    reserved_left: int = 0  # reserved-but-undrawn pages
    #: prefix-cache pages forked by reference (head of the page-table row);
    #: the row holds one pool reference per shared page while active
    shared: List[int] = dataclasses.field(default_factory=list)


class ContinuousBatchingScheduler:
    def __init__(
        self,
        model: ModelBundle,
        params,
        *,
        max_batch: int = 8,
        max_len: int = 256,
        runtime: Optional[Runtime] = None,
        kv_mode: str = "dense",
        page_size: int = 16,
        pool_pages: Optional[int] = None,
        sync_interval: int = 8,
        prefix_cache: bool = False,
    ):
        if kv_mode not in ("dense", "paged"):
            raise ValueError(f"kv_mode must be 'dense' or 'paged', got {kv_mode!r}")
        if prefix_cache and kv_mode != "paged":
            raise ValueError(
                "prefix_cache requires kv_mode='paged' (prefixes are shared "
                "as pool pages; dense slots own private caches)"
            )
        self.kv_mode = kv_mode
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefix: Optional[RadixCache] = None
        if kv_mode == "dense":
            self.decoder = SlotDecoder(
                model, params, max_slots=max_batch, max_len=max_len, runtime=runtime
            )
        else:
            self.decoder = PagedSlotDecoder(
                model, params, max_slots=max_batch, max_len=max_len,
                page_size=page_size, pool_pages=pool_pages,
                sync_interval=sync_interval, runtime=runtime,
            )
            if prefix_cache:
                self.prefix = RadixCache(self.decoder.kv, self.decoder.layout.page_size)
            #: scheduler-owned page table: logical page j of slot s ->
            #: physical pool page (0 = null/unallocated)
            self._page_table = np.zeros(
                (max_batch, self.decoder.layout.n_pages_seq), dtype=np.int32
            )
            #: host mirror of per-slot positions (set at admission, refreshed
            #: at every sync point) — growth never reads back from device
            self._pos_host = np.zeros((max_batch,), dtype=np.int32)
        # multimodal prefixes occupy cache positions before the text prompt
        self._prefix = model.cfg.vision_tokens if model.cfg.family == "vlm" else 0
        self._table: List[Optional[_Active]] = [None] * max_batch
        self._free: deque[int] = deque(range(max_batch))
        self._finished: List[FinishedRequest] = []
        self.ticks = 0
        self._tracer = self.decoder.rt.tracer

    # -- introspection ------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.max_batch - len(self._free)

    def active_ids(self) -> List[str]:
        return [row.request.rid for row in self._table if row is not None]

    def active_progress(self) -> SchedulerProgress:
        """Streaming snapshot: what the front door diffs against its
        per-request high-water marks to form delta chunks, plus pool
        occupancy in paged mode."""
        requests = {
            row.request.rid: list(row.emitted)
            for row in self._table
            if row is not None
        }
        if self.kv_mode == "paged":
            kv = self.decoder.kv
            return SchedulerProgress(
                requests=requests, pages_free=kv.pages_free,
                pages_used=kv.pages_used, free_slots=self.free_slots,
                prefix=self.prefix.stats() if self.prefix is not None else None,
            )
        return SchedulerProgress(requests=requests, free_slots=self.free_slots)

    # -- admission (any time, including mid-decode) -------------------------
    def try_admit(self, request: Request) -> bool:
        """Prefill `request` and seat it in a free slot. Returns False when
        the table is full — or, in paged mode, when the KV pool cannot
        reserve the request's worst-case pages (page-availability admission
        control); requests finishing at their very first token are completed
        without consuming a slot."""
        with self._tracer.span("serve.admit", rid=request.rid,
                               prompt_len=len(request.prompt)) as sp:
            outcome = self._admit(request, sp)
            sp.set(outcome=outcome)
        return outcome in ("seated", "finished")

    def _admit(self, request: Request, sp) -> str:
        """`try_admit`'s body; returns its outcome: ``seated``, ``finished``
        (at the first token), ``no_slot`` or ``no_pages``."""
        if request.max_new_tokens < 1:
            raise ValueError(f"request {request.rid!r}: max_new_tokens must be >= 1")
        prompt_len = len(request.prompt)
        total_positions = self._prefix + prompt_len + request.max_new_tokens
        if total_positions > self.max_len:
            raise ValueError(
                f"request {request.rid!r} needs {prompt_len + request.max_new_tokens} "
                f"cache positions (+{self._prefix} prefix), scheduler max_len is {self.max_len}"
            )
        if any(row is not None and row.request.rid == request.rid for row in self._table):
            raise ValueError(f"request id {request.rid!r} is already active")
        if not self._free:
            return "no_slot"

        tr = self._tracer
        pages_total = new_pages = 0
        m = None  # prefix-cache match (None when the cache is off)
        if self.kv_mode == "paged":
            layout = self.decoder.layout
            kv = self.decoder.kv
            pages_total = layout.pages_for(total_positions)
            n_shared = 0
            with tr.span("serve.admit.match"):
                if self.prefix is not None:
                    m = self.prefix.match(request.prompt)
                    n_shared = len(m.nodes)
                # shared pages are already resident: only the new ones need
                # reserving (the worst case shrinks with the matched prefix)
                new_pages = pages_total - n_shared
                if new_pages > kv.capacity:
                    raise ValueError(
                        f"request {request.rid!r} needs {new_pages} KV pages, "
                        f"pool capacity is {kv.capacity}"
                    )
                if m is not None:
                    self.prefix.lock(m)
            with tr.span("serve.admit.reserve") as rsp:
                evicted = 0
                reserved = kv.reserve(new_pages)
                if not reserved and self.prefix is not None:
                    # page pressure: LRU-evict cache-only pages before refusing
                    evicted += self.prefix.evict(new_pages - kv.pages_available)
                    reserved = kv.reserve(new_pages)
                    if not reserved and m is not None and self.active_count == 0:
                        # nothing in flight will ever free pages, and our
                        # own lock may be what pins every evictable page:
                        # demote the match to a miss so eviction can reclaim
                        # them — returning False here would livelock serve()
                        self.prefix.unlock(m)
                        m = None
                        new_pages = pages_total
                        if new_pages > kv.capacity:
                            raise ValueError(
                                f"request {request.rid!r} needs {new_pages} KV "
                                f"pages uncached, pool capacity is {kv.capacity}"
                            )
                        evicted += self.prefix.evict(new_pages - kv.pages_available)
                        reserved = kv.reserve(new_pages)
                rsp.set(evicted=evicted)
            if not reserved:
                if m is not None:
                    self.prefix.unlock(m)
                return "no_pages"  # retry once pages free up

        try:
            with tr.span("serve.admit.prefill") as psp:
                if self.kv_mode == "dense":
                    psp.set(tail_len=prompt_len)
                    first, state = self.decoder.prefill(request.prompt)
                else:
                    # a miss (no cache, no match, or a demoted match)
                    # gathers null pages at offset 0
                    off = m.matched_len if m is not None else 0
                    row = self._gather_row(m)
                    if self.prefix is not None:
                        sp.set(matched_len=off)
                    psp.set(tail_len=prompt_len - off)
                    first, state = self.decoder.prefill_prefix(
                        request.prompt[off:], row, off
                    )
        except BaseException:
            if new_pages:  # a failed prefill must not strand the reservation
                self.decoder.kv.free((), unreserve=new_pages)
            if m is not None:
                self.prefix.unlock(m)
            raise
        emitted = [first]
        if request.max_new_tokens == 1 or first == request.eos_id:
            if new_pages:
                self.decoder.kv.free((), unreserve=new_pages)
            if self.prefix is not None:
                if m is not None:
                    self.prefix.unlock(m)  # nothing committed: no donation
                self.prefix.note(m, prompt_len)
            self._finished.append(self._finish(request, emitted))
            return "finished"
        with tr.span("serve.admit.commit"):
            slot = self._free.popleft()
            if self.kv_mode == "dense":
                self.decoder.load(slot, state, first, self._prefix + prompt_len)
                row = _Active(request=request, slot=slot, emitted=emitted)
            else:
                layout = self.decoder.layout
                shared = m.shared_pages if m is not None else []
                n_shared = len(shared)
                # draw pages for everything prefill wrote + the first decode
                # write; the rest of the reservation is drawn as the slot
                # grows
                pages_now = layout.pages_for(self._prefix + prompt_len + 1)
                drawn = self.decoder.kv.draw(pages_now - n_shared)
                self._page_table[slot, :] = 0
                self._page_table[slot, :n_shared] = shared
                self._page_table[slot, n_shared : n_shared + len(drawn)] = drawn
                # shared pages are read-only: the commit scatters the dense
                # state's prefix region into the null page instead
                commit_row = self._page_table[slot].copy()
                commit_row[:n_shared] = 0
                self.decoder.load(
                    slot, state, first, self._prefix + prompt_len,
                    steps_left=request.max_new_tokens - 1,
                    eos_id=request.eos_id,
                    capacity=pages_total * layout.page_size,
                    full_row=commit_row,
                )
                if self.prefix is not None:
                    if m is not None:
                        self.prefix.unlock_boundary(m)  # its content is copied
                    self.prefix.note(m, prompt_len)
                self._pos_host[slot] = self._prefix + prompt_len
                row = _Active(
                    request=request, slot=slot, emitted=emitted,
                    pages=drawn, reserved_left=pages_total - pages_now,
                    shared=shared,
                )
            self._table[slot] = row
        return "seated"

    def _gather_row(self, m) -> np.ndarray:
        """Page-table row for the tail prefill's prefix gather: the matched
        pages (by reference) plus the copy-on-write boundary source,
        null-padded — padded gathers read the null page and sit past every
        position the tail can attend. All null without a match `m`."""
        row = np.zeros((self.decoder.layout.n_pages_seq,), dtype=np.int32)
        if m is not None:
            shared = m.shared_pages
            row[: len(shared)] = shared
            if m.boundary is not None:
                row[len(shared)] = m.boundary.page
        return row

    def _finish(self, request: Request, emitted: List[int]) -> FinishedRequest:
        if emitted and emitted[-1] == request.eos_id:
            reason = "eos"
        elif len(emitted) >= request.max_new_tokens:
            reason = "length"
        else:
            reason = "max_len"
        return FinishedRequest(
            rid=request.rid,
            prompt=list(request.prompt),
            tokens=emitted,
            finish_reason=reason,
        )

    # -- one scheduler tick --------------------------------------------------
    def step(self) -> List[FinishedRequest]:
        """Advance decoding and evict every request that completed. Also
        drains requests that finished during admission. Dense mode runs one
        batched decode tick; paged mode runs one fused `sync_interval`-tick
        interval device-side and harvests at the sync point. Returns the
        newly finished requests."""
        done, self._finished = self._finished, []
        if self.active_count == 0:
            return done
        with self._tracer.span("serve.step") as sp:
            if self.kv_mode == "dense":
                return done + self._step_dense(sp)
            return done + self._step_paged(sp)

    def _step_dense(self, sp) -> List[FinishedRequest]:
        done: List[FinishedRequest] = []
        sp.set(slots=self.max_batch, tokens=self.active_count)
        new_tokens = self.decoder.step()
        self.ticks += 1
        # the eviction ceiling comes from the decoder's actual allocated
        # cache depth, not a separately-tracked token budget
        capacity = self.decoder.cache_capacity
        for slot, row in enumerate(self._table):
            if row is None:
                continue
            tok = int(new_tokens[slot])
            row.emitted.append(tok)
            req = row.request
            hit_eos = tok == req.eos_id
            out_of_budget = len(row.emitted) >= req.max_new_tokens
            out_of_cache = int(self.decoder.pos[slot]) >= capacity
            if hit_eos or out_of_budget or out_of_cache:
                done.append(self._finish(req, row.emitted))
                self._table[slot] = None
                self._free.append(slot)
        return done

    def _grow_pages(self) -> int:
        """Before an interval: draw enough reserved pages for every active
        slot to cover `sync_interval` more positions. Reservations were made
        at admission, so a draw can never fail mid-flight. Returns the
        number of pages drawn."""
        layout = self.decoder.layout
        pos = self._pos_host
        n_drawn = 0
        for slot, row in enumerate(self._table):
            if row is None or not row.reserved_left:
                continue
            target = layout.pages_for(int(pos[slot]) + self.decoder.sync_interval)
            filled = len(row.shared) + len(row.pages)
            delta = min(target - filled, row.reserved_left)
            if delta > 0:
                drawn = self.decoder.kv.draw(delta)
                self._page_table[slot, filled : filled + delta] = drawn
                row.pages.extend(drawn)
                row.reserved_left -= delta
                n_drawn += delta
        return n_drawn

    def _kv_pages(self) -> Dict[str, int]:
        """Pages the paged decode kernel walks per tick at an interval's
        start, summed over the active slots (`kv_pages_live`: each slot's
        live range), against the table pages they hold (`kv_pages_table`)."""
        layout = self.decoder.layout
        live = [layout.pages_for(int(self._pos_host[slot]) + 1)
                for slot, row in enumerate(self._table) if row is not None]
        return {"kv_pages_live": sum(live),
                "kv_pages_table": len(live) * layout.n_pages_seq}

    def _step_paged(self, sp) -> List[FinishedRequest]:
        done: List[FinishedRequest] = []
        tr = self._tracer
        with tr.span("serve.step.grow") as grow:
            grow.set(drawn=self._grow_pages())
        with tr.span("serve.step.interval") as interval:
            if tr.enabled:
                interval.set(**self._kv_pages())
            out_buf, done_mask, pos = self.decoder.run_interval(self._page_table)
            if tr.enabled:  # the unit has compiled: its memory is a cache read
                interval.set(**self.decoder.stats())
        self._pos_host[:] = pos
        self.ticks += self.decoder.sync_interval
        with tr.span("serve.step.harvest"):
            for slot, row in enumerate(self._table):
                if row is None:
                    continue
                ticks = out_buf[slot]
                row.emitted.extend(int(t) for t in ticks[ticks >= 0])
                if done_mask[slot]:
                    done.append(self._finish(row.request, row.emitted))
                    if self.prefix is not None:
                        # return pages through the radix cache: full pages of
                        # the written sequence are donated/shared, the rest
                        # freed. Positions written: the prompt plus every
                        # emitted token that was fed back (the last one never
                        # was).
                        seq = list(row.request.prompt) + row.emitted[:-1]
                        self.prefix.commit(seq, row.shared + row.pages)
                        self.decoder.kv.free((), unreserve=row.reserved_left)
                    else:
                        self.decoder.kv.free(row.pages, unreserve=row.reserved_left)
                    self._page_table[slot, :] = 0
                    self._table[slot] = None
                    self._free.append(slot)
        if tr.enabled:
            sp.set(slots=self.max_batch * self.decoder.sync_interval,
                   tokens=int((out_buf >= 0).sum()), finished=len(done))
        return done

    # -- batch driver --------------------------------------------------------
    def serve(self, requests: Iterable[Request]) -> Dict[str, FinishedRequest]:
        """Drive a full workload: admit whenever a slot frees up, tick until
        every request has completed. Returns results keyed by request id."""
        backlog = deque(requests)
        results: Dict[str, FinishedRequest] = {}
        expected = len(backlog)
        n_done = 0  # count finishes, not dict keys: duplicate rids must not hang
        while n_done < expected:
            while backlog and self.try_admit(backlog[0]):
                backlog.popleft()
            for fin in self.step():
                results[fin.rid] = fin
                n_done += 1
        return results
