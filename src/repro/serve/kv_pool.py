"""Paged KV-cache pool: HiCR-registered block-pool tensors + page accounting.

This is the serve-side owner of the paper's memory-management operations
(§3.1.3) applied to KV-cache serving: the per-layer block-pool tensors are
allocated ONCE at construction and registered with the runtime's
`MemoryManager` as local memory slots; every subsequent cache operation in
the hot path moves page *indices*, never pages — admission reserves pages,
decode growth draws them, eviction frees them, all against a
`MemorySlotPool` (core/managers.py) whose null page 0 is pinned so inactive
slots' masked writes can never land on live data.

The tensors themselves are written in place: `commit_and_arm` takes them
donated and returns them in the same buffers, `fused_decode_xK` copies
them once per call and writes the copy, and `pools` is rebound to what
each returns. The registered slots record the allocation the pool handed
to the compute layer (its first buffers, which the first commit consumes)
— the same
allocate-once/place-many contract Specx task views and HDArray slices
expose, with the runtime, not the kernel author, owning placement.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.core.managers import MemorySlotPool


class PagedKVPool:
    """Block-pool KV cache for the paged serve path.

    Parameters
    ----------
    runtime:
        Runtime whose `MemoryManager` registers the pool tensors (a runtime
        without a memory role skips registration but keeps accounting).
    model:
        `ModelBundle` with `paged_ops` (transformer families).
    layout:
        `PagedLayout` from `model.paged_ops.layout(...)`.
    """

    def __init__(self, runtime, model, layout):
        if model.paged_ops is None:
            raise ValueError(
                f"model family {model.cfg.family!r} has no paged KV-cache path; "
                "use kv_mode='dense'"
            )
        self.layout = layout
        #: Per-layer block-pool tensors (the device-resident cache state;
        #: donated to the commit unit and rebound to what the commit and
        #: decode execution units return).
        self.pools = model.paged_ops.init_pools(layout)

        leaves = jax.tree_util.tree_leaves(self.pools)
        self.slots: List = []
        mm = getattr(runtime, "memory_manager", None)
        if mm is not None:
            space = mm.memory_spaces()[0]
            for leaf in leaves:
                try:
                    self.slots.append(mm.register_tensor_slot(space, leaf))
                except TypeError:
                    # host-backed managers register a host view of the array
                    self.slots.append(
                        mm.register_tensor_slot(space, np.asarray(leaf))
                    )

        # one logical page spans every layer's pool: aggregate their bytes
        page_bytes = sum(leaf.nbytes for leaf in leaves) // layout.num_pages
        self.accounting = MemorySlotPool(
            max(1, page_bytes),
            layout.num_pages,
            backing=tuple(self.slots),
            reserved_blocks=(0,),  # null page: padding + inactive-write sink
        )

    # -- page operations (hot path: indices only) ----------------------------
    def can_admit(self, n_pages: int) -> bool:
        return self.accounting.can_reserve(n_pages)

    def reserve(self, n_pages: int) -> bool:
        return self.accounting.reserve(n_pages)

    def draw(self, n_pages: int) -> List[int]:
        return self.accounting.draw(n_pages)

    def free(self, pages: Sequence[int], *, unreserve: int = 0) -> None:
        """Drop one holder per page (a finished slot returning its pages)
        and release whatever part of its reservation was never drawn."""
        self.accounting.free(pages)
        if unreserve:
            self.accounting.unreserve(unreserve)

    # -- shared pages (prefix cache: fork-by-reference) -----------------------
    def acquire(self, pages: Sequence[int]) -> None:
        """Add one holder to each page (share an existing allocation)."""
        self.accounting.acquire(pages)

    # paper-facing alias: fork a page table entry by reference
    share = acquire

    def release(self, pages: Sequence[int]) -> None:
        """Drop one holder per page; last holder frees the page."""
        self.accounting.release(pages)

    def refcount(self, page: int) -> int:
        return self.accounting.refcount(page)

    # -- introspection --------------------------------------------------------
    @property
    def pages_free(self) -> int:
        return self.accounting.blocks_free

    @property
    def pages_available(self) -> int:
        """Free pages not spoken for by an outstanding reservation."""
        return self.accounting.blocks_available

    @property
    def pages_used(self) -> int:
        return self.accounting.blocks_used

    @property
    def capacity(self) -> int:
        return self.accounting.capacity
