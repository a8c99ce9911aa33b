"""JAX-device backend: the ACL / OpenCL analog (paper §4.2).

Exposes the devices visible to JAX (TPU chips on real hardware, CpuDevice
here) as HiCR devices; memory slots are device buffers; execution units are
staged (jit-compiled) functions whose dispatch is asynchronous — matching
HiCR's requirement that computation is carried out asynchronously with
blocking/non-blocking completion queries.

Adaptation note (DESIGN.md §2): jax.Arrays are immutable, so "copying into"
a device slot rebinds the slot's handle to a functionally-updated array; the
slot object is the mutable cell. VMEM is compiler-managed on TPU and is not
exposed as an allocatable memory space.
"""
from __future__ import annotations

import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.definitions import (
    ComputeResourceKind,
    InvalidMemcpyDirectionError,
    LifetimeError,
    MemcpyDirection,
    MemorySpaceKind,
    ProcessingUnitStatus,
)
from repro.core.events import Event, Future
from repro.core.managers import (
    CommunicationManager,
    ComputeManager,
    MemoryManager,
    TopologyManager,
)
from repro.core.stateful import ExecutionState, LocalMemorySlot, ProcessingUnit
from repro.core.stateless import (
    ComputeResource,
    Device,
    ExecutionUnit,
    MemorySpace,
    Topology,
)

def _memory_bytes(d) -> int:
    """Bytes a device's memory holds: the accelerator's reported
    `bytes_limit`, or host RAM for CPU devices (which report no stats)."""
    stats = d.memory_stats() or {}
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if d.platform == "cpu":
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    raise RuntimeError(
        f"{d.platform} device {d.id} ({d.device_kind}) reports no bytes_limit"
    )


class JaxTopologyManager(TopologyManager):
    backend_name = "jaxdev"

    def query_topology(self) -> Topology:
        devices = []
        for d in jax.local_devices():
            dev_id = f"jax-{d.platform}-{d.id}"
            cr = ComputeResource(
                kind=(
                    ComputeResourceKind.TPU_TENSORCORE.value
                    if d.platform == "tpu"
                    else ComputeResourceKind.CPU_CORE.value
                ),
                index=d.id,
                device_id=dev_id,
                attributes={"platform": d.platform},
            )
            ms = MemorySpace(
                kind=(
                    MemorySpaceKind.DEVICE_HBM.value
                    if d.platform == "tpu"
                    else MemorySpaceKind.HOST_RAM.value
                ),
                index=d.id,
                device_id=dev_id,
                size_bytes=_memory_bytes(d),
            )
            devices.append(
                Device(
                    device_id=dev_id,
                    kind=d.platform,
                    compute_resources=(cr,),
                    memory_spaces=(ms,),
                    attributes={"jax_id": d.id},
                )
            )
        return Topology(devices=tuple(devices))


def _jax_device_for(space: MemorySpace):
    jid = int(space.device_id.rsplit("-", 1)[1])
    for d in jax.local_devices():
        if d.id == jid:
            return d
    raise LookupError(f"no jax device for memory space {space.device_id}")


class JaxMemoryManager(MemoryManager):
    backend_name = "jaxdev"

    def __init__(self):
        self._spaces = tuple(JaxTopologyManager().query_topology().all_memory_spaces())

    def memory_spaces(self) -> Sequence[MemorySpace]:
        return self._spaces

    def allocate_local_memory_slot(self, space: MemorySpace, size_bytes: int) -> LocalMemorySlot:
        self._check_space(space)
        if size_bytes <= 0:  # shared MemoryManager contract (conformance)
            raise ValueError("allocation size must be positive")
        arr = jax.device_put(jnp.zeros((size_bytes,), dtype=jnp.uint8), _jax_device_for(space))
        return LocalMemorySlot(space, size_bytes, arr)

    def register_local_memory_slot(self, space: MemorySpace, buffer: Any, size_bytes: int) -> LocalMemorySlot:
        self._check_space(space)
        if isinstance(buffer, jax.Array):
            arr = buffer
        else:
            arr = jax.device_put(
                jnp.asarray(np.frombuffer(buffer, dtype=np.uint8)[:size_bytes]),
                _jax_device_for(space),
            )
        return LocalMemorySlot(space, size_bytes, arr, registered=True)

    def free_local_memory_slot(self, slot: LocalMemorySlot) -> None:
        slot.check_alive()
        slot.handle = None
        slot.freed = True


@jax.jit
def _copy_region(dst: jax.Array, src: jax.Array, dst_off, src_off, size):
    chunk = jax.lax.dynamic_slice(src, (src_off,), (size,))
    return jax.lax.dynamic_update_slice(dst, chunk, (dst_off,))


def _dispatch_event(value, *, name: str) -> Event:
    """Transfer/dispatch completion as an Event: poll = XLA buffer readiness,
    untimed wait = block_until_ready (no poll loop on the blocking path)."""
    leaves = jax.tree_util.tree_leaves(value)
    event = Event(name=name)
    event.set_poll(
        lambda: all(getattr(leaf, "is_ready", lambda: True)() for leaf in leaves)
    )
    event.set_waiter(lambda: jax.block_until_ready(value))
    return event


class JaxCommunicationManager(CommunicationManager):
    """L2L device-to-device copies; async (XLA dispatch). The transfer Event
    polls buffer readiness and blocks via block_until_ready; fence() is the
    base-class wait over the tag's event set."""

    backend_name = "jaxdev"

    def _memcpy_impl(self, direction, dst, dst_off, src, src_off, size):
        if direction != MemcpyDirection.LOCAL_TO_LOCAL:
            raise InvalidMemcpyDirectionError(
                "jaxdev communication is intra-instance; use spmd/localsim for global"
            )
        dst.check_alive()
        src.check_alive()
        if dst_off + size > dst.size_bytes or src_off + size > src.size_bytes:
            raise ValueError("memcpy out of slot bounds")
        src_arr = src.handle
        if not isinstance(src_arr, jax.Array):
            src_arr = jnp.asarray(np.asarray(src.handle).view(np.uint8).reshape(-1))
        # Functional update: rebind the destination slot's handle.
        region = jax.lax.dynamic_slice(src_arr, (src.offset + src_off,), (size,))
        dst.handle = jax.lax.dynamic_update_slice(dst.handle, region, (dst.offset + dst_off,))
        return _dispatch_event(dst.handle, name="jaxdev-memcpy")

    def exchange_global_memory_slots(self, tag, local_slots):
        from repro.core.definitions import UnsupportedOperationError

        raise UnsupportedOperationError("jaxdev is intra-instance; use spmd/localsim")


class JaxComputeManager(ComputeManager):
    """Execution units are staged functions; execution states are in-flight
    asynchronous dispatches; processing units are initialized devices."""

    backend_name = "jaxdev"
    supported_formats = ("jax-jit", "python-callable")
    supports_suspension = False

    def create_execution_unit(self, fn, *, name: str = "anonymous", jit: bool = True,
                              static_argnums=(), donate_argnums=(), **metadata) -> ExecutionUnit:
        """`donate_argnums`: arguments whose buffers the unit may reuse for
        its outputs; the caller must not touch a donated argument again."""
        donate_argnums = tuple(donate_argnums) if jit else ()
        staged = (jax.jit(fn, static_argnums=static_argnums, donate_argnums=donate_argnums)
                  if jit else fn)
        return ExecutionUnit(name=name, format="jax-jit", fn=staged,
                             metadata={**metadata, "donate_argnums": donate_argnums})

    def create_processing_unit(self, resource: ComputeResource) -> ProcessingUnit:
        return ProcessingUnit(resource)

    def create_execution_state(self, unit: ExecutionUnit, *args, **kwargs) -> ExecutionState:
        self.check_format(unit)
        return ExecutionState(unit, args, kwargs)

    def initialize(self, pu: ProcessingUnit) -> None:
        jid = int(pu.compute_resource.device_id.rsplit("-", 1)[1])
        pu.context = next(d for d in jax.local_devices() if d.id == jid)
        pu.status = ProcessingUnitStatus.READY

    def execute(self, pu: ProcessingUnit, state: ExecutionState) -> Future:
        pu.check_ready()
        if state.is_finished():
            raise LifetimeError("finished execution states cannot be re-used")
        state.mark_executing()
        pu.current_state = state
        pu.status = ProcessingUnitStatus.EXECUTING
        try:
            with jax.default_device(pu.context):
                # Asynchronous dispatch: returns as soon as XLA enqueues.
                state.continuation = state.execution_unit.fn(*state.args, **state.kwargs)
        except BaseException as e:  # noqa: BLE001
            state.mark_finished(error=e)
            pu.status = ProcessingUnitStatus.READY
            return state.future
        # Completion is discovered, not signalled: poll XLA readiness, and
        # resolve through the blocking path on an untimed wait.
        state.future.set_poll(lambda: self.is_finished(state))
        state.future.set_waiter(lambda: self._resolve(state))
        return state.future

    def is_finished(self, state: ExecutionState) -> bool:
        """Non-blocking completion query (paper §3.1.5)."""
        if state.is_finished():
            return True
        leaves = jax.tree_util.tree_leaves(state.continuation)
        if all(getattr(leaf, "is_ready", lambda: True)() for leaf in leaves):
            state.mark_finished(result=state.continuation)
            return True
        return False

    def _resolve(self, state: ExecutionState) -> None:
        """Blocking completion: force the dispatch, then resolve the state."""
        if state.is_finished():
            return
        try:
            jax.block_until_ready(state.continuation)
            state.mark_finished(result=state.continuation)
        except BaseException as e:  # noqa: BLE001
            state.mark_finished(error=e)

    def finalize(self, pu: ProcessingUnit) -> None:
        pu.status = ProcessingUnitStatus.TERMINATED
        pu.current_state = None
