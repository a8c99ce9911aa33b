"""Backend conformance: hostcpu (HWLoc+Pthreads analog), coroutine (Boost
analog), jaxdev (ACL/OpenCL analog), tpu_spec (target spec sheet)."""
import threading
import time

import numpy as np
import pytest

from repro.backends import coroutine, hostcpu, jaxdev, tpu_spec
from repro.core.definitions import (
    InvalidMemcpyDirectionError,
    LifetimeError,
    MemorySpaceMismatchError,
    UnsupportedOperationError,
)
from repro.core.managers import ManagerSet
from repro.core.stateless import MemorySpace


# ---------------------------------------------------------------------------
# hostcpu
# ---------------------------------------------------------------------------


class TestHostTopology:
    def test_discovers_cores_and_memory(self):
        topo = hostcpu.HostTopologyManager().query_topology()
        assert len(topo.all_compute_resources()) >= 1
        assert topo.total_memory_bytes() > 0

    def test_numa_split(self):
        topo = hostcpu.HostTopologyManager(numa_domains=2).query_topology()
        assert len(topo.get_devices()) == 2
        # NUMA domains split memory; paper: "2 x 64GB" style reporting
        sizes = [m.size_bytes for m in topo.all_memory_spaces()]
        assert len(sizes) == 2 and abs(sizes[0] - sizes[1]) <= 1


class TestHostMemory:
    def setup_method(self):
        self.mm = hostcpu.HostMemoryManager()
        self.space = self.mm.memory_spaces()[0]

    def test_alloc_free(self):
        slot = self.mm.allocate_local_memory_slot(self.space, 128)
        assert slot.size_bytes == 128 and not slot.registered
        self.mm.free_local_memory_slot(slot)
        with pytest.raises(LifetimeError):
            slot.check_alive()

    def test_register_external_allocation(self):
        """Paper §3.1.3: registering an allocation received externally."""
        ext = np.arange(32, dtype=np.uint8)
        slot = self.mm.register_local_memory_slot(self.space, ext, 32)
        assert slot.registered
        assert bytes(slot.handle[:4]) == bytes([0, 1, 2, 3])

    def test_unknown_space_rejected(self):
        bogus = MemorySpace(kind="device_hbm", index=9, device_id="nope", size_bytes=4)
        with pytest.raises(MemorySpaceMismatchError):
            self.mm.allocate_local_memory_slot(bogus, 4)

    def test_zero_alloc_rejected(self):
        with pytest.raises(ValueError):
            self.mm.allocate_local_memory_slot(self.space, 0)


class TestHostCommunication:
    def test_async_memcpy_with_fence(self):
        mgrs = hostcpu.make_managers()
        mm, cm = mgrs["memory"], mgrs["communication"]
        space = mm.memory_spaces()[0]
        src = mm.allocate_local_memory_slot(space, 64)
        dst = mm.allocate_local_memory_slot(space, 64)
        src.handle[:] = np.arange(64, dtype=np.uint8)
        cm.memcpy(dst, 0, src, 0, 64)
        cm.fence()  # completion only guaranteed after the fence
        assert bytes(dst.handle) == bytes(src.handle)
        cm.shutdown()

    def test_offset_copy(self):
        mgrs = hostcpu.make_managers()
        mm, cm = mgrs["memory"], mgrs["communication"]
        space = mm.memory_spaces()[0]
        src = mm.allocate_local_memory_slot(space, 16)
        dst = mm.allocate_local_memory_slot(space, 16)
        src.handle[:] = np.arange(16, dtype=np.uint8)
        cm.memcpy(dst, 8, src, 4, 4)
        cm.fence()
        assert bytes(dst.handle[8:12]) == bytes([4, 5, 6, 7])
        cm.shutdown()

    def test_out_of_bounds_rejected(self):
        mgrs = hostcpu.make_managers()
        mm, cm = mgrs["memory"], mgrs["communication"]
        space = mm.memory_spaces()[0]
        a = mm.allocate_local_memory_slot(space, 8)
        b = mm.allocate_local_memory_slot(space, 8)
        with pytest.raises(ValueError):
            cm.memcpy(b, 4, a, 0, 8)
        cm.shutdown()

    def test_single_instance_no_global_slots(self):
        cm = hostcpu.HostCommunicationManager()
        with pytest.raises(UnsupportedOperationError):
            cm.exchange_global_memory_slots(0, {})
        cm.shutdown()


class TestHostCompute:
    def test_parallel_execution_pattern(self):
        """The paper's Fig. 6: run an execution unit on every compute
        resource, await, finalize."""
        cpm = hostcpu.HostComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        resources = topo.all_compute_resources()[:4]
        unit = cpm.create_execution_unit(lambda i: i * i, name="sq")
        pus, states = [], []
        for i, r in enumerate(resources):
            pu = cpm.create_processing_unit(r)
            st = cpm.create_execution_state(unit, i)
            cpm.initialize(pu)
            cpm.execute(pu, st)
            pus.append(pu)
            states.append(st)
        for pu in pus:
            cpm.await_(pu)
        for pu in pus:
            cpm.finalize(pu)
        assert [s.get_result() for s in states] == [i * i for i in range(len(resources))]

    def test_execution_is_async(self):
        cpm = hostcpu.HostComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        pu = cpm.create_processing_unit(topo.all_compute_resources()[0])
        cpm.initialize(pu)
        gate = threading.Event()
        unit = cpm.create_execution_unit(lambda: (gate.wait(5), "done")[1])
        st = cpm.create_execution_state(unit)
        cpm.execute(pu, st)
        assert not st.is_finished()  # still blocked on the gate
        gate.set()
        cpm.await_(pu)
        assert st.get_result() == "done"
        cpm.finalize(pu)

    def test_error_propagates_through_state(self):
        cpm = hostcpu.HostComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        pu = cpm.create_processing_unit(topo.all_compute_resources()[0])
        cpm.initialize(pu)

        def boom():
            raise RuntimeError("kernel failure")

        st = cpm.create_execution_state(cpm.create_execution_unit(boom))
        cpm.execute(pu, st)
        cpm.await_(pu)
        with pytest.raises(RuntimeError, match="kernel failure"):
            st.get_result()
        cpm.finalize(pu)

    def test_no_suspension(self):
        cpm = hostcpu.HostComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        pu = cpm.create_processing_unit(topo.all_compute_resources()[0])
        with pytest.raises(UnsupportedOperationError):
            cpm.suspend(pu)

    def test_finished_state_not_reusable(self):
        cpm = hostcpu.HostComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        pu = cpm.create_processing_unit(topo.all_compute_resources()[0])
        cpm.initialize(pu)
        st = cpm.create_execution_state(cpm.create_execution_unit(lambda: 1))
        cpm.execute(pu, st)
        cpm.await_(pu)
        with pytest.raises(LifetimeError):
            cpm.execute(pu, st)
        cpm.finalize(pu)


# ---------------------------------------------------------------------------
# coroutine (Boost.Context analog): suspendable execution states
# ---------------------------------------------------------------------------


class TestCoroutine:
    def setup_method(self):
        self.cpm = coroutine.CoroutineComputeManager()
        topo = hostcpu.HostTopologyManager().query_topology()
        self.pu = self.cpm.create_processing_unit(topo.all_compute_resources()[0])
        self.cpm.initialize(self.pu)

    def test_suspend_resume_at_yield_points(self):
        """Coroutines suspend and resume at arbitrary points without OS
        scheduler intervention (paper §4.2, Boost backend)."""
        trace = []

        def gen():
            trace.append("a")
            yield
            trace.append("b")
            yield
            trace.append("c")
            return 99

        st = self.cpm.create_execution_state(self.cpm.create_execution_unit(gen), )
        assert not self.cpm.execute_step(self.pu, st)  # ran to first yield
        assert trace == ["a"]
        assert not self.cpm.execute_step(self.pu, st)
        assert trace == ["a", "b"]
        assert self.cpm.execute_step(self.pu, st)  # finished
        assert trace == ["a", "b", "c"]
        assert st.get_result() == 99

    def test_plain_callable_runs_to_completion(self):
        st = self.cpm.create_execution_state(self.cpm.create_execution_unit(lambda: 7))
        self.cpm.execute(self.pu, st)
        self.cpm.await_(self.pu)
        assert st.get_result() == 7

    def test_supports_suspension_flag(self):
        assert self.cpm.supports_suspension


# ---------------------------------------------------------------------------
# jaxdev (ACL / OpenCL analog)
# ---------------------------------------------------------------------------


class TestJaxDev:
    def test_topology_exposes_devices(self):
        topo = jaxdev.JaxTopologyManager().query_topology()
        assert len(topo.get_devices()) >= 1
        assert len(topo.all_memory_spaces()) >= 1

    def test_accelerator_without_bytes_limit_is_an_error(self):
        """An accelerator's memory size comes from the device or nowhere:
        no assumed default hides a device that reports none."""

        class Silent:
            platform, id, device_kind = "tpu", 0, "TPU v5 lite"

            def memory_stats(self):
                return {}

        with pytest.raises(RuntimeError, match="bytes_limit"):
            jaxdev._memory_bytes(Silent())

    def test_memory_alloc(self):
        mm = jaxdev.JaxMemoryManager()
        space = mm.memory_spaces()[0]
        slot = mm.allocate_local_memory_slot(space, 256)
        assert slot.size_bytes == 256
        mm.free_local_memory_slot(slot)

    def test_jitted_execution_unit(self):
        import jax.numpy as jnp

        cpm = jaxdev.JaxComputeManager()
        topo = jaxdev.JaxTopologyManager().query_topology()
        pu = cpm.create_processing_unit(topo.all_compute_resources()[0])
        cpm.initialize(pu)
        unit = cpm.create_execution_unit(lambda x: (x * x).sum(), name="sq", jit=True)
        st = cpm.create_execution_state(unit, jnp.arange(8.0))
        cpm.execute(pu, st)
        cpm.await_(pu)
        assert float(st.get_result()) == pytest.approx(140.0)
        cpm.finalize(pu)

    def test_memcpy_l2l_device_buffers(self):
        mm = jaxdev.JaxMemoryManager()
        cm = jaxdev.JaxCommunicationManager()
        space = mm.memory_spaces()[0]
        src = mm.allocate_local_memory_slot(space, 32)
        dst = mm.allocate_local_memory_slot(space, 32)
        src.handle = src.handle.at[:].set(np.arange(32, dtype=np.uint8))
        cm.memcpy(dst, 0, src, 0, 32)
        cm.fence()
        assert np.asarray(dst.handle).tolist() == list(range(32))


# ---------------------------------------------------------------------------
# tpu_spec: the declarative target topology used for dry-run planning
# ---------------------------------------------------------------------------


class TestTpuSpec:
    def test_single_pod_topology(self):
        topo = tpu_spec.SpecTopologyManager().query_topology()
        chips = [d for d in topo.get_devices() if d.kind == "tpu"]
        assert len(chips) == 256
        hbm = topo.total_memory_bytes("device_hbm")
        assert hbm == 256 * (16 << 30)

    def test_multi_pod_topology(self):
        topo = tpu_spec.SpecTopologyManager(pods=2).query_topology()
        chips = [d for d in topo.get_devices() if d.kind == "tpu"]
        assert len(chips) == 512
        pods = {d.attributes.get("pod") for d in chips}
        assert pods == {0, 1}

    def test_chip_constants_match_assignment(self):
        """197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI."""
        spec = tpu_spec.V5E
        assert spec.peak_flops_bf16 == pytest.approx(1.97e14)
        assert spec.hbm_bandwidth == pytest.approx(8.19e11)
        assert spec.ici_bandwidth_per_link == pytest.approx(5.0e10)

    def test_spec_topology_serializes(self):
        """Declarative topologies broadcast like discovered ones."""
        from repro.core.stateless import Topology

        topo = tpu_spec.SpecTopologyManager().query_topology()
        again = Topology.deserialize(topo.serialize())
        assert len(again.get_devices()) == len(topo.get_devices())


# ---------------------------------------------------------------------------
# manager-set convenience (paper Fig. 4 pattern)
# ---------------------------------------------------------------------------


def test_manager_set_merges_topologies():
    ms = ManagerSet(
        topology_managers=(
            hostcpu.HostTopologyManager(),
            tpu_spec.SpecTopologyManager(pod_shape=(2, 2)),
        )
    )
    topo = ms.query_full_topology()
    kinds = {d.kind for d in topo.get_devices()}
    assert "cpu" in kinds and "tpu" in kinds
