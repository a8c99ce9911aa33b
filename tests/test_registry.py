"""Backend registry (`core/registry.py`) and the `Runtime` facade
(`core/runtime.py`): build-by-name, the capability table, error paths, and
backend-agnostic execution across hostcpu and jaxdev."""
import gc
import weakref

import jax.numpy as jnp
import pytest

from repro.core import registry
from repro.core.managers import (
    CommunicationManager,
    ComputeManager,
    ManagerSet,
    MemoryManager,
    TopologyManager,
)
from repro.core.runtime import Runtime, RuntimeAssemblyError


class TestRegistry:
    def test_builtin_backends_available(self):
        names = registry.available_backends()
        for expected in ("hostcpu", "jaxdev", "localsim", "coroutine", "spmd", "tpu_spec"):
            assert expected in names

    def test_build_instantiates_manager_roles(self):
        assert isinstance(registry.build("hostcpu", "compute"), ComputeManager)
        assert isinstance(registry.build("hostcpu", "memory"), MemoryManager)
        assert isinstance(registry.build("hostcpu", "topology"), TopologyManager)
        assert isinstance(registry.build("hostcpu", "communication"), CommunicationManager)

    def test_build_returns_fresh_instances(self):
        assert registry.build("hostcpu", "compute") is not registry.build("hostcpu", "compute")

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError, match="unknown backend"):
            registry.build("no-such-backend", "compute")

    def test_unimplemented_role_raises(self):
        # coroutine is compute-only (paper Table 1)
        with pytest.raises(KeyError, match="does not implement role"):
            registry.build("coroutine", "instance")

    def test_register_rejects_invalid_role(self):
        with pytest.raises(ValueError, match="unknown manager role"):
            registry.register_backend("bogus", {"turbo": object})

    def test_capability_table_shape(self):
        table = registry.capability_table()
        assert set(table["hostcpu"]) == set(registry.ROLES)
        assert table["hostcpu"]["compute"] is True
        assert table["hostcpu"]["instance"] is True  # single-instance view
        assert table["localsim"]["instance"] is True
        assert table["localsim"]["compute"] is False
        assert table["tpu_spec"]["topology"] is True


class TestRuntime:
    @pytest.mark.parametrize("backend", ["hostcpu", "jaxdev"])
    def test_executes_units_backend_agnostically(self, backend):
        """The same application code runs unchanged on either backend —
        the paper's switch-technologies-without-source-changes claim."""
        rt = Runtime(backend)
        unit = rt.create_execution_unit(lambda a, b: a * b + 1, name="mad")
        assert int(rt.run(unit, 6, 7)) == 43
        rt.finalize()

    @pytest.mark.parametrize("backend", ["hostcpu", "jaxdev"])
    def test_assembles_manager_set_from_registry(self, backend):
        rt = Runtime(backend)
        assert isinstance(rt.managers, ManagerSet)
        assert isinstance(rt.compute_manager, ComputeManager)
        assert isinstance(rt.memory_manager, MemoryManager)
        assert rt.compute_manager.backend_name == backend
        assert rt.query_topology().all_compute_resources()

    def test_processing_unit_is_cached(self):
        rt = Runtime("hostcpu")
        assert rt.processing_unit is rt.processing_unit
        rt.finalize()

    @pytest.mark.parametrize("backend", ["hostcpu", "jaxdev"])
    def test_resource_indexes_the_topology(self, backend):
        with Runtime(backend) as rt:
            resources = rt.query_topology().all_compute_resources()
        for i in (0, 1, len(resources) + 1):
            with Runtime(backend, resource=i) as rt:
                assert rt.processing_unit.compute_resource == resources[i % len(resources)]

    @pytest.mark.parametrize("backend", ["hostcpu", "jaxdev"])
    def test_settled_runs_release_their_arguments(self, backend):
        """Only the latest execution may keep its arguments alive, and no
        reference cycle may keep the others: a serve loop's arguments are
        its weights and each interval's copy of the KV pool."""
        gc.disable()  # refcounting alone must free them
        try:
            with Runtime(backend) as rt:
                unit = rt.create_execution_unit(lambda x: x * 2, name="double")
                refs = []
                for i in range(4):
                    x = jnp.full((8,), float(i))
                    refs.append(weakref.ref(x))
                    rt.run(unit, x)
                    del x
                assert [r() is None for r in refs] == [True, True, True, False]
        finally:
            gc.enable()

    def test_role_overrides_mix_backends(self):
        # coroutine has no topology role; borrow hostcpu's (Table 1 mixing)
        rt = Runtime("coroutine", overrides={"topology": "hostcpu"})
        assert rt.compute_manager.backend_name == "coroutine"
        assert rt.query_topology().all_compute_resources()

    def test_missing_topology_role_raises(self):
        rt = Runtime("coroutine")
        with pytest.raises(RuntimeAssemblyError, match="no topology role"):
            rt.query_topology()

    def test_missing_compute_role_raises(self):
        rt = Runtime("tpu_spec")
        with pytest.raises(RuntimeAssemblyError, match="no compute role"):
            rt.compute_manager

    def test_context_requiring_backend_raises_helpfully(self):
        # localsim factories need a world handle at launch time
        with pytest.raises(RuntimeAssemblyError, match="launch-time context"):
            Runtime("localsim")


class TestRuntimeInstanceLifecycle:
    """Runtime facade over the instance role (paper §3.1.1): the same
    template → create → terminate surface the fleet router uses, reachable
    without importing a concrete backend."""

    def test_instances_and_liveness_on_hostcpu(self):
        rt = Runtime("hostcpu")
        instances = rt.instances()
        assert len(instances) == 1 and instances[0].is_root()
        assert list(rt.live_instances()) == list(instances)

    def test_create_instances_requirements_shorthand(self):
        from repro.core.definitions import UnsupportedOperationError

        rt = Runtime("hostcpu")
        # satisfiable requirements reach the capability error (stub path)
        with pytest.raises(UnsupportedOperationError, match="template validated"):
            rt.create_instances(1, min_compute_resources=1)

    def test_create_instances_validates_template_first(self):
        from repro.core.definitions import HiCRError, UnsupportedOperationError

        rt = Runtime("hostcpu")
        with pytest.raises(HiCRError) as exc:
            rt.create_instances(1, min_memory_bytes=1 << 62)
        assert not isinstance(exc.value, UnsupportedOperationError)

    def test_terminate_unsupported_on_hostcpu(self):
        from repro.core.definitions import UnsupportedOperationError

        rt = Runtime("hostcpu")
        with pytest.raises(UnsupportedOperationError):
            rt.terminate_instance(rt.instances()[0])

    def test_backend_without_instance_role_raises_assembly_error(self):
        rt = Runtime("jaxdev")
        with pytest.raises(RuntimeAssemblyError, match="no instance role"):
            rt.instances()
