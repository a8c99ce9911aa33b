"""Production mesh construction.

`make_production_mesh` builds the assigned target meshes; `mesh_from_topology`
builds a mesh from a discovered/declared HiCR Topology — the launcher path:
TopologyManagers discover, the mesh builder consumes the model's stateless
Topology component, never raw `jax.devices()` (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.core.stateless import Topology


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Every mesh of the repo is built here, with Auto axes: the sharding
    rules are written for propagation, not for explicit-sharding types."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_from_topology(
    topology: Topology,
    *,
    model_parallelism: int = 16,
    pods: Optional[int] = None,
):
    """Build a (pod?, data, model) mesh sized by a HiCR topology's TPU
    devices. Raises if the device count does not factor."""
    chips = [d for d in topology.get_devices() if d.kind == "tpu"]
    n = len(chips)
    if n == 0:
        raise ValueError("topology contains no TPU devices")
    pod_ids = sorted({d.attributes.get("pod", 0) for d in chips})
    n_pods = pods if pods is not None else len(pod_ids)
    per_pod = n // n_pods
    if per_pod % model_parallelism != 0:
        raise ValueError(f"{per_pod} chips/pod not divisible by model={model_parallelism}")
    data = per_pod // model_parallelism
    if n_pods > 1:
        return make_mesh((n_pods, data, model_parallelism), ("pod", "data", "model"))
    return make_mesh((data, model_parallelism), ("data", "model"))
