#!/usr/bin/env python3
"""Compile a cell's programs at real widths for a described TPU v5e, without
a chip, and print what each needs in device memory and how long it took.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/aot_check.py \\
        granite-20b.chat [--tails 1536,64]

It builds the cell's scheduler on the host at full size (the KV pool is
allocated in host memory), then lowers the scheduler's own execution units
(the tail prefill at the given tail lengths, default the longest and the
shortest the mix makes; the page commit; the fused decode interval) with
shapes placed on one chip of a described ``v5e:2x2`` topology, with the
platform's kernel choice steered to the compiled Pallas kernels.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def steer_to_tpu_kernels() -> None:
    import repro.kernels.ops as ops
    import repro.kernels.paged_decode_attention as pda

    ops.decode_impl = lambda: "pallas"
    pda.interpret_mode = lambda: False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--tails", default=None, help="comma-separated tail lengths")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    import harness
    import traffic_gen
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.runtime import Runtime

    steer_to_tpu_kernels()
    cell = harness.load_cell(args.cell)
    prog = cell.program
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    shapes = jax.eval_shape(lambda: cell.reference.make_weights(prog, 0))
    pspec = jax.tree_util.tree_map(spec, shapes)
    wbytes = sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(pspec))
    with Runtime("jaxdev") as rt:
        sched = harness.build_scheduler(cell, None, rt)
        dec = sched.decoder
        pools = jax.tree_util.tree_map(spec, dec.kv.pools)
        pool_bytes = sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(pools))
        row = jax.ShapeDtypeStruct((dec.layout.n_pages_seq,), jnp.int32, sharding=chip)
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        print(f"{args.cell}: weights {wbytes / 1e9:.3f} GB, pool {pool_bytes / 1e9:.3f} GB "
              f"({dec.layout.num_pages} pages of {dec.layout.page_size})")
        tails = ([int(t) for t in args.tails.split(",")] if args.tails
                 else sorted({max(traffic_gen.tail_lengths(cell.traffic)),
                              min(traffic_gen.tail_lengths(cell.traffic))}, reverse=True))
        units = []
        for t in tails:
            tok = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=chip)
            units.append((f"prefix_prefill[{t}]", dec._prefix_unit.fn, (pspec, pools, row, tok, i32)))
        state = jax.eval_shape(dec._prefix_unit.fn, pspec, pools, row,
                               jax.ShapeDtypeStruct((1, tails[-1]), jnp.int32), i32)[1]
        state = jax.tree_util.tree_map(spec, state)
        ctl = jax.ShapeDtypeStruct(dec.ctl.shape, jnp.int32, sharding=chip)
        ring = jax.ShapeDtypeStruct(dec._ring_rows[0].shape, jnp.int32, sharding=chip)
        arm = jax.ShapeDtypeStruct((6,), jnp.int32, sharding=chip)
        units.append(("commit_and_arm", dec._commit_unit.fn, (pools, state, row, ring, ctl, arm)))
        table = jax.ShapeDtypeStruct(sched._page_table.shape, jnp.int32, sharding=chip)
        units.append((dec._fused_unit.name, dec._fused_unit.fn, (pspec, pools, table, ctl)))
        for name, fn, specs in units:
            t0 = time.perf_counter()
            compiled = fn.lower(*specs).compile()
            dt = time.perf_counter() - t0
            ma = compiled.memory_analysis()
            kernel = "tpu_custom_call" in compiled.as_text()
            print(f"  {name}: compile {dt:.1f}s, args {ma.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, temp "
                  f"{ma.temp_size_in_bytes / 1e9:.3f} GB, aliased "
                  f"{ma.alias_size_in_bytes / 1e9:.3f} GB, pallas kernel {kernel}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
