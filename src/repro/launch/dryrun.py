import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks platform and device count on
# first init). The dry-run plans on placeholder CPU devices, so it never
# contends for an accelerator held by another process.
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, proving the distribution config is coherent without
real hardware.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --json experiments/dryrun

Per cell this prints compiled.memory_analysis() (fits / doesn't fit) and
compiled.cost_analysis() (FLOPs & bytes for §Roofline), and extracts the
collective schedule from the optimized HLO.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, LONG_CONTEXT_ARCHS, SHAPES, get_config, get_shape
from repro.launch import roofline as rl
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build
from repro.sharding import partition
from repro.train import optimizer as opt_lib
from repro.train.train_step import make_train_step


def jnp_f32():
    import jax.numpy as jnp

    return jnp.float32


def scan_trip_count(cfg) -> int:
    """Dominant scan length, for while-body collective amplification."""
    if cfg.family == "hybrid":
        return max(1, cfg.num_layers // max(cfg.shared_attn_interval, 1))
    if cfg.sliding_window and cfg.global_interval:
        return max(1, cfg.num_layers // cfg.global_interval)
    return max(1, cfg.num_layers)


def param_counts(param_specs, axes_tree):
    total, expert = 0, 0
    for (path, leaf), (_, axes) in zip(
        jax.tree_util.tree_flatten_with_path(param_specs)[0],
        jax.tree_util.tree_flatten_with_path(axes_tree)[0],
    ):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if "expert" in axes:
            expert += n
    return total, expert


def calib_plan(cfg):
    """Depth-calibration plan: (layers_for_ka, layers_for_kb, ka_units,
    kb_units, full_units, tail_units). XLA cost_analysis counts while bodies
    once, so roofline totals are measured from two UNROLLED reduced-depth
    compiles and extrapolated linearly in depth (exact for the homogeneous
    stack; the sliding-window unit / hybrid group is the extrapolation unit).
    """
    if cfg.family == "ssm":
        return None  # python-loop blocks: cost_analysis already exact
    if cfg.family == "hybrid":
        g = cfg.shared_attn_interval
        full = cfg.num_layers // g
        tail = (cfg.num_layers % g) / g
        return (g, 2 * g, 1, 2, full, tail)
    if cfg.sliding_window and cfg.global_interval:
        g = cfg.global_interval
        full = cfg.num_layers // g
        tail = (cfg.num_layers % g) / g
        return (g, 2 * g, 1, 2, full, tail)
    return (1, 2, 1, 2, cfg.num_layers, 0.0)


def _cost_triple(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = rl.collective_bytes(compiled.as_text(), default_trip_count=1)
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(coll["total"]),
        {k: v for k, v in coll.items() if k != "total" and v},
    )


def calibrated_roofline(arch, shape_id, mesh, *, opt_name=None, microbatches=1,
                        remat=None, cfg_overrides=None):
    """Depth-extrapolated roofline terms from two unrolled reduced-depth
    compiles on the SAME mesh (collectives included exactly)."""
    cfg = get_config(arch)
    plan = calib_plan(cfg)
    shape = get_shape(shape_id)
    base_over = dict(cfg_overrides or {})

    def compile_depth(layers):
        over = dict(base_over, num_layers=layers, scan_layers=False)
        if cfg.family == "audio":
            over["encoder_layers"] = layers
        _, info = lower_cell(arch, shape_id, mesh, opt_name=opt_name,
                             microbatches=microbatches, remat=remat,
                             verbose=False, cfg_overrides=over)
        return info

    if plan is None:  # exact already
        _, info = lower_cell(arch, shape_id, mesh, opt_name=opt_name,
                             microbatches=microbatches, remat=remat,
                             verbose=False,
                             cfg_overrides=dict(base_over, scan_layers=False))
        r = info["roofline"]
        return {
            "flops_per_device": r["flops_per_device"],
            "bytes_per_device": r["bytes_per_device"],
            "collective_bytes_per_device": r["collective_bytes_per_device"],
            "method": "exact-unrolled",
        }

    la, lb, ka, kb, full_units, tail_units = plan
    ia = compile_depth(la)
    ib = compile_depth(lb)

    def extrap(key):
        a = ia["roofline"][key]
        b = ib["roofline"][key]
        per_unit = (b - a) / (kb - ka)
        return a + (full_units - ka + tail_units) * per_unit

    return {
        "flops_per_device": extrap("flops_per_device"),
        "bytes_per_device": extrap("bytes_per_device"),
        "collective_bytes_per_device": extrap("collective_bytes_per_device"),
        "method": f"unroll-calibrated({la},{lb})",
        "calib_points": [ia["roofline"], ib["roofline"]],
    }


def lower_cell(arch: str, shape_id: str, mesh, *, opt_name=None, microbatches=1,
               remat=None, verbose=True, cfg_overrides=None, grad_compression=None):
    """Lower + compile one cell. Returns (compiled, info dict)."""
    shape = get_shape(shape_id)
    cfg = get_config(arch)
    if remat:
        cfg = cfg.replace(remat_policy=remat)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    model = build(cfg)
    plan = partition.default_plan(cfg)

    from repro.sharding.ambient import active_mesh

    t0 = time.time()
    with mesh, active_mesh(mesh):
        axes_box = {}

        def _init_params_only():
            p, axes = model.init(jax.random.PRNGKey(0))
            axes_box["axes"] = axes  # plain-Python tree, captured not traced
            return p

        param_specs = jax.eval_shape(_init_params_only)
        axes_tree = axes_box["axes"]
        n_total, n_expert = param_counts(param_specs, axes_tree)
        n_active = None
        if cfg.is_moe:
            n_active = n_total - n_expert + n_expert * cfg.experts_per_token // cfg.num_experts
        p_shards = partition.param_shardings(axes_tree, param_specs, mesh, plan)
        params_in = partition.with_shardings(param_specs, p_shards)
        batch_specs = model.input_specs(shape)
        b_shards = partition.input_shardings(batch_specs, mesh, cfg, shape)
        batch_in = partition.with_shardings(batch_specs, b_shards)

        if shape.kind == "train":
            if opt_name is None:
                opt_name = "adafactor" if n_total * 2 > 50e9 else "adamw"
            ocfg = opt_lib.OptimizerConfig(name=opt_name)
            from repro.train.train_step import TrainConfig

            tc = TrainConfig(microbatches=microbatches, grad_compression=grad_compression)
            step = make_train_step(model, ocfg, tc, mesh=mesh)
            opt_specs = jax.eval_shape(lambda p: opt_lib.init(ocfg, p), param_specs)
            o_shards = partition.opt_state_shardings(opt_specs, param_specs, p_shards, mesh)
            opt_in = partition.with_shardings(opt_specs, o_shards)
            if grad_compression == "int8_ef":
                ef_specs = jax.tree_util.tree_map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, jnp_f32()), param_specs)
                ef_in = partition.with_shardings(ef_specs, p_shards)
                lowered = jax.jit(step, donate_argnums=(0, 1, 2)).lower(params_in, opt_in, ef_in, batch_in)
            else:
                lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params_in, opt_in, None, batch_in)
        elif shape.kind == "prefill":
            lowered = jax.jit(model.prefill).lower(params_in, batch_in)
        else:  # decode
            state_specs = model.state_specs(shape)
            s_shards = partition.state_shardings(state_specs, mesh, cfg, shape)
            state_in = partition.with_shardings(state_specs, s_shards)
            step = lambda p, s, b: model.decode_step(p, s, b)
            lowered = jax.jit(step, donate_argnums=(1,)).lower(params_in, state_in, batch_in)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    chips = mesh.devices.size
    roof = rl.analyze(compiled, chips=chips, default_trip_count=scan_trip_count(cfg))
    mf = rl.model_flops(cfg, shape, n_params=n_total, n_active_params=n_active)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
    except Exception as e:  # noqa: BLE001
        mem["error"] = repr(e)

    info = {
        "arch": arch,
        "shape": shape_id,
        "mesh": dict(mesh.shape),
        "chips": chips,
        "n_params": n_total,
        "n_active_params": n_active,
        "optimizer": opt_name if shape.kind == "train" else None,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": roof.to_dict(),
        "model_flops_global": mf,
        "collectives": rl.collective_bytes(
            compiled.as_text(), default_trip_count=scan_trip_count(cfg)
        ),
    }
    if verbose:
        arg_gb = mem.get("argument_size_in_bytes", 0) / 2**30
        tmp_gb = mem.get("temp_size_in_bytes", 0) / 2**30
        print(
            f"[{arch} × {shape_id} × {chips}chips] OK "
            f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
            f"args/dev={arg_gb:.2f}GiB temp/dev={tmp_gb:.2f}GiB "
            f"flops/dev={roof.flops_per_device:.3e} "
            f"dominant={roof.dominant} bound={roof.bound_s*1e3:.2f}ms"
        )
    return compiled, info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 or 2x2x2 (pod,data,model)")
    ap.add_argument("--json", default=None, help="directory for per-cell json records")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--calibrate", action="store_true",
                    help="add unroll-calibrated roofline totals (2 extra compiles/cell)")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf optimization stack (blocked attention, "
                         "sequential SSD, remat=full + 4 sharded microbatches on "
                         "train shapes) instead of the paper-faithful baseline")
    args = ap.parse_args()

    def build_mesh(multi_pod: bool):
        if args.mesh:
            dims = tuple(int(x) for x in args.mesh.split("x"))
            axes = ("pod", "data", "model")[-len(dims):]
            return make_mesh(dims, axes)
        return make_production_mesh(multi_pod=multi_pod)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape_id in SHAPES:
                if shape_id == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                    continue
                cells.append((arch, shape_id))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    failures = []
    for multi_pod in meshes:
        mesh = build_mesh(multi_pod)
        print(f"=== mesh {dict(mesh.shape)} ({mesh.devices.size} chips) ===")
        for arch, shape_id in cells:
            microbatches, remat, overrides = args.microbatches, args.remat, None
            if args.optimized:
                overrides = {"attention_impl": "blocked", "ssd_impl": "sequential"}
                if get_shape(shape_id).kind == "train":
                    remat = remat or "full"
                    microbatches = max(microbatches, 4)
            try:
                compiled, info = lower_cell(
                    arch, shape_id, mesh,
                    opt_name=args.optimizer,
                    microbatches=microbatches,
                    remat=remat,
                    cfg_overrides=overrides,
                )
                if args.calibrate:
                    cal = calibrated_roofline(
                        arch, shape_id, mesh,
                        opt_name=args.optimizer,
                        microbatches=microbatches,
                        remat=remat,
                        cfg_overrides=overrides,
                    )
                    info["roofline_calibrated"] = cal
                    from repro.backends.tpu_spec import V5E

                    roof = rl.Roofline(
                        flops_per_device=cal["flops_per_device"],
                        bytes_per_device=cal["bytes_per_device"],
                        collective_bytes_per_device=cal["collective_bytes_per_device"],
                        chips=mesh.devices.size, chip=V5E,
                    )
                    info["roofline_calibrated"].update(roof.to_dict())
                    print(
                        f"    calibrated: compute={roof.compute_s*1e3:.2f}ms "
                        f"memory={roof.memory_s*1e3:.2f}ms "
                        f"collective={roof.collective_s*1e3:.2f}ms "
                        f"dominant={roof.dominant}"
                    )
                if args.json:
                    os.makedirs(args.json, exist_ok=True)
                    tag = f"{arch}__{shape_id}__{'x'.join(map(str, mesh.devices.shape))}"
                    with open(os.path.join(args.json, tag + ".json"), "w") as f:
                        json.dump(info, f, indent=1)
                del compiled
            except Exception:  # noqa: BLE001
                failures.append((arch, shape_id, dict(mesh.shape)))
                print(f"[{arch} × {shape_id}] FAILED")
                traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run: all cells passed")


if __name__ == "__main__":
    main()
