"""End-to-end training driver.

The launcher path is pure HiCR (DESIGN.md §3): a data-parallel mesh spans
the visible devices (launch/mesh.py); the train step is an ExecutionUnit
dispatched through the SPMD compute manager; checkpoints commit atomically
and training resumes from the latest one.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1 --ckpt-every 50
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.backends import spmd
from repro.configs import ShapeConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt_lib
from repro.train.data import DataState, SyntheticTokenStream
from repro.train.train_step import TrainConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--optimizer", default="adamw")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch, kind="train")
    model = build(cfg)
    ocfg = opt_lib.OptimizerConfig(
        name=args.optimizer, learning_rate=args.lr, warmup_steps=20,
        decay_steps=max(args.steps, 100),
    )
    tcfg = TrainConfig(microbatches=args.microbatches)

    # ---- HiCR launcher: topology -> mesh -> SPMD compute manager ----------
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    cpm = spmd.SpmdComputeManager(mesh)
    pu = cpm.create_processing_unit(cpm.mesh_compute_resource())
    cpm.initialize(pu)

    params, axes, opt_state, ef = init_train_state(model, ocfg, jax.random.PRNGKey(0), train_cfg=tcfg)
    stream = SyntheticTokenStream(cfg, shape)
    start_step = 0

    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree = {"params": params, "opt": opt_state}
        restored, extra = ckpt.restore(args.ckpt_dir, tree)
        params, opt_state = restored["params"], restored["opt"]
        params = jax.tree_util.tree_map(jax.numpy.asarray, params)
        opt_state = jax.tree_util.tree_map(jax.numpy.asarray, opt_state)
        stream.state = DataState.from_dict(extra["data"])
        start_step = int(extra["step"])
        print(f"resumed from step {start_step}")

    unit = cpm.create_execution_unit(
        make_train_step(model, ocfg, tcfg), name=f"train_step[{args.arch}]",
        donate_argnums=(0, 1),
    )

    t0 = time.time()
    tokens_per_step = args.batch * args.seq
    for step in range(start_step, args.steps):
        batch = stream.next_batch()
        state = cpm.create_execution_state(unit, params, opt_state, ef, batch)
        cpm.execute(pu, state)
        cpm.await_(pu)
        params, opt_state, ef, metrics = state.get_result()
        if (step + 1) % args.log_every == 0:
            dt = time.time() - t0
            tps = tokens_per_step * args.log_every / max(dt, 1e-9)
            print(
                f"step {step+1:5d} loss={float(metrics['loss']):.4f} "
                f"grad_norm={float(metrics['grad_norm']):.3f} "
                f"lr={float(metrics['lr']):.2e} tok/s={tps:,.0f}"
            )
            t0 = time.time()
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(
                args.ckpt_dir, step + 1,
                {"params": params, "opt": opt_state},
                extra={"data": stream.state.to_dict(), "step": step + 1},
            )
    cpm.finalize(pu)
    print("training complete")
    return params


if __name__ == "__main__":
    main()
