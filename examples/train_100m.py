"""End-to-end training driver: a ~100M-parameter dense LM trained on CPU
with the full substrate — HiCR launcher, SPMD compute manager, prefetching
data pipeline, atomic checkpoints, resume.

    # quick demo (a few minutes on CPU):
    PYTHONPATH=src python examples/train_100m.py --steps 30

    # the full few-hundred-step run:
    PYTHONPATH=src python examples/train_100m.py --steps 300

Interrupt it at any point and re-run: it resumes from the latest committed
checkpoint, reproducing the uninterrupted trajectory exactly (tested in
tests/test_train.py::TestCheckpoint::test_resume_reproduces_trajectory).
"""
import argparse
import time

import jax
import numpy as np

from repro.backends import spmd
from repro.configs import ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.models.model_zoo import param_count
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt_lib
from repro.train.data import DataState, PrefetchingLoader, SyntheticTokenStream
from repro.train.train_step import TrainConfig, init_train_state, make_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=30)
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--ckpt-dir", default="/tmp/train_100m_ckpt")
ap.add_argument("--ckpt-every", type=int, default=25)
args = ap.parse_args()

# ~100M params: gemma3-family reduced to d_model=640, 10 layers, 50k vocab
cfg = get_config("gemma3-1b", reduced=True).replace(
    num_layers=10, d_model=640, num_heads=8, num_kv_heads=2, head_dim=80,
    d_ff=2560, vocab_size=50304, sliding_window=256, global_interval=5,
    compute_dtype="float32",
)
model = build(cfg)
shape = ShapeConfig("train100m", seq_len=args.seq, global_batch=args.batch, kind="train")
ocfg = opt_lib.OptimizerConfig(name="adamw", learning_rate=3e-4, warmup_steps=20,
                               decay_steps=max(args.steps, 100))

params, axes, opt_state, ef = init_train_state(model, ocfg, jax.random.PRNGKey(0))
print(f"model: {param_count(params) / 1e6:.1f}M parameters "
      f"({cfg.num_layers}L d={cfg.d_model} ff={cfg.d_ff} V={cfg.vocab_size})")

stream = SyntheticTokenStream(cfg, shape)
start_step = 0
if ckpt.latest_step(args.ckpt_dir) is not None:
    restored, extra = ckpt.restore(args.ckpt_dir, {"params": params, "opt": opt_state})
    params = jax.tree_util.tree_map(jax.numpy.asarray, restored["params"])
    opt_state = jax.tree_util.tree_map(jax.numpy.asarray, restored["opt"])
    stream.state = DataState.from_dict(extra["data"])
    start_step = int(extra["step"])
    print(f"resumed from checkpoint at step {start_step}")

# HiCR: the train step is an ExecutionUnit on the SPMD compute manager
mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
cpm = spmd.SpmdComputeManager(mesh)
pu = cpm.create_processing_unit(cpm.mesh_compute_resource())
cpm.initialize(pu)
unit = cpm.create_execution_unit(make_train_step(model, ocfg, TrainConfig()),
                                 name="train_step", donate_argnums=(0, 1))

loader = PrefetchingLoader(stream, depth=2, workers=2).start()
t0 = time.time()
try:
    for step in range(start_step, args.steps):
        batch = loader.next_batch()
        state = cpm.create_execution_state(unit, params, opt_state, ef, batch)
        cpm.execute(pu, state)
        cpm.await_(pu)
        params, opt_state, ef, metrics = state.get_result()
        if (step + 1) % 5 == 0:
            tok_s = args.batch * args.seq * 5 / (time.time() - t0)
            print(f"step {step + 1:4d}  loss={float(metrics['loss']):.4f}  "
                  f"grad_norm={float(metrics['grad_norm']):.3f}  tok/s={tok_s:,.0f}")
            t0 = time.time()
        if (step + 1) % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, step + 1, {"params": params, "opt": opt_state},
                             extra={"data": stream.state.to_dict(), "step": step + 1})
            print(f"checkpoint committed: {path}")
finally:
    loader.stop()
    cpm.finalize(pu)
print("done.")
