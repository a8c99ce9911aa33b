"""End-to-end system behaviour: the full HiCR-launched train→checkpoint→
restore→serve path on one reduced architecture — every substrate layer in
one flow (the paper's thesis: the application never names a technology)."""
import jax
import numpy as np

from repro.backends import spmd
from repro.configs import ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt_lib
from repro.train.data import SyntheticTokenStream
from repro.train.train_step import TrainConfig, init_train_state, make_train_step


def test_train_checkpoint_restore_serve_roundtrip(tmp_path):
    cfg = get_config("gemma3-1b", reduced=True)
    model = build(cfg)
    shape = ShapeConfig("sys", seq_len=32, global_batch=2, kind="train")
    ocfg = opt_lib.OptimizerConfig(name="adamw", learning_rate=1e-3, warmup_steps=2)

    # ---- train 3 steps through the SPMD compute manager (HiCR path) -------
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    cpm = spmd.SpmdComputeManager(mesh)
    pu = cpm.create_processing_unit(cpm.mesh_compute_resource())
    cpm.initialize(pu)
    unit = cpm.create_execution_unit(
        make_train_step(model, ocfg, TrainConfig()), name="train_step")

    params, _, opt_state, ef = init_train_state(model, ocfg, jax.random.PRNGKey(0))
    stream = SyntheticTokenStream(cfg, shape)
    losses = []
    for _ in range(3):
        st = cpm.create_execution_state(unit, params, opt_state, ef, stream.next_batch())
        cpm.execute(pu, st)
        cpm.await_(pu)
        params, opt_state, ef, metrics = st.get_result()
        losses.append(float(metrics["loss"]))
    cpm.finalize(pu)
    assert all(np.isfinite(losses))

    # ---- checkpoint, restore, verify bit-identical weights -----------------
    path = ckpt.save(str(tmp_path), 3, {"params": params},
                     extra={"data": stream.state.to_dict(), "step": 3})
    restored, extra = ckpt.restore(str(tmp_path), {"params": params})
    assert extra["step"] == 3
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # ---- serve from the restored weights ------------------------------------
    engine = ServeEngine(
        model, jax.tree_util.tree_map(jax.numpy.asarray, restored["params"]),
        max_len=48)
    prompts = np.array([[1, 2, 3, 4]], dtype=np.int32)
    out = engine.generate(prompts, steps=4)
    assert out.tokens.shape == (1, 4)
    # deterministic: same prompt, same weights, same tokens
    again = engine.generate(prompts, steps=4)
    np.testing.assert_array_equal(out.tokens, again.tokens)
