"""Serial serving engine: prefill + greedy decode over the model zoo's
uniform state protocol.

`ServeEngine` handles one batch end-to-end at a time — it is the serial
reference whose tokens `serve/scheduler.py`'s continuous-batching paths
must reproduce exactly (tests/test_serve.py). Execution units are
dispatched through a HiCR compute manager obtained from a registry-built
`Runtime` facade, so the engine never imports a concrete backend.

The channel front door lives in `serve/server.py` (`ChannelServer`), driven
by the continuous-batching scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime import Runtime
from repro.models.model_zoo import ModelBundle


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, steps)
    prefill_logits: np.ndarray  # (B, V)


class ServeEngine:
    def __init__(
        self,
        model: ModelBundle,
        params,
        *,
        max_len: int = 256,
        runtime: Optional[Runtime] = None,
    ):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.rt = runtime or Runtime("jaxdev")
        cm = self.rt.compute_manager
        # execution units through the HiCR compute manager. Prefill must
        # allocate cache headroom up to max_len so decode steps never write
        # past the cache (model_zoo.make_prefill).
        prefill_fn = model.make_prefill(max_len) if model.make_prefill else model.prefill
        self._prefill_unit = cm.create_execution_unit(
            lambda p, b: prefill_fn(p, b), name="prefill", jit=True
        )
        self._decode_unit = cm.create_execution_unit(
            lambda p, s, b: model.decode_step(p, s, b), name="decode_step", jit=True
        )

    def _run(self, unit, *args):
        return self.rt.run(unit, *args)

    def generate(
        self, prompts: np.ndarray, steps: int, *, on_first_token=None
    ) -> GenerationResult:
        """prompts: (B, S) int32. Greedy decode `steps` new tokens.
        `on_first_token`, if given, is called once the first output token is
        materialized (prefill done) — the serve benchmark's TTFT probe."""
        B, S = prompts.shape
        logits, state = self._run(self._prefill_unit, self.params, {"tokens": jnp.asarray(prompts)})
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        if on_first_token is not None:
            jax.block_until_ready(tok)
            on_first_token()
        # cache positions include any multimodal prefix (VLM vision tokens)
        pos = S + (self.model.cfg.vision_tokens if self.model.cfg.family == "vlm" else 0)
        for _ in range(steps):
            out.append(np.asarray(tok)[:, 0])
            dlogits, state = self._run(
                self._decode_unit, self.params, state, {"tokens": tok, "pos": jnp.int32(pos)}
            )
            tok = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)[:, None]
            pos += 1
        return GenerationResult(
            tokens=np.stack(out, axis=1), prefill_logits=np.asarray(logits)
        )


# compat re-export: the channel front door moved to serve/server.py
from repro.serve.server import ChannelServer  # noqa: E402,F401
