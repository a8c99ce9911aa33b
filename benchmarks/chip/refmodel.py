"""Plain float32 reference of the served dense block, and its weights.

Written from the block the configuration file states (``program``): token
embedding; per layer RMSNorm with gain ``1 + w``, rotary positions over the
whole head (halves, theta ``rope_theta``), causal grouped-query attention
with scale ``1/sqrt(head_dim)``, output projection, residual; RMSNorm, then
a GELU (tanh form) or SwiGLU MLP, residual; final RMSNorm and an untied
output projection. It imports nothing of the program under test.

`make_weights` builds the weights on the device in one jitted call from the
seed, in the dtype they are served in, in the tree layout the program takes.
The reference reads the same weights, upcast to float32 one layer at a time,
under ``jax.default_matmul_precision("highest")``.

`QUANTIZERS` make the controls: the same reference with every matrix
rounded to a lower precision (int8 per output channel, float8 e4m3, or
bfloat16 for a float32 configuration).

`matmul_params`, `attn_flops`, `attn_bytes` and `decode_flops` count the
work of one decoded token of this block, for the benchmark's `work` module.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def dims(p: dict) -> dict:
    hd = p["d_model"] // p["num_heads"]
    return dict(L=p["num_layers"], d=p["d_model"], H=p["num_heads"],
                KV=p["num_kv_heads"], hd=hd, f=p["d_ff"], V=p["vocab_size"],
                gated=p["act"] == "silu")


def make_weights(p: dict, seed: int):
    """Random weights from `seed`: matrices N(0, 1/fan_in), norm gains
    ``1 + N(0, 0.1)``, in ``param_dtype``, created on the device."""
    m = dims(p)
    dt = jnp.dtype(p["param_dtype"])
    L, d, H, KV, hd, f, V = (m[k] for k in ("L", "d", "H", "KV", "hd", "f", "V"))
    shapes = {
        ("embed", "embedding"): ((V, d), d),
        ("embed", "unembed"): ((d, V), d),
        ("final_norm",): ((d,), 0),
        ("layers", "ln1"): ((L, d), 0),
        ("layers", "ln2"): ((L, d), 0),
        ("layers", "attn", "wq"): ((L, d, H, hd), d),
        ("layers", "attn", "wk"): ((L, d, KV, hd), d),
        ("layers", "attn", "wv"): ((L, d, KV, hd), d),
        ("layers", "attn", "wo"): ((L, H, hd, d), H * hd),
        ("layers", "ffn", "w_up"): ((L, d, f), d),
        ("layers", "ffn", "w_down"): ((L, f, d), f),
    }
    if m["gated"]:
        shapes[("layers", "ffn", "w_gate")] = ((L, d, f), d)

    @jax.jit
    def build(key):
        tree: dict = {}
        keys = jax.random.split(key, len(shapes))
        for k, (path, (shape, fan_in)) in zip(keys, sorted(shapes.items())):
            std = 1.0 / math.sqrt(fan_in) if fan_in else 0.1
            leaf = (jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)).astype(dt)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return tree

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)
    return build(key)


# -- work counts -----------------------------------------------------------------

BF16 = 2  # bytes per element of the served weights, activations and cache


def matmul_params(p: dict) -> int:
    """Weights a decode token multiplies by: every layer's projections and
    MLP, and the output head (the embedding is a gather)."""
    m = dims(p)
    d, H, KV, hd, f = m["d"], m["H"], m["KV"], m["hd"], m["f"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = (3 if m["gated"] else 2) * d * f
    return m["L"] * (attn + mlp) + d * m["V"]


def attn_flops(p: dict, ctx: int) -> int:
    """Single-token attention over `ctx` positions, all layers: QK^T and PV."""
    m = dims(p)
    return m["L"] * 4 * m["H"] * m["hd"] * ctx


def attn_bytes(p: dict, ctx: int) -> int:
    """Single-token attention, all layers: K and V of `ctx` positions, the
    query in and the output out."""
    m = dims(p)
    return m["L"] * BF16 * (2 * ctx * m["KV"] * m["hd"] + 2 * m["H"] * m["hd"])


def decode_flops(p: dict, ctx: int) -> int:
    """Model operations of one decoded token at context `ctx`."""
    return 2 * matmul_params(p) + attn_flops(p, ctx)


# -- quantisers for the control ---------------------------------------------


def _int8(w, axis):
    """Symmetric int8 with one scale per output channel (reduced over `axis`)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(w / s).clip(-127, 127) * s


def _fp8(w, axis):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _bf16(w, axis):
    return w.astype(jnp.bfloat16).astype(jnp.float32)


QUANTIZERS = {"int8": _int8, "fp8": _fp8, "bf16": _bf16}


def _q(w, quant: Optional[str], axis):
    w = w.astype(jnp.float32)
    return w if quant is None else QUANTIZERS[quant](w, axis)


#: query rows per attention block of the reference
Q_BLOCK = 512

# -- the block -----------------------------------------------------------------


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate halves by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs  # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("p_items", "quant"))
def _layer(layers, l, h, *, p_items, quant):
    """Layer `l` of the stacked weights over h: (S, d) float32, causal."""
    p = dict(p_items)
    m = dims(p)
    H, KV, hd = m["H"], m["KV"], m["hd"]
    eps, theta = p["norm_eps"], p["rope_theta"]
    w = jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, False), layers)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _rms(h, w["ln1"].astype(jnp.float32), eps)
    q = jnp.einsum("sd,dhk->shk", x, _q(w["attn"]["wq"], quant, 0))
    k = jnp.einsum("sd,dhk->shk", x, _q(w["attn"]["wk"], quant, 0))
    v = jnp.einsum("sd,dhk->shk", x, _q(w["attn"]["wv"], quant, 0))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = H // KV
    qg = q.reshape(S, KV, g, hd) / math.sqrt(hd)
    parts = []
    for q0 in range(0, S, Q_BLOCK):  # query blocks bound the score tensor
        qb = qg[q0 : q0 + Q_BLOCK]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k)
        causal = pos[q0 : q0 + qb.shape[0], None] >= pos[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        parts.append(jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v))
    a = jnp.concatenate(parts, axis=0).reshape(S, H, hd)
    h = h + jnp.einsum("shk,hkd->sd", a, _q(w["attn"]["wo"], quant, (0, 1)))
    x = _rms(h, w["ln2"].astype(jnp.float32), eps)
    up = x @ _q(w["ffn"]["w_up"], quant, 0)
    if "w_gate" in w["ffn"]:
        act = jax.nn.silu(x @ _q(w["ffn"]["w_gate"], quant, 0)) * up
    else:
        act = _gelu_tanh(up)
    return h + act @ _q(w["ffn"]["w_down"], quant, 0)


@functools.partial(jax.jit, static_argnames=("p_items", "quant"))
def _head(params, h, rows, *, p_items, quant):
    p = dict(p_items)
    x = _rms(h[rows], params["final_norm"].astype(jnp.float32), p["norm_eps"])
    return x @ _q(params["embed"]["unembed"], quant, 0)


def logits(p: dict, params, tokens, rows, *, length: int, quant: Optional[str] = None):
    """Reference logits of `tokens` at positions `rows`. The sequence is
    padded to `length` (causal attention keeps padding out of every real
    position), so one compiled program serves every request."""
    items = tuple(sorted(p.items()))
    tok = np.zeros((length,), np.int32)
    tok[: len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["embedding"][jnp.asarray(tok)].astype(jnp.float32)
        for l in range(p["num_layers"]):
            h = _layer(params["layers"], l, h, p_items=items, quant=quant)
        return _head(params, h, jnp.asarray(rows, jnp.int32), p_items=items, quant=quant)


def served_gaps(p: dict, params, prompt, served, *, length: int, pad_rows: int,
                controls=()):
    """Widest gap of each served token under the reference: max over
    positions of ``ref_best - ref[served token]``. With `controls`, also the
    gap of the token each lower-precision control would put first."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    rows = np.full((pad_rows,), len(prompt) - 1, np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    ref = np.asarray(logits(p, params, seq, rows, length=length))[:n]
    best = ref.max(axis=-1)
    out = {"program": float(np.max(best - ref[np.arange(n), np.asarray(served)]))}
    for quant in controls:
        c = np.asarray(logits(p, params, seq, rows, length=length, quant=quant))[:n]
        pick = c.argmax(axis=-1)
        out[quant] = float(np.max(best - ref[np.arange(n), pick]))
    return out
