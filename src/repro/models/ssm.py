"""Recurrent sequence-mixing blocks: xLSTM's mLSTM and sLSTM, and Mamba2
(SSD). The mLSTM and Mamba2 cores are both instances of the scalar-gated
linear recurrence

    S_t = a_t * S_{t-1} + k_t^T v_t ;  y_t = q_t @ S_t

served by `repro.kernels.ops.gated_linear_scan` (chunkwise-parallel,
MXU-friendly; Pallas kernel on TPU). sLSTM is a scalar-memory recurrence
with cross-head recurrent connections and is inherently sequential — it runs
as a lax.scan (xlstm-125m uses it in 1 of every `slstm_interval` blocks).

Simplifications vs. the source papers (documented in DESIGN.md):
* mLSTM exponential input gate replaced by a sigmoid gate folded into k
  (avoids the max-state stabilizer while keeping the matrix-memory form).
* Mamba2 uses n_groups=1 and shares B/C across heads (as the paper's
  default), without the optional extra normalization branches.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.kernels import ops
from .common import ParamBuilder, rms_norm


def _impl(cfg: ArchConfig) -> str:
    return "sequential" if cfg.ssd_impl == "sequential" else "ref"


def _chunk_for(S: int) -> int:
    c = min(128, S)
    while S % c:
        c //= 2
    return max(c, 1)


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory block)
# ---------------------------------------------------------------------------


def init_mlstm_block(pb: ParamBuilder, cfg: ArchConfig, n_layers: Optional[int] = None):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    lead = () if n_layers is None else (n_layers,)
    lax = () if n_layers is None else ("layers",)
    return {
        "norm": pb.zeros(lead + (d,), lax + ("norm",)),
        "w_qkv": pb.normal(lead + (d, 3 * di), lax + ("embed", "ssm_inner"), fan_in=d),
        "w_gates": pb.normal(lead + (d, 2 * H), lax + ("embed", "heads"), fan_in=d),
        "b_gates": pb.constant(1.0, lead + (2 * H,), lax + ("heads",)),
        "w_ogate": pb.normal(lead + (d, di), lax + ("embed", "ssm_inner"), fan_in=d),
        "w_out": pb.normal(lead + (di, d), lax + ("ssm_inner", "embed"), fan_in=di),
    }


def _mlstm_qkvg(cfg, p, x):
    B, S, d = x.shape
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    hd = di // H
    cd = x.dtype
    qkv = jnp.einsum("bsd,de->bse", x, p["w_qkv"].astype(cd))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)  # (B,H,S,hd)
    k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3) / (hd**0.5)
    v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    gates = jnp.einsum("bsd,dg->bsg", x, p["w_gates"].astype(cd)) + p["b_gates"].astype(cd)
    f_logit, i_logit = jnp.split(gates, 2, axis=-1)  # (B,S,H) each
    log_a = jax.nn.log_sigmoid(f_logit.astype(jnp.float32)).transpose(0, 2, 1)  # (B,H,S)
    i_gate = jax.nn.sigmoid(i_logit.astype(jnp.float32)).transpose(0, 2, 1)  # (B,H,S)
    k = k * i_gate[..., None].astype(cd)
    return q, k, v, log_a


def mlstm_forward(cfg: ArchConfig, p, x, state=None):
    """x: (B,S,d). Returns (y, new_state) with state {"S","n"}."""
    B, S, d = x.shape
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    hd = di // H
    cd = x.dtype
    h = rms_norm(x, p["norm"], eps=cfg.norm_eps)
    q, k, v, log_a = _mlstm_qkvg(cfg, p, h)
    chunk = _chunk_for(S)
    s0 = state["S"] if state is not None else None
    n0 = state["n"] if state is not None else None
    y, S_f = ops.gated_linear_scan(q, k, v, log_a, chunk=chunk, initial_state=s0, impl=_impl(cfg))
    ones = jnp.ones((B, H, S, 1), dtype=cd)
    nrm, n_f = ops.gated_linear_scan(q, k, ones, log_a, chunk=chunk, initial_state=n0, impl=_impl(cfg))
    y = y.astype(jnp.float32) / jnp.maximum(jnp.abs(nrm.astype(jnp.float32)), 1.0)
    y = y.astype(cd).transpose(0, 2, 1, 3).reshape(B, S, di)
    ogate = jax.nn.silu(jnp.einsum("bsd,de->bse", h, p["w_ogate"].astype(cd)))
    out = jnp.einsum("bse,ed->bsd", y * ogate, p["w_out"].astype(cd))
    return x + out, {"S": S_f, "n": n_f}


def mlstm_init_state(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.num_heads
    di = cfg.ssm_expand * d
    hd = di // H
    return {
        "S": jnp.zeros((batch, H, hd, hd), dtype=dtype),
        "n": jnp.zeros((batch, H, hd, 1), dtype=dtype),
    }


def mlstm_decode_step(cfg: ArchConfig, p, x, state):
    """x: (B,1,d) -> (y (B,1,d), new_state)."""
    y, new_state = mlstm_forward(cfg, p, x, state=state)
    return y, new_state


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory block, sequential)
# ---------------------------------------------------------------------------


def init_slstm_block(pb: ParamBuilder, cfg: ArchConfig, n_layers: Optional[int] = None):
    d = cfg.d_model
    lead = () if n_layers is None else (n_layers,)
    lax = () if n_layers is None else ("layers",)
    return {
        "norm": pb.zeros(lead + (d,), lax + ("norm",)),
        "w_in": pb.normal(lead + (d, 4 * d), lax + ("embed", "ssm_inner"), fan_in=d),
        "w_rec": pb.normal(lead + (d, 4 * d), lax + ("embed", "ssm_inner"), fan_in=d, scale=0.5),
        "b": pb.zeros(lead + (4 * d,), lax + ("ssm_inner",)),
        "w_out": pb.normal(lead + (d, d), lax + ("embed", "embed"), fan_in=d),
    }


def _slstm_cell(cfg, p, carry, z_t):
    """carry: (c, n, h) each (B, d); z_t: (B, 4d) pre-activation (input part)."""
    c, n, h = carry
    cd = z_t.dtype
    rec = jnp.einsum("bd,de->be", h, p["w_rec"].astype(cd))
    zi, zf, zz, zo = jnp.split((z_t + rec + p["b"].astype(cd)).astype(jnp.float32), 4, axis=-1)
    i_g = jnp.exp(jnp.minimum(zi, 8.0))  # capped exponential input gate
    f_g = jax.nn.sigmoid(zf)
    z_v = jnp.tanh(zz)
    o_g = jax.nn.sigmoid(zo)
    c_new = f_g * c + i_g * z_v
    n_new = f_g * n + i_g
    h_new = (o_g * c_new / jnp.maximum(n_new, 1.0)).astype(cd)
    return (c_new, n_new, h_new), h_new


def slstm_forward(cfg: ArchConfig, p, x, state=None):
    B, S, d = x.shape
    cd = x.dtype
    h_in = rms_norm(x, p["norm"], eps=cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h_in, p["w_in"].astype(cd))  # (B,S,4d)
    if state is None:
        state = slstm_init_state(cfg, B)
    carry = (state["c"], state["n"], state["h"].astype(cd))

    def step(carry, z_t):
        return _slstm_cell(cfg, p, carry, z_t)

    (c, n, h_last), hs = jax.lax.scan(step, carry, jnp.moveaxis(z, 1, 0))
    hs = jnp.moveaxis(hs, 0, 1)  # (B,S,d)
    out = jnp.einsum("bsd,de->bse", hs, p["w_out"].astype(cd))
    return x + out, {"c": c, "n": n, "h": h_last.astype(jnp.float32)}


def slstm_init_state(cfg: ArchConfig, batch: int):
    d = cfg.d_model
    z = jnp.zeros((batch, d), dtype=jnp.float32)
    return {"c": z, "n": z, "h": z}


def slstm_decode_step(cfg: ArchConfig, p, x, state):
    return slstm_forward(cfg, p, x, state=state)


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------


def init_mamba_block(pb: ParamBuilder, cfg: ArchConfig, n_layers: Optional[int] = None):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    st = cfg.ssm_state
    conv_dim = di + 2 * st
    lead = () if n_layers is None else (n_layers,)
    lax = () if n_layers is None else ("layers",)
    return {
        "norm": pb.zeros(lead + (d,), lax + ("norm",)),
        # Three SEPARATE input projections (z / xBC / dt) instead of one
        # fused (d, 2di+2st+H) matrix: a fused projection's jnp.split points
        # do not align with the "model"-axis shard boundaries, forcing GSPMD
        # to all-gather the full (B, S, 14k) activation on every layer
        # (measured: the dominant zamba2 train temp term; EXPERIMENTS §Perf).
        "w_z": pb.normal(lead + (d, di), lax + ("embed", "ssm_inner"), fan_in=d),
        "w_xbc": pb.normal(lead + (d, conv_dim), lax + ("embed", "ssm_inner"), fan_in=d),
        "w_dt": pb.normal(lead + (d, H), lax + ("embed", "heads"), fan_in=d),
        "conv_w": pb.normal(lead + (cfg.ssm_conv_width, conv_dim), lax + ("conv", "ssm_inner"), fan_in=cfg.ssm_conv_width),
        "conv_b": pb.zeros(lead + (conv_dim,), lax + ("ssm_inner",)),
        "A_log": pb.zeros(lead + (H,), lax + ("heads",)),
        "dt_bias": pb.zeros(lead + (H,), lax + ("heads",)),
        "D": pb.ones(lead + (H,), lax + ("heads",)),
        "out_norm": pb.zeros(lead + (di,), lax + ("ssm_inner",)),
        "w_out": pb.normal(lead + (di, d), lax + ("ssm_inner", "embed"), fan_in=di),
    }


def _causal_conv(x, w, b, *, state=None):
    """Depthwise causal conv. x: (B,S,Cd); w: (W,Cd); returns (y, new_state)
    where state carries the trailing W-1 inputs for decode."""
    B, S, Cd = x.shape
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((B, W - 1, Cd), dtype=x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, S+W-1, Cd)
    # depthwise: sum_w xp[:, i+w, c] * w[w, c]
    y = jnp.zeros((B, S, Cd), dtype=x.dtype)
    for i in range(W):  # W is tiny (4): unrolled taps
        y = y + xp[:, i : i + S, :] * w[i][None, None, :]
    new_state = xp[:, S:, :]
    return y + b[None, None, :], new_state


def mamba_forward(cfg: ArchConfig, p, x, state=None):
    """x: (B,S,d). Returns (y, new_state {"S","conv"})."""
    B, S, d = x.shape
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    hd = di // H
    st = cfg.ssm_state
    cd = x.dtype
    h = rms_norm(x, p["norm"], eps=cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, p["w_z"].astype(cd))
    xBC = jnp.einsum("bsd,de->bse", h, p["w_xbc"].astype(cd))
    dt = jnp.einsum("bsd,de->bse", h, p["w_dt"].astype(cd))
    conv_state = state["conv"] if state is not None else None
    xBC, conv_state = _causal_conv(xBC, p["conv_w"].astype(cd), p["conv_b"].astype(cd), state=conv_state)
    xBC = jax.nn.silu(xBC)
    x_ssm, Bmat, Cmat = jnp.split(xBC, [di, di + st], axis=-1)  # (B,S,di),(B,S,st),(B,S,st)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    log_a = (dt * A[None, None, :]).transpose(0, 2, 1)  # (B,H,S)

    # map to gated linear scan: q=C, k=B*dt, v=x (per head)
    q = jnp.broadcast_to(Cmat[:, None, :, :], (B, H, S, st)).astype(cd)
    k = (Bmat[:, None, :, :] * dt.transpose(0, 2, 1)[..., None]).astype(cd)
    v = x_ssm.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    chunk = _chunk_for(S)
    s0 = state["S"] if state is not None else None
    y, S_f = ops.gated_linear_scan(q, k, v, log_a, chunk=chunk, initial_state=s0, impl=_impl(cfg))
    y = y + p["D"].astype(cd)[None, :, None, None] * v  # skip connection
    y = y.transpose(0, 2, 1, 3).reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], eps=cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(cd))
    return x + out, {"S": S_f, "conv": conv_state}


def mamba_init_state(cfg: ArchConfig, batch: int, dtype=jnp.float32, conv_dtype=None):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    hd = di // H
    st = cfg.ssm_state
    conv_dim = di + 2 * st
    return {
        "S": jnp.zeros((batch, H, st, hd), dtype=dtype),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype=conv_dtype or dtype),
    }


def mamba_decode_step(cfg: ArchConfig, p, x, state):
    return mamba_forward(cfg, p, x, state=state)
