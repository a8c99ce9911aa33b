"""Compile the serve path's decode kernels for a TPU v5e that is described,
not attached, at gemma3-1b widths (H=4, KV=1, hd=256, bfloat16, page 16,
B=8), the paged kernel also at the shapes of the chip benchmark's
granite-20b.chat cell (also reading one layer of the cell's stacked pool)
and at head widths that take its per-page grid, and the dense paged
decoder's decode interval, which must write its pool in place.
Mosaic refuses here what it would refuse on the chip (unaligned blocks,
SMEM operand shapes, VMEM overuse), so a kernel change that only
interpret mode accepts fails in this file.

The topology is described inside a fixture: the TPU library admits one
process at a time, and only the test worker that runs this file loads it.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_decode_attention import paged_decode_attention

B, H, KV, HD, PAGE = 8, 4, 1, 256, 16
N_PAGES = 44  # 704 positions per slot: past the 512-token window
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [512, 688, 704])
def test_decode_attention_compiles_for_v5e(one_chip, S):
    fn = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=False))
    compiled = fn.lower(
        _sds(one_chip, (B, H, HD), BF16),
        _sds(one_chip, (B, S, KV, HD), BF16),
        _sds(one_chip, (B, S, KV, HD), BF16),
        _sds(one_chip, (B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [0, 512])
def test_paged_decode_attention_compiles_for_v5e(one_chip, window):
    P = B * N_PAGES + 1
    fn = jax.jit(
        lambda q, k, v, t, p: paged_decode_attention(
            q, k, v, t, p, window=window, interpret=False
        )
    )
    compiled = fn.lower(
        _sds(one_chip, (B, H, HD), BF16),
        _sds(one_chip, (P, PAGE, KV, HD), BF16),
        _sds(one_chip, (P, PAGE, KV, HD), BF16),
        _sds(one_chip, (B, N_PAGES), jnp.int32),
        _sds(one_chip, (B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "b, h, kv, hd, n, pool",
    [
        pytest.param(48, 48, 1, 128, 144, 6913, id="granite-20b.chat"),
        pytest.param(8, 16, 4, 128, 44, 353, id="gqa-kv4-block-walk"),
        pytest.param(8, 64, 8, 160, 44, 353, id="hd160-page-grid"),
    ],
)
def test_paged_decode_attention_compiles_at_serving_shapes(one_chip, b, h, kv, hd, n, pool):
    fn = jax.jit(lambda q, k, v, t, p: paged_decode_attention(q, k, v, t, p, interpret=False))
    compiled = fn.lower(
        _sds(one_chip, (b, h, hd), BF16),
        _sds(one_chip, (pool, PAGE, kv, hd), BF16),
        _sds(one_chip, (pool, PAGE, kv, hd), BF16),
        _sds(one_chip, (b, n), jnp.int32),
        _sds(one_chip, (b,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_layer_of_a_stacked_pool_compiles_at_the_cells_shapes(one_chip):
    """granite-20b.chat's interval reads each layer out of the stacked
    13-layer pool through the scalar-prefetched layer index."""
    L, b, h, n, pool = 13, 48, 48, 144, 6913
    fn = jax.jit(lambda q, k, v, t, p, ly: paged_decode_attention(q, k, v, t, p, ly,
                                                                   interpret=False))
    compiled = fn.lower(
        _sds(one_chip, (b, h, 128), BF16),
        _sds(one_chip, (L, pool, PAGE, 1, 128), BF16),
        _sds(one_chip, (L, pool, PAGE, 1, 128), BF16),
        _sds(one_chip, (b, n), jnp.int32),
        _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _whole_copies(hlo: str, shapes) -> dict:
    """Instructions that copy, or update a slice of, a buffer of `shapes`,
    by the computation that holds them ("ENTRY" for the unit's top level,
    which runs once per call)."""
    op = re.compile(r"= (\w+\[[\d,]*\])\{[^}]*\} (copy|copy-done|dynamic-update-slice)\(")
    found, comp = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
        elif (m := op.search(line)) and m.group(1) in shapes:
            found.setdefault(comp, []).append((m.group(2), line.strip()[:120]))
    return found


def test_the_decode_interval_writes_the_pool_in_place(one_chip, monkeypatch):
    """The dense paged decoder's `fused_decode_x8`, compiled for the chip:
    no op inside the tick or layer loops copies the stacked pool or a layer
    of it, or rewrites either through a dynamic-update-slice (only the new
    rows are scattered). The pools given are left intact, so the unit's top
    level copies each once per call into the pools it returns."""
    import repro.kernels.ops as ops
    import repro.kernels.paged_decode_attention as pda
    from repro.configs import ArchConfig
    from repro.models import build
    from repro.serve.batching import PagedSlotDecoder

    monkeypatch.setattr(ops, "decode_impl", lambda: "pallas")
    monkeypatch.setattr(pda, "interpret_mode", lambda: False)
    cfg = ArchConfig(name="paged-probe", family="dense", num_layers=3, d_model=512,
                     num_heads=4, num_kv_heads=1, d_ff=1024, vocab_size=1024,
                     max_seq_len=1024, head_dim=128, param_dtype="bfloat16",
                     compute_dtype="bfloat16")
    model = build(cfg)
    dec = PagedSlotDecoder(model, None, max_slots=16, max_len=1024, page_size=PAGE,
                           sync_interval=8)
    spec = lambda x: _sds(one_chip, x.shape, x.dtype)  # noqa: E731
    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0]))
    pools = jax.tree_util.tree_map(spec, dec.kv.pools)
    table = _sds(one_chip, (16, dec.layout.n_pages_seq), jnp.int32)
    compiled = dec._fused_unit.fn.lower(params, pools, table, spec(dec.ctl)).compile()

    stacked = pools["k"].shape
    shapes = {"bf16[%s]" % ",".join(map(str, s)) for s in (stacked, stacked[1:])}
    found = _whole_copies(compiled.as_text(), shapes)
    assert set(found) <= {"ENTRY"}, found
    assert {kind for kind, _ in found.get("ENTRY", [])} <= {"copy", "copy-done"}
