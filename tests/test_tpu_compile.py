"""Compile the serve path's decode kernels for a TPU v5e that is described,
not attached, at gemma3-1b widths (H=4, KV=1, hd=256, bfloat16, page 16,
B=8), the paged kernel also at the shapes of the chip benchmark's
granite-20b.chat cell and at head widths that take its per-page grid.
Mosaic refuses here what it would refuse on the chip (unaligned blocks,
SMEM operand shapes, VMEM overuse), so a kernel change that only
interpret mode accepts fails in this file.

The topology is described inside a fixture: the TPU library admits one
process at a time, and only the test worker that runs this file loads it.
"""
import os

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
