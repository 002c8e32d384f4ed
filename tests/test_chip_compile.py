"""Compile the serve path's attention kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a ``v5e:2x2`` topology that is
described, not attached, and refuses what Mosaic would refuse on the chip
(unaligned block shapes, oversized VMEM) — which interpret mode accepts.
Shapes are granite-8b's: head_dim 128, 8 KV heads, GQA 4, block size 16.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and every test worker imports this
file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.attention import ops

HEAD_DIM, KV_HEADS, GQA, BLOCK = 128, 8, 4, 16
SLOTS, NUM_BLOCKS, TABLE_W, CHUNK = 8, 64, 8, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _pool(kv_dtype, sh):
    page = (NUM_BLOCKS, BLOCK, KV_HEADS, HEAD_DIM)
    if kv_dtype == "bf16":
        return {"k": _spec(page, jnp.bfloat16, sh),
                "v": _spec(page, jnp.bfloat16, sh)}
    scales = (NUM_BLOCKS, BLOCK, KV_HEADS)
    return {"k": _spec(page, jnp.int8, sh), "v": _spec(page, jnp.int8, sh),
            "k_scale": _spec(scales, jnp.float32, sh),
            "v_scale": _spec(scales, jnp.float32, sh)}


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel emitted"


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype):
    q = _spec((SLOTS, 1, KV_HEADS * GQA, HEAD_DIM), jnp.bfloat16, one_chip)
    bt = _spec((SLOTS, TABLE_W), jnp.int32, one_chip)
    idx = _spec((SLOTS,), jnp.int32, one_chip)
    compiled = ops.paged_attention.lower(
        _pool(kv_dtype, one_chip), q, bt, idx, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("block_q", [None, 128])
def test_paged_span_compiles_for_v5e(one_chip, kv_dtype, block_q):
    rows = 2
    q = _spec((rows, CHUNK, KV_HEADS * GQA, HEAD_DIM), jnp.bfloat16, one_chip)
    bt = _spec((rows, TABLE_W), jnp.int32, one_chip)
    vec = _spec((rows,), jnp.int32, one_chip)
    compiled = ops.paged_span_attention.lower(
        _pool(kv_dtype, one_chip), q, bt, vec, vec, block_q=block_q,
        interpret=False).compile()
    _assert_kernel(compiled)


def test_dense_flash_compiles_for_v5e(one_chip):
    q = _spec((1, 256, KV_HEADS * GQA, HEAD_DIM), jnp.bfloat16, one_chip)
    kv = _spec((1, 256, KV_HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    compiled = ops.flash_attention.lower(q, kv, kv, interpret=False).compile()
    _assert_kernel(compiled)
