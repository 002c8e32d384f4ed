"""Compile the serve path's attention kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a ``v5e:2x2`` topology that is
described, not attached, and refuses what Mosaic would refuse on the chip
(unaligned block shapes, oversized VMEM) — which interpret mode accepts.
Shapes are granite-8b's: head_dim 128, 8 KV heads, GQA 4, block size 16;
the paged decode kernel is also built at yi-9b's fold (4 KV heads, GQA 8)
over a 256-entry table, as the decode benchmark cell runs it, and at the
registry's other kv-head counts (1, 12, 16, 32).

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and every test worker imports this
file.
"""
from __future__ import annotations

import os
import re

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


def _pool(kv_dtype, sh, num_blocks=NUM_BLOCKS, kv_heads=KV_HEADS,
          head_dim=HEAD_DIM, block=BLOCK):
    page = (num_blocks, block, kv_heads, head_dim)
    if kv_dtype == "bf16":
        return {"k": _spec(page, jnp.bfloat16, sh),
                "v": _spec(page, jnp.bfloat16, sh)}
    scales = (num_blocks, block, kv_heads)
    return {"k": _spec(page, jnp.int8, sh), "v": _spec(page, jnp.int8, sh),
            "k_scale": _spec(scales, jnp.float32, sh),
            "v_scale": _spec(scales, jnp.float32, sh)}


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel emitted"


# (slots, kv heads, GQA, table entries, pool blocks, head dim, block size)
# per model; "mqa" is a lone kv head (recurrentgemma's local attention, or a
# yi shard at mp 4); codeqwen1.5-7b and deepseek-moe-16b are unsharded MHA
# pools whose steps the VMEM budget bounds; whisper-small's 12 heads do not
# divide a 128-row score group
DECODE_SHAPES = {
    "granite": (SLOTS, KV_HEADS, GQA, TABLE_W, NUM_BLOCKS, HEAD_DIM, BLOCK),
    "yi": (15, 4, 8, 256, 3856, HEAD_DIM, BLOCK),
    "mqa": (SLOTS, 1, 8, TABLE_W, NUM_BLOCKS, HEAD_DIM, BLOCK),
    "codeqwen": (SLOTS, 32, 1, 256, NUM_BLOCKS, HEAD_DIM, BLOCK),
    "deepseek": (SLOTS, 16, 1, 256, NUM_BLOCKS, HEAD_DIM, BLOCK),
    "whisper": (SLOTS, 12, 1, 32, NUM_BLOCKS, 64, BLOCK),
}
DECODE_CASES = [(m, kv) for m in DECODE_SHAPES for kv in ("bf16", "int8")]


@pytest.mark.parametrize(
    "model,kv_dtype", DECODE_CASES,
    ids=[kv if m == "granite" else f"{m}-{kv}" for m, kv in DECODE_CASES])
def test_paged_decode_compiles_for_v5e(one_chip, model, kv_dtype):
    slots, kv_heads, gqa, table_w, blocks, head_dim, block = \
        DECODE_SHAPES[model]
    q = _spec((slots, 1, kv_heads * gqa, head_dim), jnp.bfloat16, one_chip)
    bt = _spec((slots, table_w), jnp.int32, one_chip)
    idx = _spec((slots,), jnp.int32, one_chip)
    compiled = ops.paged_attention.lower(
        _pool(kv_dtype, one_chip, blocks, kv_heads, head_dim, block), q, bt,
        idx, interpret=False).compile()
    _assert_kernel(compiled)
    # the pool is read in place: no re-layout copy (whisper's 64-wide
    # heads are padded to a lane tile, a copy of the pool)
    if kv_dtype == "bf16" and head_dim % 128 == 0:
        assert " copy(" not in compiled.as_text()


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


# mellum2-12b on one v5e, 8 layers beside 8192-token contexts: 25 slots
# over a 512-entry table of a 12,816-block pool, 4 kv heads x GQA 8; the
# sliding layers pass their 1024-position window, the full layers none
@pytest.mark.parametrize("window", [1024, None], ids=["sliding", "full"])
def test_mellum_paged_decode_compiles_for_v5e(one_chip, window):
    q = _spec((25, 1, 32, HEAD_DIM), jnp.bfloat16, one_chip)
    bt = _spec((25, 512), jnp.int32, one_chip)
    idx = _spec((25,), jnp.int32, one_chip)
    compiled = ops.paged_attention.lower(
        _pool("bf16", one_chip, 12816, 4), q, bt, idx, window=window,
        interpret=False).compile()
    _assert_kernel(compiled)
    assert " copy(" not in compiled.as_text()


@pytest.mark.parametrize("tokens", [25, 2048], ids=["decode", "chunk"])
def test_dropless_expert_layer_compiles_for_v5e(one_chip, tokens):
    """mellum2-12b's MoE layer at published widths (64 experts of 896, top
    8) over a decode step's 25 rows and a 4 x 512 prefill chunk.  The
    grouped matmuls reach the device trace as ``ragged-dot-none`` (seen so
    in a v5e trace of the 8-layer model), the name a reader of their time
    matches."""
    from repro.configs import get_config
    from repro.models.moe import moe_block, moe_decl
    from repro.models.params import abstract_params

    cfg = get_config("mellum2-12b")
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          abstract_params(moe_decl(cfg)))
    x = _spec((1, tokens, cfg.d_model), jnp.bfloat16, one_chip)
    text = jax.jit(lambda p, v: moe_block(p, v, cfg)[0]).lower(
        params, x).compile().as_text()
    where = _computations(text)
    ragged = [n for n in where if n.startswith("ragged-dot-none")]
    assert len(ragged) == 3
    # each is an op of its own, not an instruction inside a fusion: the
    # device trace names top-level ops only
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    assert not {where[n] for n in ragged} & fused


def _computations(text: str) -> dict[str, str]:
    """{instruction name: the computation that holds it} of HLO text."""
    out, comp = {}, None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and not line.startswith(" "):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        elif " = " in line and comp is not None:
            out[line.split(" = ")[0].split()[-1].lstrip("%")] = comp
    return out
