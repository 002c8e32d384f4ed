"""Shared decode-cache write/position helpers.

The per-slot position arithmetic and the one-hot / flat-scatter cache
writes used by single-token decode were previously duplicated across the
attention paths (and re-derived by the encoder-decoder stack through
them).  They live here once, with sharding constraints threaded through:
every helper constrains its outputs by *logical* axis names, so the same
code is correct single-device (constrain is a no-op without rules) and
under the serve mesh (pooled K/V sharded on kv-heads / head_dim).

Position convention (both full and ring caches): slot ``s`` of a
capacity-``C`` cache holds absolute position ``p`` with ``p % C == s``,
taking the greatest such ``p`` at or below the decode index.

Every write into the paged pool runs under ``jax.named_scope("kv_write")``,
so its device ops carry ``kv_write`` in their op-name path in a profiler
trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.sharding.partition import constrain

# Logical axes of ONE layer's pooled KV leaf [num_blocks, block_size, Kh, D].
PAGED_POOL_AXES = (None, None, "cache_kv", "cache_hd")
# Logical axes of ONE layer's pooled scale leaf [num_blocks, block_size, Kh]
# (quantized pools only; sharded on kv-heads alongside the data leaves).
PAGED_SCALE_AXES = (None, None, "cache_kv")
# Logical axes of ONE layer's contiguous KV leaf [B, C, Kh, D].
SLOT_CACHE_AXES = ("cache_batch", "cache_seq", "cache_kv", "cache_hd")


def ring_slot(index, capacity: int, window: int | None):
    """Cache slot for absolute position ``index`` (scalar or [B])."""
    return index % capacity if window is not None else index


def slot_positions(index, capacity: int, window: int | None):
    """(kv_pos, kv_valid) for a capacity-``C`` slot cache at decode index.

    index scalar -> [C] vectors; index [B] -> [B, C] (continuous batching:
    every slot at its own depth).  For ring caches the position stored in
    slot ``s`` is the greatest ``p <= index`` with ``p % C == s``; for full
    caches slot ``s`` simply holds position ``s``.
    """
    slots = jnp.arange(capacity, dtype=jnp.int32)
    if index.ndim == 0:
        if window is not None:
            kv_pos = index - ((index - slots) % capacity)
            return kv_pos, kv_pos >= 0
        return slots, slots <= index
    if window is not None:
        kv_pos = index[:, None] - ((index[:, None] - slots[None, :]) % capacity)
        return kv_pos, kv_pos >= 0
    kv_pos = jnp.broadcast_to(slots[None, :], (index.shape[0], capacity))
    return kv_pos, slots[None, :] <= index[:, None]


def slot_cache_write(kc, vc, k_new, v_new, index, window: int | None):
    """Write one token per batch row into a contiguous [B, C, Kh, D] cache.

    Scalar ``index`` (lockstep batch) uses a dynamic-slice update; vector
    ``index`` [B] (continuous batching) lowers to a per-example one-hot
    select, which keeps the write batchable without scatter.
    """
    C = kc.shape[1]
    slot = ring_slot(index, C, window)
    if index.ndim == 0:
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k_new.astype(kc.dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v_new.astype(vc.dtype), slot, axis=1)
    else:
        slots = jnp.arange(C, dtype=jnp.int32)
        hit = slots[None, :] == slot[:, None]  # [B, C] one-hot write mask
        kc = jnp.where(hit[..., None, None], k_new.astype(kc.dtype), kc)
        vc = jnp.where(hit[..., None, None], v_new.astype(vc.dtype), vc)
    kc = constrain(kc, SLOT_CACHE_AXES)
    vc = constrain(vc, SLOT_CACHE_AXES)
    return kc, vc


@jax.named_scope("kv_write")
def paged_span_write(kp, vp, k_new, v_new, block_tables, row_start, row_len):
    """Write a per-row query span into the pooled [NB, bs, Kh, D] layout.

    k_new/v_new: [B, Q, Kh, D] — row ``b`` holds ``row_len[b]`` valid tokens
    at absolute positions ``row_start[b] + j``; padding columns
    (``j >= row_len``) and positions past the table's last entry are routed
    into the NULL block so a fixed-shape chunk/draft batch never scribbles
    on live blocks (an out-of-range clamp would alias the write into the
    slot's LAST block, corrupting committed K/V).  Valid destinations are
    unique (disjoint block tables per row), so the flat scatter is
    deterministic everywhere a read can land.
    """
    nb, bs = kp.shape[0], kp.shape[1]
    b, q = k_new.shape[0], k_new.shape[1]
    # padding lands in the NULL block's [0, bs) range (garbage nobody reads)
    dest = _span_dest(block_tables, row_start, row_len, q, bs)
    kf = kp.reshape((nb * bs,) + kp.shape[2:])
    vf = vp.reshape((nb * bs,) + vp.shape[2:])
    kf = kf.at[dest].set(k_new.reshape((b * q,) + k_new.shape[2:]).astype(kf.dtype))
    vf = vf.at[dest].set(v_new.reshape((b * q,) + v_new.shape[2:]).astype(vf.dtype))
    kp = constrain(kf.reshape(kp.shape), PAGED_POOL_AXES)
    vp = constrain(vf.reshape(vp.shape), PAGED_POOL_AXES)
    return kp, vp


def microbatch_bounds(n: int, parts: int) -> list[int]:
    """Contiguous row-group boundaries for the micro-batched span pipeline:
    ``parts + 1`` monotone cut points over ``[0, n]`` with near-equal group
    sizes.  Splitting a span batch this way is safe because every row's
    cache-write destinations are disjoint (per-row block tables, see
    :func:`paged_span_write`) and rows never read each other's pool blocks —
    so the groups may execute back to back with the caches threaded through,
    bit-identical to the single-batch span."""
    parts = max(1, min(int(parts), max(int(n), 1)))
    return [i * int(n) // parts for i in range(parts + 1)]


def _span_dest(block_tables, row_start, row_len, q, bs):
    """Flat pool destinations for a per-row query span (see paged_span_write)."""
    j = jnp.arange(q, dtype=jnp.int32)[None, :]  # [1, Q]
    pos = row_start[:, None] + j  # [B, Q] absolute positions
    w_raw = pos // bs
    valid = (j < row_len[:, None]) & (w_raw < block_tables.shape[1])
    w = jnp.clip(w_raw, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, w, axis=1)  # [B, Q]
    return jnp.where(valid, blk * bs + pos % bs, pos % bs).reshape(-1)


@jax.named_scope("kv_write")
def copy_pool_blocks(leaf, src, dst):
    """Copy whole pool blocks ``src[i] -> dst[i]`` within one pooled leaf.

    Leaves here are the engine's layers-STACKED pool entries —
    ``[layers, NB, bs, ...]`` for data and scale alike — so the block axis
    is axis 1, not axis 0.  This is the device half of copy-on-write
    forking (serve/block_pool.py ``cow``): the host moves a writer's
    reference to a fresh block, and this replicates the shared block's
    contents there before the write dispatches, so the copy is bit-exact
    and the other holders never observe the divergence.  Call sites pad
    the pair list with NULL -> NULL self-copies to keep the jit cache
    small; block 0 is garbage by contract, so the padding is inert.
    """
    out = leaf.at[:, dst].set(leaf[:, src])
    axes = (None,) + (PAGED_POOL_AXES if leaf.ndim == 5 else PAGED_SCALE_AXES)
    return constrain(out, axes)


def _scatter_pool(leaf, new_flat, dest, axes):
    flat = leaf.reshape((leaf.shape[0] * leaf.shape[1],) + leaf.shape[2:])
    flat = flat.at[dest].set(new_flat.astype(leaf.dtype))
    return constrain(flat.reshape(leaf.shape), axes)


@jax.named_scope("kv_write")
def quantized_span_write(cache, k_new, v_new, block_tables, row_start, row_len,
                         kv_dtype: str):
    """paged_span_write for a quantized pool: quantize-on-write.

    ``cache`` holds the per-layer quantized entry — data leaves ``k``/``v``
    [NB, bs, Kh, D] in storage dtype plus scale leaves ``k_scale``/
    ``v_scale`` [NB, bs, Kh] f32.  Each incoming token row is quantized
    per-(position, kv-head) and its q-values and scales land at the same
    flat destination, so a read always sees a matching (q, scale) pair.
    """
    bs = cache["k"].shape[1]
    b, q = k_new.shape[0], k_new.shape[1]
    dest = _span_dest(block_tables, row_start, row_len, q, bs)
    out = dict(cache)
    for name, new in (("k", k_new), ("v", v_new)):
        qv, sc = quant.kv_quantize(new, kv_dtype)
        out[name] = _scatter_pool(
            cache[name], qv.reshape((b * q,) + qv.shape[2:]), dest,
            PAGED_POOL_AXES)
        out[name + "_scale"] = _scatter_pool(
            cache[name + "_scale"], sc.reshape((b * q,) + sc.shape[2:]), dest,
            PAGED_SCALE_AXES)
    return out


@jax.named_scope("kv_write")
def quantized_cache_write(cache, k_new, v_new, block_tables, index,
                          kv_dtype: str):
    """paged_cache_write for a quantized pool (one token per slot)."""
    bs = cache["k"].shape[1]
    blk = jnp.take_along_axis(block_tables, (index // bs)[:, None], axis=1)[:, 0]
    dest = blk * bs + index % bs  # [B] flat positions
    out = dict(cache)
    for name, new in (("k", k_new), ("v", v_new)):
        qv, sc = quant.kv_quantize(new[:, 0], kv_dtype)
        out[name] = _scatter_pool(cache[name], qv, dest, PAGED_POOL_AXES)
        out[name + "_scale"] = _scatter_pool(
            cache[name + "_scale"], sc, dest, PAGED_SCALE_AXES)
    return out


@jax.named_scope("kv_write")
def paged_cache_write(kp, vp, k_new, v_new, block_tables, index):
    """Write one token per slot into the pooled [NB, bs, Kh, D] layout.

    The destination is ``table[b, index // bs] * bs + index % bs`` — a flat
    scatter over the (blocks * block_size) dim, unique per live slot
    (retired slots point at the NULL block, absorbing frozen writes).
    """
    nb, bs = kp.shape[0], kp.shape[1]
    blk = jnp.take_along_axis(block_tables, (index // bs)[:, None], axis=1)[:, 0]
    dest = blk * bs + index % bs  # [B] flat positions
    kf = kp.reshape((nb * bs,) + kp.shape[2:])
    vf = vp.reshape((nb * bs,) + vp.shape[2:])
    kf = kf.at[dest].set(k_new[:, 0].astype(kf.dtype))
    vf = vf.at[dest].set(v_new[:, 0].astype(vf.dtype))
    kp = constrain(kf.reshape(kp.shape), PAGED_POOL_AXES)
    vp = constrain(vf.reshape(vp.shape), PAGED_POOL_AXES)
    return kp, vp
