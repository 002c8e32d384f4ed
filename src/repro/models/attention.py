"""Grouped-query attention: full / causal / sliding-window, train + decode.

Three execution paths:
  * naive SDPA      — materializes [.., Sq, Skv] scores (small seqs, oracle)
  * blocked SDPA    — online-softmax over KV blocks (``cfg.attn_block_kv``):
                      flash-style memory footprint in pure jnp, used for the
                      32k shapes; optional compile-time causal block skipping
  * Pallas kernels  — ``repro.kernels.attention`` (the default hot path on
                      TPU under ``cfg.kernel_mode="auto"``; every call site
                      routes through ``dispatch.resolve`` and degrades to
                      the jnp paths above when shape/dtype/platform say no)

Decode maintains either a full KV cache (one slot per absolute position) or a
ring buffer of ``window`` slots for sliding-window attention; ring-slot
positions are reconstructed arithmetically from the decode index, so no
position side-table is needed.

Convention: train/prefill ``positions`` are **numpy** arrays (static ->
enables compile-time block culling and constant rope tables); decode
positions are traced scalars derived from the cache index.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels.attention import dispatch as kdispatch
from repro.models import cache_utils
from repro.models.cache_utils import PAGED_POOL_AXES, PAGED_SCALE_AXES
from repro.models.layers import accum_dtype, dense, dense_decl, rope
from repro.models.params import ParamDecl
from repro.sharding.partition import constrain, current_rules

NEG_INF = -2.0e38


def attn_decl(cfg, *, kv_dim: int | None = None) -> dict:
    d = cfg.d_model
    hd = cfg.head_dim
    kd = kv_dim or d
    return {
        "wq": dense_decl(d, (cfg.num_heads, hd), "embed", ("q_heads", "head_dim"), bias=cfg.qkv_bias),
        "wk": dense_decl(kd, (cfg.num_kv_heads, hd), "embed", ("kv_heads", "kv_head_dim"), bias=cfg.qkv_bias),
        "wv": dense_decl(kd, (cfg.num_kv_heads, hd), "embed", ("kv_heads", "kv_head_dim"), bias=cfg.qkv_bias),
        "wo": {
            "w": ParamDecl((cfg.num_heads, hd, d), ("q_heads", "head_dim", "embed"), init="normal")
        },
    }


def _out_proj(params, o, accum=jnp.float32):
    w = params["wo"]["w"]
    y = jax.lax.dot_general(
        o, w.astype(o.dtype), (((o.ndim - 2, o.ndim - 1), (0, 1)), ((), ())),
        preferred_element_type=accum,
    )
    return y.astype(o.dtype)


def _mask(q_pos, kv_pos, *, causal: bool, window: int | None, kv_valid=None):
    """Boolean mask from position vectors: [Sq, Skv] for shared positions
    ([Sq]/[Skv] inputs) or [B, Sq, Skv] for per-example positions
    ([B, Sq]/[B, Skv] inputs — the batched-index decode path)."""
    qp = jnp.asarray(q_pos)[..., :, None]
    kp = jnp.asarray(kv_pos)[..., None, :]
    m = (kp <= qp) if causal else jnp.broadcast_to(
        jnp.ones((), bool), jnp.broadcast_shapes(qp.shape, kp.shape))
    if window is not None:
        m &= kp > qp - window
    if kv_valid is not None:
        m &= jnp.asarray(kv_valid)[..., None, :]
    return m


def _sdpa_naive(q, k, v, mask, scale):
    """q: [B,Sq,Kh,G,D]; k/v: [B,Skv,Kh,D]; mask: [Sq,Skv] or [B,Sq,Skv]."""
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    m = mask if mask.ndim == 3 else mask[None]  # -> [B|1, Sq, Skv]
    scores = jnp.where(m[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    return o


def _sdpa_blocked(q, k, v, *, q_pos, kv_pos, causal, window, kv_valid, scale,
                  block_kv: int, skip_blocks: bool, block_q: int | None = None):
    """Online-softmax tiled over BOTH q and KV blocks (flash-style memory:
    O(block_q * block_kv) live scores instead of O(Sq * Skv)).

    With static (numpy) positions the q/kv block loops are python loops and
    fully-masked (q-block, kv-block) pairs are culled at compile time —
    causality halves the pair count, SWA reduces it to a band.
    """
    B, Sq, Kh, G, D = q.shape
    Skv = k.shape[1]
    static_pos = isinstance(q_pos, np.ndarray) and isinstance(kv_pos, np.ndarray)
    bq = min(block_q or block_kv, Sq)
    nqb = -(-Sq // bq)
    pad_q = nqb * bq - Sq
    nkb = -(-Skv // block_kv)
    pad_k = nkb * block_kv - Skv

    if kv_valid is None:
        kv_valid = np.ones((Skv,), bool) if static_pos else jnp.ones((Skv,), bool)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        mod = np if static_pos else jnp
        kv_pos = mod.pad(mod.asarray(kv_pos), (0, pad_k), constant_values=2**30)
        kv_valid = mod.pad(mod.asarray(kv_valid), (0, pad_k))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        mod = np if static_pos else jnp
        q_pos = mod.pad(mod.asarray(q_pos), (0, pad_q), constant_values=2**30)
    qf = q.astype(jnp.float32)
    kv_pos_j = jnp.asarray(kv_pos)
    kv_valid_j = jnp.asarray(kv_valid)

    def pair(q_blk, q_pos_blk, m, l, acc, kb_idx):
        kb = jax.lax.dynamic_slice_in_dim(k, kb_idx * block_kv, block_kv, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, kb_idx * block_kv, block_kv, axis=1)
        pb = jax.lax.dynamic_slice(kv_pos_j, (kb_idx * block_kv,), (block_kv,))
        valb = jax.lax.dynamic_slice(kv_valid_j, (kb_idx * block_kv,), (block_kv,))
        s = jnp.einsum("bqkgd,bskd->bqkgs", q_blk, kb.astype(jnp.float32)) * scale
        msk = _mask(q_pos_blk, pb, causal=causal, window=window, kv_valid=valb)
        s = jnp.where(msk[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p, vb.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    kv_pos_np = np.asarray(kv_pos) if static_pos else None
    q_pos_np = np.asarray(q_pos) if static_pos else None
    out_blocks = []
    for qi in range(nqb):
        q_blk = qf[:, qi * bq:(qi + 1) * bq]
        q_pos_blk = (
            q_pos_np[qi * bq:(qi + 1) * bq] if static_pos
            else jax.lax.dynamic_slice(jnp.asarray(q_pos), (qi * bq,), (bq,))
        )
        m = jnp.full((B, bq, Kh, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, bq, Kh, G), jnp.float32)
        acc = jnp.zeros((B, bq, Kh, G, D), jnp.float32)
        if skip_blocks and static_pos:
            real_q = q_pos_blk[q_pos_blk < 2**30]
            q_lo = int(real_q.min()) if real_q.size else 0
            q_hi = int(real_q.max()) if real_q.size else 2**30
            for kb_idx in range(nkb):
                blk_pos = kv_pos_np[kb_idx * block_kv:(kb_idx + 1) * block_kv]
                real = blk_pos[blk_pos < 2**30]
                if real.size == 0:
                    continue
                if causal and int(real.min()) > q_hi:
                    continue  # future block for every query in this q block
                if window is not None and int(real.max()) <= q_lo - window:
                    continue  # outside the sliding window for every query
                m, l, acc = pair(q_blk, q_pos_blk, m, l, acc, kb_idx)
        else:
            def body(carry, kb_idx):
                return pair(q_blk, q_pos_blk, *carry, kb_idx), None

            (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), jnp.arange(nkb))
        out_blocks.append(acc / jnp.maximum(l[..., None], 1e-30))
    out = jnp.concatenate(out_blocks, axis=1)[:, :Sq]
    return out.astype(v.dtype)


def multi_head_attention(
    q, k, v, *, q_pos, kv_pos, causal=True, window=None, kv_valid=None,
    block_kv=0, skip_blocks=True, kernel_mode="xla",
):
    """q: [B,Sq,Hq,D]; k/v: [B,Skv,Hkv,D]; positions int32 [Sq]/[Skv].

    ``kernel_mode`` (auto|pallas|xla) routes eligible dense calls through
    the Pallas flash kernel via ``dispatch.resolve``; standalone callers
    default to the pure-jnp paths.
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Sq, Hkv, G, D)

    # the flash kernel needs multi-query spans, static contiguous positions,
    # and no per-key validity mask (padding is derived from Skv alone)
    if (kernel_mode != "xla" and Sq > 1 and kv_valid is None
            and isinstance(q_pos, np.ndarray)):
        decision = kdispatch.resolve(
            kernel_mode, "dense", head_dim=D, kv_heads=Hkv,
            dtype=str(q.dtype), window=window,
        )
        if decision.backend == "pallas":
            from repro.kernels.attention import ops as att_ops

            return att_ops.flash_attention(
                q, k, v, causal=causal, window=window,
                q_offset=int(q_pos[0]) if q_pos.size else 0,
                **decision.params,
            )

    if block_kv and Sq > 1 and k.shape[1] > block_kv:
        o = _sdpa_blocked(
            qg, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window,
            kv_valid=kv_valid, scale=scale, block_kv=block_kv, skip_blocks=skip_blocks,
        )
    else:
        mask = _mask(q_pos, kv_pos, causal=causal, window=window, kv_valid=kv_valid)
        o = _sdpa_naive(qg, k, v, mask, scale)
    return o.reshape(B, Sq, Hq, D)


# ----------------------------------------------------------------------
# Full attention block (projections + rope + cache management)
# ----------------------------------------------------------------------


def init_cache_spec(cfg, batch: int, max_len: int, dtype, window=None):
    """Abstract KV-cache entry for ONE layer (leading layer dim added by the
    caller via stacking); a ``window`` makes it a ring of that many slots."""
    c = min(max_len, window) if window else max_len
    kv = (batch, c, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(kv, dtype),
        "v": jax.ShapeDtypeStruct(kv, dtype),
    }


def paged_cache_spec(cfg, num_blocks: int, block_size: int, dtype):
    """Pooled KV storage for ONE layer: ``[num_blocks, block_size, Kh, D]``.
    No batch dim — requests reference blocks through per-slot block tables,
    and SWA archs store absolute positions (window enforced by masking, not
    a ring), so one layout serves full and sliding-window attention.

    Quantized pools (``cfg.kv_dtype`` int8/fp8) store the data leaves in the
    storage dtype and carry per-(position, kv-head) f32 scales as sibling
    ``k_scale``/``v_scale`` leaves ``[num_blocks, block_size, Kh]`` — same
    block/position layout, so block tables, prefix hits, preemption and the
    engine's scatter/gather treat them like any other pool leaf."""
    kv = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_dtype != "fp16":
        sd = quant.storage_dtype(cfg.kv_dtype)
        sc = (num_blocks, block_size, cfg.num_kv_heads)
        return {
            "k": jax.ShapeDtypeStruct(kv, sd),
            "v": jax.ShapeDtypeStruct(kv, sd),
            "k_scale": jax.ShapeDtypeStruct(sc, jnp.float32),
            "v_scale": jax.ShapeDtypeStruct(sc, jnp.float32),
        }
    return {
        "k": jax.ShapeDtypeStruct(kv, dtype),
        "v": jax.ShapeDtypeStruct(kv, dtype),
    }


CACHE_AXES = {
    "k": cache_utils.SLOT_CACHE_AXES,
    "v": cache_utils.SLOT_CACHE_AXES,
}

# Logical axes of the pooled layout [num_blocks, block_size, Kh, D]:
# kv-head (or, last resort, head_dim) sharding over the serve mesh.
PAGED_CACHE_AXES = {"k": PAGED_POOL_AXES, "v": PAGED_POOL_AXES}

PAGED_LEAF_MASK = {"k": True, "v": True}


def paged_cache_axes(cfg) -> dict:
    """Logical axes per pool leaf, kv_dtype-aware (scale leaves have no
    head_dim axis but shard on kv-heads alongside the data leaves)."""
    axes = dict(PAGED_CACHE_AXES)
    if cfg.kv_dtype != "fp16":
        axes["k_scale"] = PAGED_SCALE_AXES
        axes["v_scale"] = PAGED_SCALE_AXES
    return axes


def paged_leaf_mask(cfg) -> dict:
    """Which per-layer cache-entry leaves live in the paged pool."""
    return {name: True for name in paged_cache_axes(cfg)}


def attention_block(
    params, x, cfg, *, positions, cache=None, index=None,
    window=None, causal=True, use_rope=True, kv_x=None, kv_valid=None,
    cross=False, cache_len=None, block_tables=None, ring=True, row_len=None,
    yarn=None,
):
    """Returns (y, new_cache).  ``yarn`` (a ``configs.base.Yarn``) switches
    the rotary embedding to YaRN's frequencies and scale.

    * train/prefill: ``cache is None`` -> self-attention over x; a fresh cache
      holding the (window-truncated, ring-arranged) K/V is returned.
      ``ring=False`` (paged prefill) keeps FULL-length K/V even under SWA —
      the paged pool stores absolute positions and masks the window instead.
    * decode: ``cache`` given, ``index`` is the absolute position of the new
      token; Sq == 1.  With ``block_tables`` ([B, W] int32) the cache is the
      pooled ``[num_blocks, block_size, Kh, D]`` layout and reads/writes go
      through the table (:func:`_paged_decode_attend`).
    * per-row query spans (``block_tables`` given AND ``row_len`` [B] given):
      row ``b`` of x holds ``row_len[b]`` valid tokens at absolute positions
      ``index[b] + j`` — one decode token (``row_len == 1``) or a prefill
      chunk; K/V are scattered into the pool first, then every query attends
      its own block table causally at absolute positions
      (:func:`_paged_span_attend` — subsumes both the single-token paged
      decode and the gather-concat chunk path for the unified serve step).
    * chunked prefill (``cache`` given, ``index is None``): x is the TAIL of
      a prompt whose first ``P`` positions are already cached (prefix-cache
      hit); attends over prefix+tail, returns tail K/V only.
    * cross-attention (``cross=True``): ``kv_x`` is the encoder output (its
      K/V are cached once at prefill; decode reads the cache position-free).
    """
    q = dense(params["wq"], x)  # [B,Sq,Hq,hd]

    if cross and cache is not None:
        # cross-attention decode: read-only cache, no new K/V projection
        kc, vc = cache["k"], cache["v"]
        kv_pos = jnp.arange(kc.shape[1], dtype=jnp.int32)
        o = multi_head_attention(
            q, kc, vc,
            q_pos=jnp.zeros((q.shape[1],), jnp.int32), kv_pos=kv_pos,
            causal=False, window=None, kv_valid=kv_valid,
        )
        y = _out_proj(params, o, accum_dtype(cfg))
        return constrain(y, ("act_batch", "act_seq", "act_embed")), cache

    src = kv_x if kv_x is not None else x
    k = dense(params["wk"], src)
    v = dense(params["wv"], src)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = constrain(k, ("act_batch", "act_seq", "act_kv", None))
    v = constrain(v, ("act_batch", "act_seq", "act_kv", None))

    if use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta, yarn)
        k = rope(k, positions, cfg.rope_theta, yarn)

    if cache is None:
        if kv_x is None:
            kv_pos = positions
            is_causal = causal
        else:
            kv_pos = np.arange(k.shape[1], dtype=np.int32)
            is_causal = False
        o = multi_head_attention(
            q, k, v, q_pos=positions, kv_pos=kv_pos,
            causal=is_causal, window=window, kv_valid=kv_valid,
            block_kv=cfg.attn_block_kv, kernel_mode=kdispatch.mode_from(cfg),
        )
        new_cache = _build_cache(k, v, window if ring else None, cache_len)
        if not ring and cfg.kv_dtype != "fp16":
            # paged prefill: the entry is headed for a quantized pool —
            # quantize per-(position, kv-head) AFTER padding (all-zero pad
            # rows deterministically become q=0 / scale=1e-12), so the
            # engine's generic scatter moves storage-dtype leaves verbatim.
            # Attention itself ran at full precision over the prompt.
            new_cache = _quantize_entry(new_cache, cfg.kv_dtype)
    elif index is None:
        o, new_cache = _chunk_attend(q, k, v, cache, positions, window, cfg)
    elif block_tables is not None and row_len is not None:
        o, new_cache = _paged_span_attend(q, k, v, cache, index, row_len,
                                          positions, block_tables, window, cfg)
    elif block_tables is not None:
        o, new_cache = _paged_decode_attend(q, k, v, cache, index,
                                            block_tables, window, cfg)
    else:
        o, new_cache = _decode_attend(q, k, v, cache, index, window)
    y = _out_proj(params, o, accum_dtype(cfg))
    y = constrain(y, ("act_batch", "act_seq", "act_embed"))
    return y, new_cache


def _build_cache(k, v, window, cache_len=None):
    """Prefill -> cache with target capacity C = min(cache_len, window).

    Slot invariant (both full and ring caches): slot s holds position p with
    p % C == s, taking the greatest such p already seen.  Positions below
    S <= C land at slot p directly; truncation keeps the last C positions via
    a roll so the invariant survives decode-time wraparound.
    """
    S = k.shape[1]
    c = cache_len if cache_len is not None else S
    if window is not None:
        c = min(c, window)
    if S < c:
        pad = ((0, 0), (0, c - S), (0, 0), (0, 0))
        return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    if S == c:
        return {"k": k, "v": v}
    if window is None:
        raise ValueError(f"cannot truncate full-attention cache {S} -> {c}")
    k_t, v_t = k[:, S - c:], v[:, S - c:]
    shift = (S - c) % c
    k_t = jnp.roll(k_t, shift, axis=1)
    v_t = jnp.roll(v_t, shift, axis=1)
    return {"k": k_t, "v": v_t}


def _quantize_entry(entry, kv_dtype: str):
    """{"k","v"} [B, S, Kh, D] -> quantized entry with scale leaves."""
    out = {}
    for name in ("k", "v"):
        qv, sc = quant.kv_quantize(entry[name], kv_dtype)
        out[name] = qv
        out[name + "_scale"] = sc
    return out


def _dequantize_entry(entry, dtype):
    """Inverse of :func:`_quantize_entry` (identity for native entries)."""
    if "k_scale" not in entry:
        return entry
    return {name: quant.kv_dequantize(entry[name], entry[name + "_scale"], dtype)
            for name in ("k", "v")}


def _decode_attend(q, k_new, v_new, cache, index, window):
    """Single-token decode against a full or ring cache.

    index: int32 absolute position of the incoming token — a scalar (whole
    batch in lockstep) or a [B] vector (continuous batching: every slot at
    its own depth; cache writes become per-example one-hot selects and the
    position masks gain a batch dim).
    """
    kc, vc = cache["k"], cache["v"]
    C = kc.shape[1]
    index = jnp.asarray(index, jnp.int32)
    kc, vc = cache_utils.slot_cache_write(kc, vc, k_new, v_new, index, window)
    kv_pos, kv_valid = cache_utils.slot_positions(index, C, window)
    if index.ndim == 0:
        q_pos = jnp.full((q.shape[1],), index, jnp.int32)
    else:
        q_pos = index[:, None]  # [B, Sq=1]
    o = multi_head_attention(
        q, kc, vc, q_pos=q_pos, kv_pos=kv_pos, causal=True,
        window=window, kv_valid=kv_valid, block_kv=0,
    )
    return o, {"k": kc, "v": vc}


def _chunk_attend(q, k_new, v_new, prefix, positions, window, cfg):
    """Tail prefill against a resident prefix (prefix-cache hit).

    prefix: {"k","v"} of shape [B, P, Kh, D] — the gathered prefix blocks
    (quantized pools also carry gathered "k_scale"/"v_scale" [B, P, Kh]:
    the prefix is dequantized for the attention math and the returned tail
    is re-quantized so the engine scatters storage-dtype leaves).
    positions: static numpy [S] = P + arange(S) (absolute tail positions).
    Attends q over prefix ++ tail with the standard causal/window masks and
    returns ONLY the tail K/V (the engine scatters them into fresh blocks;
    the prefix blocks are shared and must never be rewritten).
    """
    quantized = "k_scale" in prefix
    pfx = _dequantize_entry(prefix, k_new.dtype)
    P = pfx["k"].shape[1]
    kc = jnp.concatenate([pfx["k"].astype(k_new.dtype), k_new], axis=1)
    vc = jnp.concatenate([pfx["v"].astype(v_new.dtype), v_new], axis=1)
    kc = constrain(kc, ("act_batch", None, "act_kv", None))
    vc = constrain(vc, ("act_batch", None, "act_kv", None))
    kv_pos = np.arange(P + k_new.shape[1], dtype=np.int32)
    o = multi_head_attention(
        q, kc, vc, q_pos=positions, kv_pos=kv_pos, causal=True,
        window=window, block_kv=cfg.attn_block_kv,
        kernel_mode=kdispatch.mode_from(cfg),
    )
    tail = {"k": k_new, "v": v_new}
    if quantized:
        tail = _quantize_entry(tail, cfg.kv_dtype)
    return o, tail


def _paged_decode_attend(q, k_new, v_new, cache, index, block_tables, window, cfg):
    """Single-token decode against the pooled block cache.

    cache: {"k","v"} [num_blocks, block_size, Kh, D] (no batch dim);
    block_tables: [B, W] int32 (entry w maps positions [w*bs, (w+1)*bs));
    index: [B] int32 absolute position of the incoming token.

    Positions are ABSOLUTE (block w holds positions w*bs..), so full and
    sliding-window attention share the layout — SWA is a mask, not a ring.
    The write lands in the slot's uniquely-owned tail block (prefix-shared
    blocks are read-only by construction: the first decode position is
    always past the last shared block).  Retired slots point at the NULL
    block 0, so their frozen writes scribble garbage nobody reads.
    """
    bs = cache["k"].shape[1]
    B, W = block_tables.shape
    index = jnp.asarray(index, jnp.int32)
    quantized = "k_scale" in cache

    # ---- write: one token per slot at table[b, index//bs], offset index%bs
    # (quantized pools quantize-on-write: q-values and their scales land at
    # the same flat destination)
    if quantized:
        entry = cache_utils.quantized_cache_write(
            cache, k_new, v_new, block_tables, index, cfg.kv_dtype)
    else:
        kp, vp = cache_utils.paged_cache_write(cache["k"], cache["v"], k_new,
                                               v_new, block_tables, index)
        entry = {"k": kp, "v": vp}

    rules = current_rules()
    kv_shards = (rules.axis_size(rules.axis("cache_kv"))
                 if rules is not None else 1)
    hd_shards = (rules.axis_size(rules.axis("cache_hd"))
                 if rules is not None else 1)
    # head_dim sharding (the rules' last resort) contracts inside the
    # scores: it must use the gather path (GSPMD partitions the dots), not
    # the head-parallel kernel — a plain pallas_call over a D-sharded pool
    # would hand XLA an unpartitionable custom call
    decision = kdispatch.resolve(
        kdispatch.mode_from(cfg), "paged_decode", head_dim=entry["k"].shape[3],
        kv_heads=entry["k"].shape[2], dtype=str(q.dtype), window=window,
        block_size=bs, supported=hd_shards == 1,
        why=f"head_dim sharded {hd_shards}-way", kv_dtype=cfg.kv_dtype,
    )
    if decision.backend == "pallas":
        from repro.kernels.attention import ops as att_ops

        if kv_shards > 1:
            # per-shard head slice: each model-axis shard runs the kernel
            # over its own kv heads (and the aligned q-head group)
            o = att_ops.paged_attention_sharded(
                entry, q, block_tables, index, window=window, rules=rules)
        else:
            o = att_ops.paged_attention(entry, q, block_tables, index,
                                        window=window)
    else:
        # ---- read: gather the slot's blocks into its logical [W*bs] view
        # (quantized: gather q-values + scales, dequant the gathered view)
        kg, vg = _gathered_view(entry, block_tables, q.dtype)
        kv_pos = jnp.broadcast_to(
            jnp.arange(W * bs, dtype=jnp.int32)[None], (B, W * bs))
        kv_valid = kv_pos <= index[:, None]
        q_pos = index[:, None]  # [B, Sq=1]
        o = multi_head_attention(
            q, kg, vg, q_pos=q_pos, kv_pos=kv_pos, causal=True,
            window=window, kv_valid=kv_valid, block_kv=0,
        )
    return o, entry


def _gathered_view(entry, block_tables, dtype):
    """Gather a pool entry into per-slot logical [B, W*bs, Kh, D] K/V views,
    dequantizing through the gathered scales when the entry is quantized."""
    b, w = block_tables.shape
    bs = entry["k"].shape[1]
    out = []
    for name in ("k", "v"):
        leaf = entry[name]
        g = leaf[block_tables].reshape(b, w * bs, *leaf.shape[2:])
        if name + "_scale" in entry:
            sleaf = entry[name + "_scale"]
            sc = sleaf[block_tables].reshape(b, w * bs, *sleaf.shape[2:])
            g = quant.kv_dequantize(g, sc, dtype)
        out.append(constrain(g, ("act_batch", None, "act_kv", "cache_hd")))
    return out


def _paged_span_attend(q, k_new, v_new, cache, row_start, row_len, positions,
                       block_tables, window, cfg):
    """Per-row query-span attention against the pooled block cache: the
    mixed-batch primitive of the unified serve step.

    q/k_new/v_new: [B, Q, ...]; row ``b`` carries ``row_len[b]`` valid
    tokens at absolute positions ``row_start[b] + j`` — a 1-token decode row
    and a Q-token prefill chunk are the same operation at different spans.
    The span's K/V are scattered into their blocks FIRST (padding columns
    land in the NULL block), then every query attends its row's gathered
    block table with plain causal/window masks at absolute positions —
    intra-chunk causality needs no special casing because chunk tokens sit
    at their final pool positions before the gather.  Positions covered by
    the causal mask are always row-owned writes (prefix + this span), so
    stale block contents beyond the span are never read with weight; padded
    queries (j >= row_len) produce garbage rows the caller discards.

    This write-then-mask discipline is also what makes speculative
    decoding's rejected drafts provably inert (docs/speculative.md): a
    rejected draft's K/V sits at an absolute position at or past the
    committed frontier, the next span starts AT that frontier and rewrites
    every position it can reach before attending (overwrite-on-next-span),
    and absolute-position masking — unlike a ring — can never alias the
    residue back into causal range.
    """
    bs = cache["k"].shape[1]
    b, w = block_tables.shape
    quantized = "k_scale" in cache
    if quantized:
        entry = cache_utils.quantized_span_write(
            cache, k_new, v_new, block_tables, row_start, row_len,
            cfg.kv_dtype)
    else:
        kp, vp = cache_utils.paged_span_write(cache["k"], cache["v"], k_new,
                                              v_new, block_tables, row_start,
                                              row_len)
        entry = {"k": kp, "v": vp}

    rules = current_rules()
    kv_shards = (rules.axis_size(rules.axis("cache_kv"))
                 if rules is not None else 1)
    hd_shards = (rules.axis_size(rules.axis("cache_hd"))
                 if rules is not None else 1)
    decision = kdispatch.resolve(
        kdispatch.mode_from(cfg), "paged_span", head_dim=entry["k"].shape[3],
        kv_heads=entry["k"].shape[2], dtype=str(q.dtype), window=window,
        block_size=bs, supported=hd_shards == 1,
        why=f"head_dim sharded {hd_shards}-way", kv_dtype=cfg.kv_dtype,
    )
    if decision.backend == "pallas":
        from repro.kernels.attention import ops as att_ops

        if kv_shards > 1:
            o = att_ops.paged_span_attention_sharded(
                entry, q, block_tables, row_start, row_len,
                window=window, rules=rules,
                block_q=decision.params.get("block_q"))
        else:
            o = att_ops.paged_span_attention(
                entry, q, block_tables, row_start, row_len,
                window=window, block_q=decision.params.get("block_q"))
    else:
        kg, vg = _gathered_view(entry, block_tables, q.dtype)
        kv_pos = jnp.broadcast_to(
            jnp.arange(w * bs, dtype=jnp.int32)[None], (b, w * bs))
        o = multi_head_attention(
            q, kg, vg, q_pos=positions, kv_pos=kv_pos, causal=True,
            window=window, block_kv=0,
        )
    return o, entry


def span_pipeline(span_fn, caches, row_args, *, micro_batches: int = 1):
    """Software-pipelined span step: split the row batch into contiguous
    micro-batches and run ``span_fn(caches, *rows)`` once per group, caches
    threaded A -> B.

    This is the device half of communication/compute overlap for
    tensor-parallel serving (``repro.sharding.overlap``): micro-batch B's
    layer-``l`` compute depends only on A's layer-``l`` cache *write* — not
    on A's attention math or projections — so under mp>1 A's post-attention
    and post-MLP all-reduces are free to drain while B computes.  Each group
    runs under ``overlap.stage(i)`` (a ``jax.named_scope``) so the compiled
    HLO carries the stage on every op and the trace loop can classify each
    collective as overlapped or blocking from the actual schedule.

    Bit-identity: groups are contiguous row slices (never reordered), row
    cache writes are disjoint (:func:`repro.models.cache_utils.paged_span_write`),
    and per-row logits do not depend on batch size, so concatenating the
    group logits reproduces the single-batch result exactly.  The
    ``optimization_barrier`` between stages only pins the stage boundary in
    the schedule; it is value-transparent.

    ``row_args`` is a tuple of per-row arrays (leading dim = rows), e.g.
    ``(tokens, row_start, row_len, block_tables)``.  Returns
    ``(caches, logits)`` with logits concatenated back to the full batch.
    """
    from repro.models.cache_utils import microbatch_bounds
    from repro.sharding import overlap as overlap_mod

    n = int(row_args[0].shape[0])
    bounds = microbatch_bounds(n, micro_batches)
    if len(bounds) <= 2:  # 1 group: the plain span step, no scopes
        return span_fn(caches, *row_args)
    outs = []
    for i in range(len(bounds) - 1):
        lo, hi = bounds[i], bounds[i + 1]
        group = tuple(a[lo:hi] for a in row_args)
        with overlap_mod.stage(i):
            caches, logits = span_fn(caches, *group)
        if i + 2 < len(bounds):
            # keep XLA from re-fusing the stages into one region (which
            # would erase the interleaving the named scopes describe)
            caches = jax.lax.optimization_barrier(caches)
        outs.append(logits)
    return caches, jnp.concatenate(outs, axis=0)
