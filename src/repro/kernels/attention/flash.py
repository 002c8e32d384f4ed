"""Pallas TPU flash-attention forward kernel (causal / sliding-window / GQA).

The dense-prefill member of the unified attention-kernel family
(``repro.kernels.attention``).  TPU adaptation notes (vs the CUDA
FlashAttention algorithm):
  * tiling targets VMEM and the 128x128 MXU: block sizes are multiples of
    128 on the (Sq, Skv) dims and the head_dim lives on the lane dimension;
  * the KV loop is a sequential grid dimension (Pallas TPU grids execute
    in order per core) with the running (m, l, acc) softmax state held in
    VMEM scratch across grid steps — no shared-memory/warp semantics;
  * GQA is folded into the BlockSpec index maps (q head h reads kv head
    h // q_per_kv), so no repeated-KV materialization in HBM;
  * fully-masked KV blocks (future blocks under causality, out-of-window
    blocks under SWA) are skipped with ``pl.when`` — the block still
    occupies a grid slot but does no MXU work;
  * ``block_q``/``block_k`` are the autotuned tiling parameters
    (``repro.kernels.attention.autotune``) — changing ``block_k`` changes
    the online-softmax accumulation order, so tuned runs are reproducible
    only through the persistent parameter cache.

Grid: (B, Hq, Sq/bq, Skv/bk), KV innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, q_offset: int,
    bq: int, bk: int, num_kv_blocks: int, sq_valid: int, skv_valid: int,
):
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + iq * bq
    k_lo = jk * bk
    # static-shape block culling (positions are affine in grid ids)
    not_future = jnp.logical_or(
        jnp.logical_not(causal), k_lo <= q_lo + bq - 1
    )
    in_window = (
        jnp.bool_(True) if window is None
        else (k_lo + bk - 1) > (q_lo - window)
    )
    in_bounds = k_lo < skv_valid

    @pl.when(jnp.logical_and(jnp.logical_and(not_future, in_window), in_bounds))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < skv_valid  # padding
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bk]
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(jk == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, window: int | None = None,
    q_offset: int = 0, block_q: int = 128, block_k: int = 128,
    interpret: bool = False,
):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D] (head-major layout).

    Sq/Skv are padded to block multiples by the caller (ops.py).
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "ops.py must pad to block multiples"
    nq, nk = sq // bq, skv // bk
    grid = (b, hq, nq, nk)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=1.0 / (d ** 0.5), causal=causal,
        window=window, q_offset=q_offset, bq=bq, bk=bk, num_kv_blocks=nk,
        sq_valid=sq, skv_valid=skv,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
