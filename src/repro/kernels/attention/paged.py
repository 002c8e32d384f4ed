"""Pallas TPU paged-attention kernels: single-token decode + ragged span.

The paged members of the unified attention-kernel family
(``repro.kernels.attention``).  Both read K/V directly from the paged
block pool through per-slot block tables — no gather materialization in
HBM.  The block table (and the per-row index/start/len scalars) ride in
SMEM via ``PrefetchScalarGridSpec``: the KV BlockSpec index map derefs
``bt[b, w]`` so the DMA engine fetches exactly the block each grid step
needs, including NULL-block padding slots whose contribution is masked
out (garbage never reaches the output).

Decode grid: (B, Hkv, W) — one query token per slot, online softmax over
the W table entries in VMEM scratch, NULL/future blocks skipped with
``pl.when``.

Span grid: (B, Hkv, Q*G/bq, W) — ragged multi-token rows (the unified
serve step's chunked-prefill + spec-verify batches) with the query dim
folded as q*G+g so GQA rows share the KV fetch.  ``block_q`` tiles the
folded query dim across a grid axis; it is the span kernel's autotuned
VMEM-tiling parameter (``repro.kernels.attention.autotune``).  Per-row
numerics are tile-invariant: each row sees the same KV-block sequence
and masks regardless of which tile it lands in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _paged_decode_kernel(
    bt_ref, idx_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, window: int | None, bs: int, num_w: int, quant: bool,
):
    # quantized pools append per-(position, head) scale rows after v: the
    # scales ride the same bt[b, w] DMA schedule as their block.  A row
    # [1, bs] keeps the block's last two dims equal to the array's (Mosaic
    # tiling rule), so dequant scales the scores (keys) and the softmax
    # weights (values) along lanes instead of the [bs, d] pages
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    idx = idx_ref[b]
    k_lo = w * bs
    not_future = k_lo <= idx
    in_window = (
        jnp.bool_(True) if window is None
        else (k_lo + bs - 1) > (idx - window)
    )

    @pl.when(jnp.logical_and(not_future, in_window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [bs, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, bs]
        if quant:
            s = s * ks_ref[0, 0]  # [1, bs] per-position key scales
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos <= idx
        if window is not None:
            mask &= k_pos > idx - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * vs_ref[0, 0]  # value scales fold into the weights
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(w == num_w - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_fwd(
    q, k_pages, v_pages, block_tables, index, *,
    k_scales=None, v_scales=None,
    window: int | None = None, interpret: bool = False,
):
    """q: [B, Hkv, G, D]; k/v_pages: [Hkv, NB, bs, D] (head-major pool);
    block_tables: [B, W] int32; index: [B] int32 (last valid position).
    k/v_scales (quantized pools): [Hkv, NB, 1, bs] f32 per-position scale
    rows, DMA'd block-aligned with their pages and applied in-kernel."""
    b, hkv, g, d = q.shape
    bs = k_pages.shape[2]
    num_w = block_tables.shape[1]
    grid = (b, hkv, num_w)
    quant = k_scales is not None

    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / (d ** 0.5), window=window,
        bs=bs, num_w=num_w, quant=quant,
    )
    page_spec = pl.BlockSpec((1, 1, bs, d),
                             lambda b_, h, w, bt, idx: (h, bt[b_, w], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b_, h, w, bt, idx: (b_, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, 1, bs),
                                  lambda b_, h, w, bt, idx: (h, bt[b_, w], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, h, w, bt, idx: (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, index, *operands)


def _paged_span_kernel(
    bt_ref, start_ref, len_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, window: int | None, bs: int, num_w: int, gq: int,
    bq: int, quant: bool,
):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    iq = pl.program_id(2)
    w = pl.program_id(3)

    @pl.when(w == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    last = start + len_ref[b] - 1  # last valid query position of the row
    k_lo = w * bs
    # row-level culling (not tile-level) so every query tile of a row sees
    # the same KV-block sequence — per-row numerics are bq-invariant
    not_future = k_lo <= last
    in_window = (
        jnp.bool_(True) if window is None
        else (k_lo + bs - 1) > (start - window)
    )

    @pl.when(jnp.logical_and(not_future, in_window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [bs, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bs]
        if quant:
            s = s * ks_ref[0, 0]  # [1, bs] per-position key scales
        # folded query row r of this tile is query (iq*bq + r) // gq of the row
        q_pos = start + (
            iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ) // gq
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * vs_ref[0, 0]  # value scales fold into the weights
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(w == num_w - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_span_fwd(
    q, k_pages, v_pages, block_tables, row_start, row_len, *,
    group: int, k_scales=None, v_scales=None,
    window: int | None = None, block_q: int | None = None,
    interpret: bool = False,
):
    """q: [B, Hkv, Q*G, D] (query-major fold: row q*G+g is query q, group g);
    k/v_pages: [Hkv, NB, bs, D]; block_tables: [B, W];
    row_start/row_len: [B] int32.  Rows beyond row_len are garbage by
    contract (the engine discards them).  k/v_scales (quantized pools):
    [Hkv, NB, 1, bs] f32, fetched alongside their pages and applied
    in-kernel.

    ``block_q`` tiles the folded Q*G dim over its own grid axis; the
    caller (ops.py) pads Q*G to a block multiple.  None keeps one tile.
    """
    b, hkv, qg, d = q.shape
    bs = k_pages.shape[2]
    num_w = block_tables.shape[1]
    bq = qg if block_q is None else min(block_q, qg)
    assert qg % bq == 0, "ops.py must pad the folded query dim to a block multiple"
    nq = qg // bq
    grid = (b, hkv, nq, num_w)
    quant = k_scales is not None

    kernel = functools.partial(
        _paged_span_kernel, scale=1.0 / (d ** 0.5), window=window,
        bs=bs, num_w=num_w, gq=group, bq=bq, quant=quant,
    )
    page_spec = pl.BlockSpec((1, 1, bs, d),
                             lambda b_, h, i, w, bt, st, ln: (h, bt[b_, w], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, bq, d),
                     lambda b_, h, i, w, bt, st, ln: (b_, h, i, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, 1, bs), lambda b_, h, i, w, bt, st, ln: (h, bt[b_, w], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b_, h, i, w, bt, st, ln: (b_, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, qg, d), q.dtype),
        interpret=interpret,
        name="paged_span_attention",
    )(block_tables, row_start, row_len, *operands)
