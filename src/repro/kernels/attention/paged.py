"""Pallas TPU paged-attention kernels: single-token decode + ragged span.

The paged members of the unified attention-kernel family
(``repro.kernels.attention``).  Both read K/V directly from the paged
block pool through per-slot block tables — no gather materialization in
HBM.  The block table (and the per-row index/start/len scalars) ride in
SMEM via ``PrefetchScalarGridSpec``.

Decode grid: (B, W/P) — one query token per slot, every kv head folded
into the step, P table entries per step (the wrapper picks P from the
block size, table width and the step's VMEM).  The pool stays in HBM in
the engine's [NB, bs, Hkv, D] layout; the kernel DMAs only a row's live
pages (up to ``idx``, from the window's first page) into a VMEM double
buffer, the next live block's pages in flight while this one computes,
and grid steps past a row's live range do nothing.  Scores group key
rows (position, kv head) in whole lane tiles per batched matmul; a query
head's scores against another head's rows are masked out, as are
positions past ``idx`` (their V rows too: unfetched VMEM may hold NaN).
A head_dim under a whole lane tile is zero-padded, and a kv-head count
the in-place page view cannot tile is flattened, by the wrapper: both
copy the pool (``_rows_in_place``; 128-wide heads in 4, 8, 16 or 32 kv
heads are read in place).

The span kernel's BlockSpec index map derefs ``bt[b, w]`` so the DMA
engine fetches exactly the block each grid step needs, including
NULL-block padding slots whose contribution is masked out (garbage never
reaches the output).

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _live_pages(idx, *, bs: int, num_w: int, window: int | None):
    """First and last table entry a decode row at position ``idx`` reads:
    entries past ``idx // bs`` hold only future positions, entries wholly
    before the window only stale ones.  Every row keeps at least one
    entry, so the prefetch chain across rows never breaks."""
    hi = jnp.clip(idx // bs, 0, num_w - 1)
    if window is None:
        return jnp.int32(0), hi
    return jnp.minimum(jnp.maximum(idx - window + 1, 0) // bs, hi), hi


def _key_groups(span: int, rows: int) -> int:
    """Lanes per score group: the fewest whole lane tiles that whole pages
    (or whole lane tiles of a page, ``rows`` per page) fill, where a grid
    step's ``span`` key rows split into such groups; else the whole span
    is one group."""
    kg = 128 if rows % 128 == 0 else math.lcm(rows, 128)
    return kg if span % kg == 0 else span


def _rows_in_place(hkv: int, itemsize: int) -> bool:
    """Whether the kernel may view a page's [bs, Hkv, D] block as [bs * Hkv,
    D] rows in place.  TPU tiles pack 4 // itemsize rows per 32-bit word
    and pad a [Hkv, D] block of fewer (a lone bf16 head, one or two int8
    heads), which the view would misread; and a Hkv that is neither a power
    of two nor a multiple of 8 (3, 6, 12) sits in padded sublane tiles the
    view cannot slice."""
    return (hkv * itemsize % 4 == 0
            and (hkv & (hkv - 1) == 0 or hkv % 8 == 0))


def _paged_decode_kernel(
    bt_ref, idx_ref, q_ref, k_hbm, v_hbm, *refs,
    scale: float, window: int | None, bs: int, hkv: int, num_w: int,
    pages: int, quant: bool,
):
    # one grid step = one slot's `pages` consecutive table entries, every
    # kv head folded in.  K/V stay in HBM; each live page is one DMA of its
    # [bs * hkv, D] rows into a double buffer, and the next live block's
    # pages (this slot's or the next slot's first) are in flight while this
    # one computes.  Quantized pools DMA each page's scale row alongside.
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, slot_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        o_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_b = pl.num_programs(0)
    rows = bs * hkv  # pool rows per page, ordered (position, kv head)
    span = pages * rows
    kg = _key_groups(span, rows)
    groups = span // kg
    d = k_hbm.shape[-1]
    if k_hbm.ndim == 4:
        k_rows = k_hbm.reshape(k_hbm.shape[0], rows, d)
        v_rows = v_hbm.reshape(v_hbm.shape[0], rows, d)
    else:  # rows already flattened by paged_decode_fwd
        k_rows, v_rows = k_hbm, v_hbm

    def live(row):
        lo, hi = _live_pages(idx_ref[row], bs=bs, num_w=num_w, window=window)
        return lo, hi, lo // pages, hi // pages

    def block_pages(row, blk):
        """(first live entry, live entry count) of block ``blk``."""
        lo, hi, _, _ = live(row)
        first = jnp.maximum(lo, blk * pages)
        return first, jnp.minimum(hi, blk * pages + pages - 1) + 1 - first

    # DMA kinds: 0 K, 1 V, 2 K scales, 3 V scales (quantized pools)
    pairs = [(k_rows, k_buf), (v_rows, v_buf)]
    if quant:
        pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]

    def copy(kind, page, i, slot):
        """The DMA of pool page ``page`` into buffer page ``i`` of ``slot``."""
        src, buf = pairs[kind]
        n = buf.shape[1] // pages  # buffer rows per page
        return pltpu.make_async_copy(src.at[page],
                                     buf.at[slot, pl.ds(i * n, n)],
                                     sems.at[kind, slot])

    def fetch(row, blk, slot):
        first, count = block_pages(row, blk)

        def body(p, carry):
            for kind in range(len(pairs)):
                copy(kind, bt_ref[row, first + p], first + p - blk * pages,
                     slot).start()
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    def wait(kinds, count, slot):
        # a wait reads only the semaphore and the copy's size: any page
        # stands in for the one in flight
        def body(p, carry):
            for kind in kinds:
                copy(kind, 0, 0, slot).wait()
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    def flat_scales(buf, slot):
        """The block's per-key-row scales as [groups, 1, kg] lane rows."""
        if rows >= kg:  # a page spans whole groups: rows already in order
            return buf[slot]
        per = kg // rows  # pages per group
        lanes = buf.shape[-1]
        by_group = buf[slot].reshape(groups, per, 1, lanes)
        lane = jax.lax.broadcasted_iota(jnp.int32, (groups, 1, lanes), 2)
        out = by_group[:, 0]
        for i in range(1, per):
            part = pltpu.roll(by_group[:, i], i * rows, 2)
            out = jnp.where(lane >= i * rows, part, out)
        return out[..., :kg]

    idx = idx_ref[b]
    _, _, j_lo, j_hi = live(b)

    @pl.when(jnp.logical_and(j >= j_lo, j <= j_hi))
    def _block():
        @pl.when(jnp.logical_and(b == 0, j == j_lo))
        def _first():
            slot_ref[0] = 0
            fetch(b, j, 0)

        slot = slot_ref[0]

        @pl.when(j < j_hi)
        def _next_in_row():
            fetch(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(j == j_hi, b + 1 < num_b))
        def _next_row():
            fetch(b + 1, live(b + 1)[2], 1 - slot)

        slot_ref[0] = 1 - slot

        @pl.when(j == j_lo)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        _, count = block_pages(b, j)
        # key row r of the block (not of its score group) is position
        # base + r // hkv, kv head r % hkv; query row h*G + g reads kv head
        # h only
        base = j * pages * bs
        hq = q_ref.shape[1]
        key = (jax.lax.broadcasted_iota(jnp.int32, (groups, 1, kg), 0) * kg
               + jax.lax.broadcasted_iota(jnp.int32, (groups, 1, kg), 2))
        pos = base + key // hkv
        live_key = pos <= idx
        if window is not None:
            live_key &= pos > idx - window
        same_head = ((key - (pos - base) * hkv)
                     == jax.lax.broadcasted_iota(jnp.int32, (1, hq, kg), 1)
                     // (hq // hkv))
        mask = live_key & same_head  # [groups, hq, kg]

        wait((0, 2) if quant else (0,), count, slot)
        q = q_ref[0].astype(jnp.float32) * scale  # [hq, d]
        k = k_buf[slot].astype(jnp.float32).reshape(groups, kg, d)
        s = jax.lax.dot_general(
            jnp.broadcast_to(q, (groups, hq, d)), k,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [groups, hq, kg]
        if quant:
            s = s * flat_scales(ks_buf, slot)  # per-key-row scales
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(jnp.max(s, axis=0), axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])
        l_new = l_scr[...] * corr + jnp.sum(jnp.sum(p, axis=0), axis=1,
                                            keepdims=True)
        wait((1, 3) if quant else (1,), count, slot)
        if quant:
            # value scales fold into the weights; an unfetched scale row
            # may hold NaN bits, and 0 * NaN is NaN
            p = p * jnp.where(live_key, flat_scales(vs_buf, slot), 0.0)
        # V rows past idx (or before the window) may be unfetched VMEM:
        # zero them, since a zero weight times NaN is still NaN
        v_pos = base + (
            jax.lax.broadcasted_iota(jnp.int32, (groups, kg, 1), 0) * kg
            + jax.lax.broadcasted_iota(jnp.int32, (groups, kg, 1), 1)) // hkv
        v_live = v_pos <= idx
        if window is not None:
            v_live &= v_pos > idx - window
        v = jnp.where(v_live,
                      v_buf[slot].astype(jnp.float32).reshape(groups, kg, d),
                      0.0)
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [groups, hq, d]
        acc_scr[...] = acc_scr[...] * corr + jnp.sum(pv, axis=0)
        m_scr[...] = m_new
        l_scr[...] = l_new

        @pl.when(j == j_hi)
        def _finalize():
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scale_rows(scales, *, pages: int, kg: int):
    """[NB, bs, Hkv] scales -> [NB, c, 1, L] rows a page DMAs as a block:
    a page of ``rows = bs * Hkv`` scales spanning whole lane groups splits
    into ``c = rows // kg`` rows of ``kg``; a smaller page is one row,
    zero-padded to the lane tiles the kernel's group assembly rolls in."""
    nb = scales.shape[0]
    rows = scales.shape[1] * scales.shape[2]
    flat = scales.reshape(nb, rows).astype(jnp.float32)
    if rows >= kg:
        return flat.reshape(nb, rows // kg, 1, kg)
    lanes = -(-kg // 128) * 128
    return jnp.pad(flat, ((0, 0), (0, lanes - rows))).reshape(nb, 1, 1, lanes)


def paged_decode_fwd(
    q, k_pages, v_pages, block_tables, index, *, pages: int,
    k_scales=None, v_scales=None,
    window: int | None = None, interpret: bool = False,
):
    """q: [B, Hq, D] (q head h*G + g reads kv head h); k/v_pages:
    [NB, bs, Hkv, D] (the engine's pool, left in HBM); block_tables: [B, W]
    int32; index: [B] int32 (last valid position).  ``pages`` (a divisor
    of W) table entries per grid step.  k/v_scales (quantized pools):
    [NB, bs, Hkv] f32 per-(position, head) scales, applied in-kernel."""
    b, hq, head_dim = q.shape
    _, bs, hkv, _ = k_pages.shape
    num_w = block_tables.shape[1]
    assert num_w % pages == 0, "pages must divide the table width"
    if head_dim % 128:
        # a TPU tile is 128 lanes wide and Mosaic slices no narrower page
        # rows: pad head_dim with zeros, which moves neither q.k nor p.v
        q, k_pages, v_pages = (
            jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, -head_dim % 128),))
            for x in (q, k_pages, v_pages))
    d = q.shape[-1]
    if not _rows_in_place(hkv, k_pages.dtype.itemsize):
        # the kernel's page view [bs, Hkv, D] -> [bs * Hkv, D] would misread
        # or fail to slice the pool: reshape here, where XLA lays the rows
        # out itself
        nb = k_pages.shape[0]
        k_pages = k_pages.reshape(nb, bs * hkv, d)
        v_pages = v_pages.reshape(nb, bs * hkv, d)
    quant = k_scales is not None
    rows = bs * hkv
    span = pages * rows
    kg = _key_groups(span, rows)

    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / (head_dim ** 0.5), window=window,
        bs=bs, hkv=hkv, num_w=num_w, pages=pages, quant=quant,
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hq, d), lambda b_, j, bt, idx: (b_, 0, 0)),
                hbm, hbm]
    operands = [q, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, span, d), k_pages.dtype),
               pltpu.VMEM((2, span, d), v_pages.dtype)]
    if quant:
        leaves = [_scale_rows(s, pages=pages, kg=kg)
                  for s in (k_scales, v_scales)]
        in_specs += [hbm, hbm]
        operands += leaves
        c, lanes = leaves[0].shape[1], leaves[0].shape[3]
        scratch += [pltpu.VMEM((2, pages * c, 1, lanes), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((4 if quant else 2, 2)),
        pltpu.SMEM((1,), jnp.int32),  # buffer slot of the current block
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, d), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_w // pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hq, d),
                                   lambda b_, j, bt, idx: (b_, 0, 0)),
            scratch_shapes=scratch,
        ),
        # the prefetch chain crosses grid steps: they run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, index, *operands)
    return out[..., :head_dim]


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
