"""Paged-decode + ragged-span Pallas kernels vs pure-jnp oracles
(interpret mode)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels.attention import (
    paged_attention, paged_attention_ref, paged_span_attention, paged_span_ref,
)
from repro.kernels.attention.ops import (
    DECODE_STEP_TOKENS, DECODE_STEP_VMEM, decode_pages_per_step,
)
from repro.models.cache_utils import quantized_cache_write


def _case(seed, B, W, bs, Hkv, G, D, NB):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    # distinct non-null blocks per slot; trailing entries NULL
    bt = np.zeros((B, W), np.int32)
    ids = rng.permutation(np.arange(1, NB))[:B * W].reshape(B, W)
    alloc = rng.integers(1, W + 1, B)  # allocated span per slot
    for b in range(B):
        bt[b, :alloc[b]] = ids[b, :alloc[b]]
    idx = np.array([int(rng.integers(0, alloc[b] * bs)) for b in range(B)],
                   np.int32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(idx)


def _edge_case(seed, B, W, bs, Hkv, G, D, NB, idx):
    """Every row owns all W table entries (distinct blocks); ``idx`` pins
    each row's last position."""
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    bt = rng.permutation(np.arange(1, NB))[:B * W].reshape(B, W)
    return q, kp, vp, jnp.asarray(bt, jnp.int32), jnp.asarray(idx, jnp.int32)


def _int8_case(seed, B, W, bs, Hkv, G, D, NB, idx):
    """An int8 pool written token by token through the engine's
    ``quantized_cache_write`` (every row owns all W entries; short rows
    rewrite their last position); the reference reads it dequantized."""
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(np.arange(1, NB))[:B * W].reshape(B, W),
                     jnp.int32)
    idx = jnp.asarray(idx, jnp.int32)
    cache = {"k": jnp.zeros((NB, bs, Hkv, D), jnp.int8),
             "v": jnp.zeros((NB, bs, Hkv, D), jnp.int8),
             "k_scale": jnp.ones((NB, bs, Hkv), jnp.float32),
             "v_scale": jnp.ones((NB, bs, Hkv), jnp.float32)}
    k_new = jnp.asarray(rng.standard_normal((W * bs, B, 1, Hkv, D)),
                        jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((W * bs, B, 1, Hkv, D)),
                        jnp.float32)
    write = jax.jit(functools.partial(quantized_cache_write, kv_dtype="int8"))
    for t in range(int(idx.max()) + 1):
        cache = write(cache, k_new[t], v_new[t], bt,
                      jnp.minimum(jnp.int32(t), idx))
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    kd = quant.kv_dequantize(cache["k"], cache["k_scale"], jnp.float32)
    vd = quant.kv_dequantize(cache["v"], cache["v_scale"], jnp.float32)
    return q, cache, (kd, vd), bt, idx


def _edges(W, bs, k):
    """idx at 0, at a page's last and the next page's first position (k
    pages in), and at the table's last position."""
    return [0, k * bs - 1, k * bs, W * bs - 1]


# the decode kernel walks decode_pages_per_step(...) table entries per grid
# step, here bounded by the tokens per step: W = 4 at bs 8 fits one step
# (W = P); W = 16 at bs 64 takes two of 8 (W > P), so k = 8 puts idx on
# both sides of the step boundary
PAGED_CASES = {
    # the original grid: window x G, in the old ids
    "1-None": dict(G=1, window=None),
    "4-None": dict(G=4, window=None),
    "1-9": dict(G=1, window=9),
    "4-9": dict(G=4, window=9),
    "G8": dict(G=8, window=None),
    "yi-fold-Hkv4-G8": dict(G=8, Hkv=4, window=None),
    "W>P": dict(G=4, W=16, bs=64, NB=80, window=None),
    "W>P-edges": dict(G=8, Hkv=4, W=16, bs=64, NB=80, B=4,
                      idx=_edges(16, 64, 8), window=None),
    "W=P-edges": dict(G=8, Hkv=4, W=4, bs=8, NB=32, B=4,
                      idx=_edges(4, 8, 2), window=None),
    "W>P-window": dict(G=4, W=16, bs=64, NB=80, B=4,
                       idx=_edges(16, 64, 8), window=100),
    "W=P-window": dict(G=8, Hkv=4, W=4, bs=8, NB=32, B=4,
                       idx=_edges(4, 8, 2), window=9),
    # four steps of 32 entries: full middle steps between a row's edges
    "W=4P": dict(G=4, W=128, bs=16, NB=300, B=2, idx=[1700, 2047],
                 window=None),
    "W=4P-window": dict(G=4, W=128, bs=16, NB=300, B=2, idx=[1700, 2047],
                        window=1100),
    # int8 pools: the kernel's fused dequant against the dequantized pool
    "int8-W=P": dict(G=8, Hkv=4, W=4, bs=8, NB=13, idx=[0, 16, 31],
                     window=None, int8=True),
    "int8-W>P": dict(G=8, Hkv=4, W=16, bs=64, NB=49, idx=[0, 512, 1023],
                     window=None, int8=True),
    # fewer kv heads than a TPU tile word packs rows (a lone bf16 head, two
    # int8 heads): the pool reaches the kernel with its rows flattened
    "bf16-Hkv1-G8": dict(G=8, Hkv=1, window=None, bf16=True),
    "int8-Hkv2": dict(G=4, Hkv=2, W=4, bs=8, NB=13, idx=[0, 16, 31],
                      window=None, int8=True),
    # kv heads that do not divide a 128-row score group: every group after
    # the first starts on a kv head other than 0
    "Hkv3-bs128": dict(G=2, Hkv=3, bs=128, window=None),
    "Hkv12-bs32-window": dict(G=1, Hkv=12, bs=32, window=40),
    "int8-Hkv12-bs32": dict(G=1, Hkv=12, W=4, bs=32, NB=13, idx=[0, 40, 127],
                            window=None, int8=True),
    # 48-row pages: a score group is lcm(48, 128) = 384 rows, eight pages
    "int8-Hkv3-bs16": dict(G=2, Hkv=3, W=16, bs=16, NB=49,
                           idx=[0, 200, 255], window=None, int8=True),
}


@pytest.mark.parametrize("case", list(PAGED_CASES), ids=list(PAGED_CASES))
def test_paged_kernel_matches_ref(case):
    c = dict(B=3, W=4, bs=8, Hkv=2, D=16, NB=32) | PAGED_CASES[case]
    window, idx = c.pop("window"), c.pop("idx", None)
    tol = 2e-6
    if c.pop("int8", False):
        q, cache, (kp, vp), bt, idx = _int8_case(4, idx=idx, **c)
        tol = 2e-5
    else:
        bf16 = c.pop("bf16", False)
        if idx is None:
            q, kp, vp, bt, idx = _case(0, **c)
        else:
            q, kp, vp, bt, idx = _edge_case(0, idx=idx, **c)
        if bf16:  # the reference reads the same values in float32
            kp, vp = (x.astype(jnp.bfloat16).astype(jnp.float32)
                      for x in (kp, vp))
            cache = {"k": kp.astype(jnp.bfloat16),
                     "v": vp.astype(jnp.bfloat16)}
        else:
            cache = {"k": kp, "v": vp}
    out = paged_attention(cache, q, bt, idx, window=window, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, idx, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


# (block size, table width, kv heads, head dim, itemsize) -> entries per step
PAGES_PER_STEP = {
    "yi-bf16": ((16, 256, 4, 128, 2), 32),
    "granite-bf16": ((16, 256, 8, 128, 2), 32),
    "granite-int8": ((16, 256, 8, 128, 1), 32),
    "codeqwen-bf16": ((16, 256, 32, 128, 2), 8),  # bounded by VMEM
    "deepseek-bf16": ((16, 256, 16, 128, 2), 16),
    "odd-width": ((16, 100, 4, 128, 2), 25),  # a divisor of W
}


@pytest.mark.parametrize("case", list(PAGES_PER_STEP))
def test_decode_pages_per_step(case):
    shape, want = PAGES_PER_STEP[case]
    pages = decode_pages_per_step(*shape)
    assert pages == want
    bs, w, hkv, d, itemsize = shape
    assert w % pages == 0 and pages * bs <= DECODE_STEP_TOKENS
    assert pages * bs * hkv * d * 2 * (2 * itemsize + 4) <= DECODE_STEP_VMEM


def _span_case(seed, B, W, bs, Hkv, G, D, NB, Q):
    """Rows with ragged valid lengths at block-unaligned start positions."""
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NB, bs, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Q, Hkv * G, D)), jnp.float32)
    bt = np.zeros((B, W), np.int32)
    ids = rng.permutation(np.arange(1, NB))[:B * W].reshape(B, W)
    row_len = rng.integers(1, Q + 1, B).astype(np.int32)
    row_start = np.zeros((B,), np.int32)
    for b in range(B):
        # enough allocated blocks to cover start + len, start unaligned
        row_start[b] = int(rng.integers(0, W * bs - row_len[b]))
        alloc = (row_start[b] + row_len[b] - 1) // bs + 1
        bt[b, :alloc] = ids[b, :alloc]
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(row_start), jnp.asarray(row_len)


def _mask_pad(out, row_len):
    q = out.shape[1]
    valid = (np.arange(q)[None, :] < np.asarray(row_len)[:, None])[..., None, None]
    return np.where(valid, np.asarray(out), 0.0)


@pytest.mark.parametrize("window", [None, 9])
@pytest.mark.parametrize("G", [1, 4])  # MHA and GQA
def test_span_kernel_matches_ref(window, G):
    """Ragged multi-query rows (the unified serve step's mixed batch):
    padded query rows are compared masked — the engine discards them."""
    q, kp, vp, bt, st, ln = _span_case(2, B=3, W=4, bs=8, Hkv=2, G=G, D=16,
                                       NB=32, Q=6)
    out = paged_span_attention({"k": kp, "v": vp}, q, bt, st, ln,
                               window=window, interpret=True)
    ref = paged_span_ref(q, kp, vp, bt, st, ln, window=window)
    np.testing.assert_allclose(_mask_pad(out, ln), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_span_kernel_block_q_tile_invariance():
    """The autotuned ``block_q`` tiling over the folded Q*G dim must not
    change per-row numerics: every row sees the same KV-block sequence and
    masks regardless of tile boundaries (incl. the padded-fold case)."""
    q, kp, vp, bt, st, ln = _span_case(5, B=3, W=4, bs=8, Hkv=2, G=4, D=16,
                                       NB=32, Q=6)
    base = paged_span_attention({"k": kp, "v": vp}, q, bt, st, ln,
                                window=9, interpret=True)
    for bq in (4, 8, 16):  # Q*G = 24: exact tiles and a padded fold
        tiled = paged_span_attention({"k": kp, "v": vp}, q, bt, st, ln,
                                     window=9, block_q=bq, interpret=True)
        np.testing.assert_allclose(_mask_pad(tiled, ln), _mask_pad(base, ln),
                                   rtol=1e-6, atol=1e-6)


def test_span_kernel_single_token_equals_decode_kernel():
    """A 1-token span IS a paged decode row: both kernels must agree."""
    q, kp, vp, bt, idx = _case(3, B=3, W=4, bs=8, Hkv=2, G=2, D=16, NB=32)
    dec = paged_attention({"k": kp, "v": vp}, q, bt, idx, interpret=True)
    span = paged_span_attention({"k": kp, "v": vp}, q, bt, idx,
                                jnp.ones((3,), jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(span),
                               atol=1e-6, rtol=1e-6)


def test_paged_kernel_ignores_null_and_future_blocks():
    """Garbage in the NULL block, in a row's tail block past its index and
    in allocated future blocks never reaches the output: poison them with
    NaN (a zero weight times NaN is still NaN, so the V rows themselves
    must be masked), answers must not move by a bit.  One table fits one
    grid step, the other takes two (W > P)."""
    for W, bs, nb in ((3, 8, 16), (16, 64, 40)):
        q, kp, vp, bt, idx = _case(1, B=2, W=W, bs=bs, Hkv=2, G=2, D=16,
                                   NB=nb)
        base = paged_attention({"k": kp, "v": vp}, q, bt, idx,
                               interpret=True)
        kp2, vp2 = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
        for b in range(2):
            i = int(idx[b])
            tail = int(bt[b, i // bs])
            kp2 = kp2.at[tail, i % bs + 1:].set(jnp.nan)
            vp2 = vp2.at[tail, i % bs + 1:].set(jnp.nan)
            for blk in np.asarray(bt[b, i // bs + 1:]):
                kp2 = kp2.at[int(blk)].set(jnp.nan)
                vp2 = vp2.at[int(blk)].set(jnp.nan)
        poisoned = paged_attention({"k": kp2, "v": vp2}, q, bt, idx,
                                   interpret=True)
        assert not np.isnan(np.asarray(base)).any()
        np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))
