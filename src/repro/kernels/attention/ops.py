"""jit'd wrappers for the attention-kernel family: model/pool layout <->
kernel layout, padding, backend select, tuned-parameter plumbing."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.attention.flash import flash_attention_fwd
from repro.kernels.attention.paged import paged_decode_fwd, paged_span_fwd


def _pad_to(x, axis: int, mult: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: int | None = None,
    q_offset: int = 0, block_q: int = 128, block_k: int = 128,
    interpret: bool | None = None,
):
    """Dense prefill.  q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] (model
    layout).  block_q/block_k are the autotuned tiling parameters.

    interpret=None -> auto: Pallas interpret mode off-TPU (this container),
    compiled Mosaic kernel on TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qt = jnp.swapaxes(q, 1, 2)  # [B, Hq, Sq, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    qt, sq = _pad_to(qt, 2, block_q)
    kt, _ = _pad_to(kt, 2, block_k)
    vt, _ = _pad_to(vt, 2, block_k)
    out = flash_attention_fwd(
        qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return jnp.swapaxes(out[:, :, :sq], 1, 2)


def _scale_pages(cache):
    """Quantized pools: head-major [Hkv, NB, 1, bs] scale rows for the
    kernels (empty kwargs for native pools — the static `quant` flag stays
    False)."""
    if "k_scale" not in cache:
        return {}
    return {n + "s": jnp.transpose(cache[n], (2, 0, 1))[:, :, None, :]
            for n in ("k_scale", "v_scale")}


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(cache, q, block_tables, index, *, window: int | None = None,
                    interpret: bool | None = None):
    """Paged decode.  cache: {"k","v"} [NB, bs, Hkv, D] pooled blocks
    (engine layout); q: [B, 1, Hq, D]; block_tables: [B, W] int32;
    index: [B] int32.

    interpret=None -> auto: Pallas interpret mode off-TPU (this container),
    compiled Mosaic kernel on TPU.  Returns [B, 1, Hq, D].
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, hq, d = q.shape
    hkv = cache["k"].shape[2]
    g = hq // hkv
    qt = q.reshape(b, hkv, g, d)  # q head h = kh*G + g_
    kp = jnp.transpose(cache["k"], (2, 0, 1, 3))  # [Hkv, NB, bs, D]
    vp = jnp.transpose(cache["v"], (2, 0, 1, 3))
    out = paged_decode_fwd(
        qt, kp, vp, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(index, jnp.int32), window=window, interpret=interpret,
        **_scale_pages(cache),
    )
    return out.reshape(b, 1, hq, d)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "interpret"))
def paged_span_attention(cache, q, block_tables, row_start, row_len, *,
                         window: int | None = None,
                         block_q: int | None = None,
                         interpret: bool | None = None):
    """Ragged multi-query paged attention (the unified serve step's mixed
    rows).  cache: {"k","v"} [NB, bs, Hkv, D] pooled blocks; q: [B, Q, Hq, D]
    — row ``b`` holds ``row_len[b]`` valid queries at absolute positions
    ``row_start[b] + j``; block_tables: [B, W] int32.  block_q tiles the
    folded Q*G query dim (the autotuned parameter); None keeps one tile.
    Returns [B, Q, Hq, D] (padded query rows are garbage, caller discards).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, qlen, hq, d = q.shape
    hkv = cache["k"].shape[2]
    g = hq // hkv
    # query-major span fold per kv head: kernel row j*G + g_ = (query j, group g_)
    qt = q.reshape(b, qlen, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(b, hkv, qlen * g, d)
    if block_q is not None:
        qt, qg = _pad_to(qt, 2, block_q)
    else:
        qg = qlen * g
    kp = jnp.transpose(cache["k"], (2, 0, 1, 3))  # [Hkv, NB, bs, D]
    vp = jnp.transpose(cache["v"], (2, 0, 1, 3))
    out = paged_span_fwd(
        qt, kp, vp, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(row_start, jnp.int32), jnp.asarray(row_len, jnp.int32),
        group=g, window=window, block_q=block_q, interpret=interpret,
        **_scale_pages(cache),
    )
    out = out[:, :, :qg].reshape(b, hkv, qlen, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, qlen, hq, d)


def paged_attention_sharded(cache, q, block_tables, index, *,
                            window: int | None, rules,
                            interpret: bool | None = None):
    """Tensor-parallel paged decode: one kernel instance per model-axis
    shard, each over its OWN kv-head slice of the pool and the aligned
    q-head group (q head ``h`` belongs to kv head ``h // G``, and q heads
    are laid out kv-major, so a contiguous Hq split matches a contiguous
    Hkv split).  No cross-shard communication: heads are embarrassingly
    parallel, the all-reduce happens later in the output projection.
    """
    from repro.compat import shard_map
    from repro.models.cache_utils import PAGED_POOL_AXES, PAGED_SCALE_AXES

    kv_spec = rules.pspec(PAGED_POOL_AXES)  # [NB, bs, Kh, D] pool sharding
    q_spec = P(None, None, kv_spec[2], kv_spec[3])  # [B, 1, Hq, D]
    hkv = cache["k"].shape[2]
    shards = rules.axis_size(kv_spec[2]) if kv_spec[2] is not None else 1
    if kv_spec[2] is not None and hkv % shards:
        raise ValueError(f"kv heads {hkv} not divisible by {shards}-way shard")
    names = [n for n in ("k", "v", "k_scale", "v_scale") if n in cache]
    # scale leaves shard on kv-heads alongside their pages
    sc_spec = rules.pspec(PAGED_SCALE_AXES)
    leaf_specs = tuple(kv_spec if n in ("k", "v") else sc_spec for n in names)

    def per_shard(*args):
        entry = dict(zip(names, args[:len(names)]))
        qs, bt, ix = args[len(names):]
        return paged_attention(entry, qs, bt, ix,
                               window=window, interpret=interpret)

    fn = shard_map(
        per_shard, mesh=rules.mesh,
        in_specs=leaf_specs + (q_spec, P(None, None), P(None)),
        out_specs=q_spec,
    )
    return fn(*(cache[n] for n in names), q, block_tables, index)


def paged_span_attention_sharded(cache, q, block_tables, row_start, row_len, *,
                                 window: int | None, rules,
                                 block_q: int | None = None,
                                 interpret: bool | None = None):
    """Tensor-parallel span attention: same per-shard kv-head slicing as
    :func:`paged_attention_sharded` (q heads are kv-major, so a contiguous
    Hq split follows a contiguous Hkv split), with the span registers
    replicated — heads stay embarrassingly parallel across queries."""
    from repro.compat import shard_map
    from repro.models.cache_utils import PAGED_POOL_AXES, PAGED_SCALE_AXES

    kv_spec = rules.pspec(PAGED_POOL_AXES)
    q_spec = P(None, None, kv_spec[2], kv_spec[3])
    hkv = cache["k"].shape[2]
    shards = rules.axis_size(kv_spec[2]) if kv_spec[2] is not None else 1
    if kv_spec[2] is not None and hkv % shards:
        raise ValueError(f"kv heads {hkv} not divisible by {shards}-way shard")
    names = [n for n in ("k", "v", "k_scale", "v_scale") if n in cache]
    sc_spec = rules.pspec(PAGED_SCALE_AXES)
    leaf_specs = tuple(kv_spec if n in ("k", "v") else sc_spec for n in names)

    def per_shard(*args):
        entry = dict(zip(names, args[:len(names)]))
        qs, bt, st, ln = args[len(names):]
        return paged_span_attention(entry, qs, bt, st, ln,
                                    window=window, block_q=block_q,
                                    interpret=interpret)

    fn = shard_map(
        per_shard, mesh=rules.mesh,
        in_specs=leaf_specs + (q_spec, P(None, None), P(None), P(None)),
        out_specs=q_spec,
    )
    # explicit scope UNDER any enclosing overlap stage scope (ovl_mb<i>/...):
    # the micro-batched span pipeline invokes this wrapper once per stage,
    # and keeping the kernel's ops inside the inherited stage scope is what
    # lets hlo_comm attribute the surrounding collectives per micro-batch
    with jax.named_scope("paged_span_sharded"):
        return fn(*(cache[n] for n in names), q, block_tables, row_start,
                  row_len)
