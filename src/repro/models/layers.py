"""Shared layer primitives: norms, dense, rotary embedding, embeddings, MLP."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import ParamDecl
from repro.sharding.partition import constrain, padded_vocab

# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------


def norm_decl(cfg, dim: int | None = None) -> dict:
    d = dim or cfg.d_model
    decl = {"scale": ParamDecl((d,), ("embed_noshard",), init="ones", dtype=jnp.float32)}
    if cfg.norm == "layernorm":
        decl["bias"] = ParamDecl((d,), ("embed_noshard",), init="zeros", dtype=jnp.float32)
    return decl


def apply_norm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if "bias" in params:  # LayerNorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"] + params["bias"]
    else:  # RMSNorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dtype)


def rmsnorm_gated(params: dict, x: jax.Array, gate: jax.Array, eps: float) -> jax.Array:
    """Mamba-2 gated RMSNorm: norm(x * silu(gate))."""
    xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["scale"]).astype(x.dtype)


# ----------------------------------------------------------------------
# Dense
# ----------------------------------------------------------------------


def dense_decl(
    in_dim: int,
    out_dims: tuple[int, ...],
    in_axis: str | None,
    out_axes: tuple[str | None, ...],
    *,
    bias: bool = False,
    scale: float | None = None,
) -> dict:
    decl = {
        "w": ParamDecl((in_dim, *out_dims), (in_axis, *out_axes), init="normal", scale=scale)
    }
    if bias:
        decl["b"] = ParamDecl(tuple(out_dims), tuple(out_axes), init="zeros", dtype=jnp.float32)
    return decl


_ACCUM = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def accum_dtype(cfg):
    return _ACCUM[getattr(cfg, "accum_dtype", "float32")]


def dense(params: dict, x: jax.Array, *, accum=jnp.float32) -> jax.Array:
    """y[..., o1, o2, ...] = x[..., i] @ w[i, o1, o2, ...] (+ b).

    ``accum`` is the dot's preferred_element_type: with a TP-sharded
    contraction dim, XLA places the cross-shard all-reduce on partial sums
    of this dtype — bfloat16 halves that collective's bytes (MXU-internal
    accumulation on TPU stays fp32 either way).
    """
    w = params["w"]
    y = jax.lax.dot_general(
        x,
        w.astype(x.dtype),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=accum,
    )
    if "b" in params:
        y = y + params["b"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary position embedding
# ----------------------------------------------------------------------


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's rotary frequencies (arXiv:2309.00071) as Hugging Face's
    ``_compute_yarn_parameters`` computes them: frequency i (of D/2) is
    ``theta^(-2i/D) * ((1 - g_i) / factor + g_i)``, ``g_i = 1 - clip((i -
    low) / (high - low), 0, 1)``, where ``low`` and ``high`` are the
    dimensions whose wavelength makes ``beta_fast`` and ``beta_slow`` turns
    over ``original_max_position_embeddings`` positions (floored and
    ceiled, clamped to [0, D - 1]).  Static: float32 constants."""
    d = head_dim

    def dim_of(turns):
        return (d * math.log(yarn.original_max_position_embeddings
                             / (turns * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    keep = 1.0 - np.clip((np.arange(d // 2, dtype=np.float32) - low)
                         / (high - low), 0.0, 1.0)
    return (base / yarn.factor * (1.0 - keep) + base * keep).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         yarn=None) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable int32).
    ``yarn`` (a ``configs.base.Yarn``): YaRN's frequencies, and cos and sin
    scaled by its ``attention_factor``."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freq = jnp.asarray(yarn_inv_freq(d, theta, yarn))
    ang = positions[..., :, None].astype(jnp.float32) * freq  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings computed on the fly.

    (The real whisper-small has a learned 448-entry table; the assigned
    decode_32k shape exceeds it, so we use functional sinusoids — noted in
    DESIGN.md as an approximation.)
    """
    half = d_model // 2
    freq = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------
# Embedding / LM head
# ----------------------------------------------------------------------


def embedding_decl(cfg) -> dict:
    v = padded_vocab(cfg.vocab_size)
    decl = {"embedding": ParamDecl((v, cfg.d_model), ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        decl["lm_head"] = ParamDecl(
            (cfg.d_model, v), ("embed", "vocab"), init="normal"
        )
    return decl


def embed_tokens(params: dict, tokens: jax.Array, dtype, method: str = "take") -> jax.Array:
    emb = params["embedding"].astype(dtype)
    if method == "onehot":
        # one-hot matmul: with the table sharded on vocab, each shard
        # contributes a partial [B, d] row sum and XLA reduces it — no
        # whole-table all-gather (decode-time lookup of a sharded table
        # otherwise replicates the table per token).
        v = emb.shape[0]
        oh = jax.nn.one_hot(tokens, v, dtype=dtype)
        x = jnp.einsum("...v,vd->...d", oh, emb, preferred_element_type=jnp.float32)
        x = x.astype(dtype)
    else:
        x = jnp.take(emb, tokens, axis=0)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def lm_logits(params: dict, x: jax.Array, cfg) -> jax.Array:
    """Logits over the padded vocab; pad ids masked to a large negative."""
    if cfg.tie_embeddings or "lm_head" not in params:
        w = params["embedding"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    logits = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    v = logits.shape[-1]
    if v != cfg.vocab_size:
        pad_mask = jnp.arange(v) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e9, logits)
    axes = ("act_batch",) + ("act_seq",) * (logits.ndim - 2) + ("act_vocab",)
    return constrain(logits, axes)


# ----------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain)
# ----------------------------------------------------------------------


def mlp_decl(cfg, d_ff: int | None = None, mlp_axis: str = "mlp") -> dict:
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    decl = {"w_up": dense_decl(d, (ff,), "embed", (mlp_axis,))}
    if cfg.gated_mlp:
        decl["w_gate"] = dense_decl(d, (ff,), "embed", (mlp_axis,))
    decl["w_down"] = dense_decl(ff, (d,), mlp_axis, ("embed",))
    if cfg.qkv_bias and not cfg.gated_mlp:  # whisper-style biases
        decl["w_up"] = dense_decl(d, (ff,), "embed", (mlp_axis,), bias=True)
        decl["w_down"] = dense_decl(ff, (d,), mlp_axis, ("embed",), bias=True)
    return decl


_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def apply_mlp(params: dict, x: jax.Array, cfg) -> jax.Array:
    act = _ACTS[cfg.act]
    up = dense(params["w_up"], x)
    if "w_gate" in params:
        h = act(dense(params["w_gate"], x)) * up
    else:
        h = act(up)
    h = constrain(h, ("act_batch", "act_seq", "act_ff"))
    # w_down is row-parallel (contraction dim TP-sharded) -> its psum is the
    # hot activation collective; honor cfg.accum_dtype here
    return dense(params["w_down"], h, accum=accum_dtype(cfg))
