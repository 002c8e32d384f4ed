"""Plain reference: a dense GQA decoder (Llama layout) in float32 jax.numpy.

Pre-norm RMSNorm, rotary embedding on q and k (halves rotated, as the
Llama/Granite/Yi configurations state), grouped-query attention with a
causal mask, SwiGLU MLP, untied LM head.  No kernel, cache or batching
trick: each sequence attends over itself in full.  Weights are drawn from
the seed by :mod:`seedweights`, one layer at a time, so that the whole
reference never holds more than one layer of float32 weights; every matrix
product runs at ``Precision.HIGHEST`` (on a TPU a float32 product otherwise
runs in bfloat16 passes).

``control=True`` also runs the control beside it: the same forward with
every matrix product's two operands rounded to float8 e4m3 (per output
channel for weights, per token for activations), the precision a
bfloat16 deployment would be tempted to step down to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import seedweights as sw

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [B, L, H, D]; positions [L]."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, qblk):
    """One sequence: q [L, Kh, G, D], k/v [L, Kh, D] -> [L, Kh, G, D],
    causal, in query blocks of ``qblk`` rows."""
    L, kh, g, d = q.shape
    nb = L // qblk
    qs = q.reshape(nb, qblk, kh, g, d)
    t = jnp.arange(L)

    def blk(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) / np.sqrt(d)
        rows = i * qblk + jnp.arange(qblk)
        s = jnp.where(t[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    return jax.lax.map(blk, (qs, jnp.arange(nb))).reshape(L, kh, g, d)


@functools.partial(jax.jit, static_argnames=("c", "fp8", "qblk"))
def _layer(w, x, *, c, fp8, qblk):
    z = sw.dims(dict(c))
    B, L, _ = x.shape
    pos = jnp.arange(L)
    h = _rms(x, w["attn_norm"], z["eps"])
    q = _mm(h, w["wq"], fp8).reshape(B, L, z["h"], z["hd"])
    k = _mm(h, w["wk"], fp8).reshape(B, L, z["kh"], z["hd"])
    v = _mm(h, w["wv"], fp8).reshape(B, L, z["kh"], z["hd"])
    q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
    g = z["h"] // z["kh"]
    o = jax.vmap(lambda a, b, cc: _attend(a, b, cc, qblk))(
        q.reshape(B, L, z["kh"], g, z["hd"]), k, v)
    x = x + _mm(o.reshape(B, L, z["h"] * z["hd"]), w["wo"], fp8)
    h = _rms(x, w["mlp_norm"], z["eps"])
    a = jax.nn.silu(_mm(h, w["w_gate"], fp8)) * _mm(h, w["w_up"], fp8)
    return x + _mm(a, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "names"))
def _draw(key, layer, *, c, names):
    shapes = sw.canonical_shapes(dict(c))
    dt = sw.DTYPES[dict(c)["torch_dtype"]]
    return {n: sw.tensor(key, n, layer, shapes[n], dt).astype(jnp.float32)
            for n in names}


def _frozen(c: dict):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "num_hidden_layers",
            "rope_theta", "rms_norm_eps", "torch_dtype")
    return tuple(sorted((k, c[k]) for k in keep if k in c))


def hidden(seed: int, c: dict, tokens: np.ndarray, *, fp8: bool = False,
           qblk: int = 128):
    """Final-normed hidden states [B, L, d] (float32) of token rows
    [B, L]; L must be a multiple of ``qblk``."""
    return _hidden_pair(seed, c, tokens, fp8s=(fp8,), qblk=qblk)[0]


def _hidden_pair(seed, c, tokens, *, fp8s, qblk):
    fc = _frozen(c)
    key = sw.base_key(seed)
    emb = _draw(key, -1, c=fc, names=("embed",))["embed"]
    x0 = jnp.take(emb, jnp.asarray(tokens), axis=0)
    del emb
    xs = [x0 for _ in fp8s]
    for layer in range(sw.dims(c)["layers"]):
        w = _draw(key, layer, c=fc, names=sw.LAYER)
        xs = [_layer(w, x, c=fc, fp8=f, qblk=qblk) for x, f in zip(xs, fp8s)]
        del w
    g = _draw(key, -1, c=fc, names=("final_norm",))["final_norm"]
    eps = sw.dims(c)["eps"]
    return [_rms(x, g, eps) for x in xs]


def lm_head(seed: int, c: dict):
    return _draw(sw.base_key(seed), -1, c=_frozen(c), names=("lm_head",))["lm_head"]


@functools.partial(jax.jit, static_argnames=("fp8",))
def _head_gaps(h, hc, w, idx, served, *, fp8):
    """Logit gaps at gathered positions.  h/hc [B, L, d] (reference,
    control), idx [B, N] positions, served [B, N] tokens.  Returns
    (reference best - reference logit of the served token,
     reference best - reference logit of the control's first token)."""
    take = lambda a: jnp.take_along_axis(a, idx[..., None], axis=1)
    ref = jnp.matmul(take(h), w, precision=HI)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    if hc is None:
        return gap, None
    ctl = _mm(take(hc), w, fp8)
    pick = jnp.argmax(ctl, -1)
    return gap, best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]


def served_gaps(seed: int, c: dict, seqs: list[np.ndarray],
                starts: list[int], *, control: bool = False,
                qblk: int = 256, rows: int = 0, length: int = 0) -> dict:
    """Teacher-forced reference over each sequence (prompt + served
    tokens).  For each served token ``seqs[i][t]`` (t >= starts[i]) the
    gap is how far its reference logit (at position t - 1) lies below the
    reference's best.  Returns {"gap": [...], "control_gap": [...]} as
    flat float lists over all served tokens.  The batch is padded to at
    least ``rows`` sequences of at least ``length`` positions (causal, so
    padding changes no gap), which fixes the shape that compiles."""
    L = max(max(len(s) for s in seqs), length)
    L = -(-L // qblk) * qblk
    B = max(len(seqs), rows)
    tok = np.zeros((B, L), np.int32)
    n = max(len(s) - p for s, p in zip(seqs, starts))
    n = 1 << max(n - 1, 1).bit_length()  # few head shapes, each cached
    idx = np.zeros((B, n), np.int32)
    served = np.zeros((B, n), np.int32)
    valid = np.zeros((B, n), bool)
    for i, (s, p) in enumerate(zip(seqs, starts)):
        tok[i, :len(s)] = s
        m = len(s) - p
        idx[i, :m] = np.arange(p - 1, len(s) - 1)
        served[i, :m] = s[p:]
        valid[i, :m] = True
    fp8s = (False, True) if control else (False,)
    hs = _hidden_pair(seed, c, tok, fp8s=fp8s, qblk=qblk)
    w = lm_head(seed, c)
    gap, cgap = _head_gaps(hs[0], hs[1] if control else None, w,
                           jnp.asarray(idx), jnp.asarray(served), fp8=True)
    out = {"gap": np.asarray(gap)[valid].astype(float).tolist()}
    if control:
        out["control_gap"] = np.asarray(cgap)[valid].astype(float).tolist()
    return out
