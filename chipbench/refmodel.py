"""Plain reference in float32 jax.numpy: the part every architecture shares.

The embedding, teacher forcing over padded rows, the final RMSNorm, the
untied LM head and the control; each layer's tensors and forward are the
configuration's architecture's (``chipbench/arch/<reference>.py``), one
compiled forward per kind of layer.  No kernel, cache or batching trick:
each sequence attends over itself.  Weights are drawn from the seed by
:mod:`seedweights`, one layer at a time, so that the whole reference never
holds more than one layer of float32 weights; every matrix product runs
through :func:`_mm` at ``Precision.HIGHEST`` (on a TPU a float32 product
otherwise runs in bfloat16 passes).

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

import cellspec
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


def _arch(c: dict):
    return cellspec.arch(c.get("reference"))


@functools.partial(jax.jit, static_argnames=("c", "kind", "fp8", "qblk"))
def _layer(w, x, *, c, kind, fp8, qblk):
    c = dict(c)
    pos = jnp.arange(x.shape[1])
    return _arch(c).forward(w, x, pos, kind, c=c, fp8=fp8, qblk=qblk)


@functools.partial(jax.jit, static_argnames=("c", "names"))
def _draw(key, layer, *, c, names):
    c = dict(c)
    arch = _arch(c)
    shapes, tids, n_exp = arch.shapes(c), sw.ids(arch), arch.experts(c)
    dt = sw.DTYPES[c["torch_dtype"]]

    def one(n, e=None):
        return sw.tensor(key, n, tids[n], layer, shapes[n], dt,
                         e).astype(jnp.float32)

    return {n: (jax.lax.map(lambda e, n=n: one(n, e), jnp.arange(n_exp[n]))
                if n in n_exp else one(n)) for n in names}


# what the shared code reads; an architecture adds its own KEYS
KEYS = ("reference", "num_hidden_layers", "rms_norm_eps", "torch_dtype")


def _frozen(c: dict):
    keep = set(KEYS) | set(_arch(c).KEYS)
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
    arch = _arch(c)
    for layer in range(c["num_hidden_layers"]):
        kind = arch.kind(c, layer)
        w = _draw(key, layer, c=fc, names=arch.tensors(c, kind))
        xs = [_layer(w, x, c=fc, kind=kind, fp8=f, qblk=qblk)
              for x, f in zip(xs, fp8s)]
        del w
    g = _draw(key, -1, c=fc, names=("final_norm",))["final_norm"]
    eps = float(c["rms_norm_eps"])
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
