"""Dense GQA decoder (Llama layout): yi-9b, granite-8b.

Pre-norm RMSNorm, rotary embedding on q and k (halves rotated, as the
Llama/Granite/Yi configurations state), grouped-query attention with a
causal mask over the whole context, SwiGLU MLP, untied LM head.  Every
layer is of one kind and draws the same nine tensors.  The reference
attends each sequence over itself in full; the work counts have every
token multiply every weight and attend its whole context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import refmodel as rm
import seedweights as sw

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "num_hidden_layers",
        "rope_theta", "rms_norm_eps", "torch_dtype")
LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
         "w_down")
IDS = {n: len(sw.GLOBAL) + i for i, n in enumerate(LAYER)}
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=1, head_dim=32, num_hidden_layers=2,
            vocab_size=512)


def dims(c: dict) -> dict:
    """Sizes read from a configuration file (Hugging Face key names)."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return dict(d=d, h=h, kh=c["num_key_value_heads"],
                hd=c.get("head_dim") or d // h, ff=c["intermediate_size"],
                v=c["vocab_size"], layers=c["num_hidden_layers"],
                theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def shapes(c: dict) -> dict[str, tuple[int, ...]]:
    z = dims(c)
    d, h, kh, hd, ff, v = z["d"], z["h"], z["kh"], z["hd"], z["ff"], z["v"]
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
            "attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kh * hd),
            "wv": (d, kh * hd), "wo": (h * hd, d), "mlp_norm": (d,),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def experts(c: dict) -> dict[str, int]:
    return {}


def kind(c: dict, i: int) -> str:
    return "dense"


def tensors(c: dict, kind: str) -> tuple[str, ...]:
    return LAYER


def program(c: dict) -> dict:
    z = dims(c)
    return dict(d_model=z["d"], num_heads=z["h"], num_kv_heads=z["kh"],
                head_dim=z["hd"], d_ff=z["ff"], vocab_size=z["v"],
                dtype=c["torch_dtype"], norm_eps=z["eps"],
                rope_theta=z["theta"])


def tiny(c: dict, dtype: str) -> dict:
    """The program's ``reduced()`` widths, two layers."""
    return dict(TINY, torch_dtype=dtype, program=dict(
        d_model=TINY["hidden_size"], d_ff=TINY["intermediate_size"],
        num_heads=TINY["num_attention_heads"],
        num_kv_heads=TINY["num_key_value_heads"], head_dim=TINY["head_dim"],
        vocab_size=TINY["vocab_size"], dtype=dtype, attn_block_kv=0,
        remat="none"))


# ---- the reference --------------------------------------------------------

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
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=rm.HI) / np.sqrt(d)
        rows = i * qblk + jnp.arange(qblk)
        s = jnp.where(t[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=rm.HI)

    return jax.lax.map(blk, (qs, jnp.arange(nb))).reshape(L, kh, g, d)


def forward(w, x, pos, kind, *, c, fp8, qblk):
    z = dims(c)
    B, L, _ = x.shape
    h = rm._rms(x, w["attn_norm"], z["eps"])
    q = rm._mm(h, w["wq"], fp8).reshape(B, L, z["h"], z["hd"])
    k = rm._mm(h, w["wk"], fp8).reshape(B, L, z["kh"], z["hd"])
    v = rm._mm(h, w["wv"], fp8).reshape(B, L, z["kh"], z["hd"])
    q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
    g = z["h"] // z["kh"]
    o = jax.vmap(lambda a, b, cc: _attend(a, b, cc, qblk))(
        q.reshape(B, L, z["kh"], g, z["hd"]), k, v)
    x = x + rm._mm(o.reshape(B, L, z["h"] * z["hd"]), w["wo"], fp8)
    h = rm._rms(x, w["mlp_norm"], z["eps"])
    a = jax.nn.silu(rm._mm(h, w["w_gate"], fp8)) * rm._mm(h, w["w_up"], fp8)
    return x + rm._mm(a, w["w_down"], fp8)


# ---- work counts (see workcount) ------------------------------------------

def _item(c: dict) -> int:
    return 2 if c["torch_dtype"] == "bfloat16" else 4


def matmul_params(c: dict) -> int:
    z = dims(c)
    d, h, kh, hd, ff = z["d"], z["h"], z["kh"], z["hd"], z["ff"]
    layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff
    return z["layers"] * layer + d * z["v"]


def weight_bytes(c: dict) -> int:
    z = dims(c)
    return sw.param_bytes(c) - z["v"] * z["d"] * _item(c)


def kv_bytes_per_position(c: dict) -> int:
    z = dims(c)
    return 2 * z["kh"] * z["hd"] * _item(c)


def attn_flops_per_position(c: dict) -> int:
    z = dims(c)
    return 4 * z["h"] * z["hd"]


def decode_flops(c: dict, tokens: int, ctx_sum: int, spans=None) -> int:
    return (2 * matmul_params(c) * tokens
            + attn_flops_per_position(c) * dims(c)["layers"] * ctx_sum)


def decode_bytes(c: dict, steps: float, ctx_sum: int, spans=None) -> float:
    return (weight_bytes(c) * steps
            + kv_bytes_per_position(c) * dims(c)["layers"] * ctx_sum)


def paged_decode_bytes(c: dict, ctx_sum: int, tokens: int, spans=None) -> int:
    z = dims(c)
    qo = 2 * z["h"] * z["hd"] * _item(c)
    return z["layers"] * (kv_bytes_per_position(c) * ctx_sum + qo * tokens)


def paged_decode_flops(c: dict, ctx_sum: int, spans=None) -> int:
    return attn_flops_per_position(c) * dims(c)["layers"] * ctx_sum
