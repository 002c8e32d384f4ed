"""Mellum2 MoE decoder (``model_type: mellum``): Mellum2-12B-A2.5B.

Pre-norm RMSNorm; grouped-query attention without QK-norm (the config has
no key for one) over a causal mask, on a ``sliding_attention`` layer also
within its window (the query at p attends (p - sliding_window, p]), with
the rotary embedding of the layer's kind from ``rope_parameters`` (halves
rotated): plain RoPE, or YaRN (arXiv:2309.00071) as Hugging Face computes
it, cos and sin scaled by ``attention_factor``.  Every MLP is sparse: the
router's float32 softmax over ``num_experts``, the top
``num_experts_per_tok`` renormalised (``norm_topk_prob``), and each chosen
expert's SwiGLU of width ``moe_intermediate_size`` weighted by its gate;
no shared expert.  Untied LM head.  The MTP head is left out (off the
decode path).

The reference runs every expert on every position and weights it by its
gate, zero for the experts a position does not route to: the layer's
definition, with no sort, buffer or capacity.  Expert tensors are drawn
once per published expert index.

Work counts take each row's ``spans`` (context before, tokens decoded)
and return None without them: a sliding layer attends at most
``sliding_window`` positions, and a token multiplies its
``num_experts_per_tok`` experts.  A decode step's B rows read each expert
that any of them routes to; under uniform routing that is E * (1 - (1 -
k/E)^B) distinct experts per layer, the count the bytes take.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import cellspec
import refmodel as rm
import seedweights as sw

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "num_hidden_layers", "rms_norm_eps",
        "torch_dtype", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "norm_topk_prob", "sliding_window")
ATTN = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")
EXPERT = ("w_gate", "w_up", "w_down")
LAYER = ATTN + ("router",) + EXPERT
# the names dense_gqa draws keep its ids; the router is new
IDS = dict({n: i for n, i in cellspec.arch("dense_gqa").IDS.items()
            if n in LAYER}, router=sw.new_id("router"))
FULL, SLIDING = "full_attention", "sliding_attention"
# the program's layer kinds for the configuration's layer types
KINDS = {SLIDING: "moe_sliding", FULL: "moe_full"}


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return dict(d=d, h=h, kh=c["num_key_value_heads"],
                hd=c.get("head_dim") or d // h, v=c["vocab_size"],
                layers=c["num_hidden_layers"], eps=float(c["rms_norm_eps"]),
                e=c["num_experts"], k=c["num_experts_per_tok"],
                ff=c["moe_intermediate_size"], window=c["sliding_window"])


def shapes(c: dict) -> dict[str, tuple[int, ...]]:
    z = dims(c)
    d, h, kh, hd, ff, v = z["d"], z["h"], z["kh"], z["hd"], z["ff"], z["v"]
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
            "attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kh * hd),
            "wv": (d, kh * hd), "wo": (h * hd, d), "mlp_norm": (d,),
            "router": (d, z["e"]), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}


def experts(c: dict) -> dict[str, int]:
    return {n: c["num_experts"] for n in EXPERT}


def kind(c: dict, i: int) -> tuple:
    """(layer type, window or None, the type's ``rope_parameters`` as
    sorted items): hashable, and all a layer's forward needs besides the
    widths."""
    t = c["layer_types"][i]
    rope = tuple(sorted(c["rope_parameters"][t].items()))
    return t, c["sliding_window"] if t == SLIDING else None, rope


def tensors(c: dict, kind: tuple) -> tuple[str, ...]:
    return LAYER


def _period(c: dict) -> list[str]:
    """One period of ``layer_types``, checked to repeat over the published
    depth and to hold only sparse MLPs (the program has no other)."""
    types = c["layer_types"]
    n = types.index(FULL) + 1
    if types != types[:n] * (len(types) // n) or len(types) % n:
        raise SystemExit(f"layer_types do not repeat a period: {types}")
    if set(c["mlp_layer_types"]) != {"sparse"}:
        raise SystemExit("every MLP of this architecture is sparse")
    if c["num_hidden_layers"] % n:
        raise SystemExit(f"{c['num_hidden_layers']} layers are not whole "
                         f"periods of {n}")
    return types[:n]


def _yarn(rope: dict) -> tuple:
    return (float(rope["factor"]), int(rope["original_max_position_embeddings"]),
            float(rope["beta_fast"]), float(rope["beta_slow"]),
            float(rope["attention_factor"]))


def program(c: dict) -> dict:
    z = dims(c)
    rp = c["rope_parameters"]
    if rp[SLIDING]["rope_type"] != "default" or rp[FULL]["rope_type"] != "yarn":
        raise SystemExit(f"rope types {rp}: the program gives plain RoPE to "
                         f"sliding layers and YaRN to full ones")
    if rp[SLIDING]["rope_theta"] != rp[FULL]["rope_theta"]:
        raise SystemExit("the program has one rope_theta for both kinds")
    return dict(d_model=z["d"], num_heads=z["h"], num_kv_heads=z["kh"],
                head_dim=z["hd"], vocab_size=z["v"], dtype=c["torch_dtype"],
                norm_eps=z["eps"], num_experts=z["e"],
                experts_per_token=z["k"], moe_d_ff=z["ff"],
                num_shared_experts=0, moe_impl="dropless",
                block_pattern=tuple(KINDS[t] for t in _period(c)),
                attention_window=z["window"],
                rope_theta=float(rp[FULL]["rope_theta"]),
                rope_yarn=_yarn(rp[FULL]), tie_embeddings=False)


def param_map(c: dict) -> dict:
    """The program's leaf paths -> tensors: sub-layer j of the scanned unit
    holds layers j, j + P, ... of the period P; its expert leaves hold
    every published expert."""
    n, depth = len(_period(c)), c["num_hidden_layers"]
    out = {"embed/embedding": "embed", "embed/lm_head": "lm_head",
           "final_norm/scale": "final_norm"}
    leaves = {"ln1/scale": "attn_norm", "ln2/scale": "mlp_norm",
              "attn/wq/w": "wq", "attn/wk/w": "wk", "attn/wv/w": "wv",
              "attn/wo/w": "wo", "moe/router/w": "router",
              "moe/experts/w_gate": "w_gate", "moe/experts/w_up": "w_up",
              "moe/experts/w_down": "w_down"}
    for j in range(n):
        for leaf, name in leaves.items():
            e = {"name": name, "layers": [j, depth, n]}
            if name in EXPERT:
                e["experts"] = [0, c["num_experts"], 1]
            out[f"stack/units/sub{j}/{leaf}"] = e
    return out


TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=1,
            head_dim=32, num_hidden_layers=4, vocab_size=512, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=64,
            sliding_window=16)


def tiny(c: dict, dtype: str) -> dict:
    """One period at small widths: 8 experts, top 2, a 16-position window,
    and YaRN over 64 original positions, so that its interpolated
    frequencies turn within the small cell's contexts."""
    rope = {t: dict(r) for t, r in c["rope_parameters"].items()}
    rope[FULL]["original_max_position_embeddings"] = 64
    t = dict(c, **TINY, torch_dtype=dtype, rope_parameters=rope)
    return dict(TINY, torch_dtype=dtype, rope_parameters=rope,
                param_map=param_map(t),
                program=dict(program(t), attn_block_kv=0, remat="none"))


# ---- the reference --------------------------------------------------------

def _inv_freq(rope: dict, hd: int):
    """Rotary frequencies, [hd/2] float32: plain, or YaRN's as Hugging
    Face's ``_compute_yarn_parameters`` gives them (the correction range
    floored and ceiled, clamped to [0, hd - 1])."""
    theta = float(rope["rope_theta"])
    base = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if rope["rope_type"] == "default":
        return base, 1.0
    l0 = rope["original_max_position_embeddings"]
    at = lambda turns: hd * math.log(l0 / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(at(rope["beta_fast"])), 0)
    high = min(math.ceil(at(rope["beta_slow"])), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    gamma = 1.0 - ramp
    return (base * ((1.0 - gamma) / rope["factor"] + gamma),
            float(rope["attention_factor"]))


def _rope(x, pos, rope, hd):
    """x [B, L, H, D]; positions [L]."""
    freq, scale = _inv_freq(rope, hd)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    half = hd // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window, qblk):
    """One sequence: q [L, Kh, G, D], k/v [L, Kh, D] -> [L, Kh, G, D],
    causal and, with a window, within it; in query blocks of ``qblk``."""
    L, kh, g, d = q.shape
    nb = L // qblk
    qs = q.reshape(nb, qblk, kh, g, d)
    t = jnp.arange(L)

    def blk(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=rm.HI) / np.sqrt(d)
        rows = (i * qblk + jnp.arange(qblk))[:, None]
        live = t[None, :] <= rows
        if window is not None:
            live &= t[None, :] > rows - window
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=rm.HI)

    return jax.lax.map(blk, (qs, jnp.arange(nb))).reshape(L, kh, g, d)


def _moe(w, h, z, fp8):
    """h [B, L, d] -> the gate-weighted sum of every expert's SwiGLU."""
    probs = jax.nn.softmax(rm._mm(h, w["router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, z["k"])
    top = top / top.sum(-1, keepdims=True)  # norm_topk_prob
    gates = (jax.nn.one_hot(idx, z["e"], dtype=jnp.float32)
             * top[..., None]).sum(-2)  # [B, L, E], zero off the top k

    def one(y, e):
        we = {n: w[n][e] for n in EXPERT}
        a = jax.nn.silu(rm._mm(h, we["w_gate"], fp8)) * rm._mm(h, we["w_up"],
                                                               fp8)
        return y + gates[..., e, None] * rm._mm(a, we["w_down"], fp8), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(z["e"]))[0]


def forward(w, x, pos, kind, *, c, fp8, qblk):
    _, window, rope = kind
    rope = dict(rope)
    z = dims(c)
    B, L, _ = x.shape
    h = rm._rms(x, w["attn_norm"], z["eps"])
    q = rm._mm(h, w["wq"], fp8).reshape(B, L, z["h"], z["hd"])
    k = rm._mm(h, w["wk"], fp8).reshape(B, L, z["kh"], z["hd"])
    v = rm._mm(h, w["wv"], fp8).reshape(B, L, z["kh"], z["hd"])
    q, k = _rope(q, pos, rope, z["hd"]), _rope(k, pos, rope, z["hd"])
    g = z["h"] // z["kh"]
    o = jax.vmap(lambda a, b, cc: _attend(a, b, cc, window, qblk))(
        q.reshape(B, L, z["kh"], g, z["hd"]), k, v)
    x = x + rm._mm(o.reshape(B, L, z["h"] * z["hd"]), w["wo"], fp8)
    return x + _moe(w, rm._rms(x, w["mlp_norm"], z["eps"]), z, fp8)


# ---- work counts (see workcount) ------------------------------------------

def _item(c: dict) -> int:
    return 2 if c["torch_dtype"] == "bfloat16" else 4


def _attn_params(z) -> int:
    return 2 * z["d"] * z["h"] * z["hd"] + 2 * z["d"] * z["kh"] * z["hd"]


def _expert_params(z) -> int:
    return 3 * z["d"] * z["ff"]


def matmul_params(c: dict) -> int:
    """Weights one token multiplies: attention, router, its k experts, and
    the LM head."""
    z = dims(c)
    layer = _attn_params(z) + z["d"] * z["e"] + z["k"] * _expert_params(z)
    return z["layers"] * layer + z["d"] * z["v"]


def weight_bytes(c: dict) -> int:
    """Every weight held but the embedding table: all experts."""
    z = dims(c)
    return sw.param_bytes(c) - z["v"] * z["d"] * _item(c)


def kv_bytes_per_position(c: dict) -> int:
    z = dims(c)
    return 2 * z["kh"] * z["hd"] * _item(c)


def attn_flops_per_position(c: dict) -> int:
    z = dims(c)
    return 4 * z["h"] * z["hd"]


def _attended(spans, window) -> int:
    """Positions attended by the decoded tokens: the k-th token of a row
    (k = 1..n) after a context of c0 attends min(c0 + k, window)."""
    total = 0
    for c0, n in spans:
        if window is None:
            total += n * c0 + n * (n + 1) // 2
            continue
        below = max(min(n, window - c0), 0)  # tokens whose context fits
        total += below * c0 + below * (below + 1) // 2 + (n - below) * window
    return total


def _attended_all(c: dict, spans) -> int:
    """``_attended`` summed over the layers, each with its window."""
    return sum(_attended(spans, kind(c, i)[1])
               for i in range(c["num_hidden_layers"]))


def distinct_experts(c: dict, rows: float) -> float:
    """Experts per layer that ``rows`` tokens route to, expected under
    uniform routing: E * (1 - (1 - k/E)^rows)."""
    z = dims(c)
    return z["e"] * (1.0 - (1.0 - z["k"] / z["e"]) ** rows)


def expert_flops(c: dict, tokens: int) -> int:
    """The expert FFNs' FLOPs of ``tokens`` tokens over every layer."""
    z = dims(c)
    return 2 * z["layers"] * z["k"] * _expert_params(z) * tokens


def expert_bytes(c: dict, steps: float, tokens: int) -> float:
    """Least bytes of the expert FFNs over ``steps`` steps of ``tokens``
    tokens in all: each step reads its distinct experts' weights once per
    layer, and each routed row's input and output."""
    z = dims(c)
    rows = tokens / steps
    per_step = distinct_experts(c, rows) * _expert_params(z) * _item(c)
    io = 2 * tokens * z["k"] * z["d"] * _item(c)
    return z["layers"] * (per_step * steps + io)


def decode_flops(c: dict, tokens: int, ctx_sum: int, spans=None):
    if spans is None:
        return None
    return (2 * matmul_params(c) * tokens
            + attn_flops_per_position(c) * _attended_all(c, spans))


def decode_bytes(c: dict, steps: float, ctx_sum: int, spans=None):
    if spans is None:
        return None
    z = dims(c)
    tokens = sum(n for _, n in spans)
    held = z["layers"] * z["e"] * _expert_params(z) * _item(c)
    read = (z["layers"] * distinct_experts(c, tokens / steps)
            * _expert_params(z) * _item(c))
    return ((weight_bytes(c) - held + read) * steps
            + kv_bytes_per_position(c) * _attended_all(c, spans))


def paged_decode_bytes(c: dict, ctx_sum: int, tokens: int, spans=None):
    if spans is None:
        return None
    z = dims(c)
    qo = 2 * z["h"] * z["hd"] * _item(c)
    return (kv_bytes_per_position(c) * _attended_all(c, spans)
            + z["layers"] * qo * tokens)


def paged_decode_flops(c: dict, ctx_sum: int, spans=None):
    if spans is None:
        return None
    return attn_flops_per_position(c) * _attended_all(c, spans)
