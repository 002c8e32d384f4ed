"""Mixture-of-Experts FFN: top-k routing, shared experts, EP sharding.

Three dispatch implementations, selectable via ``cfg.moe_impl``:

  * ``einsum`` — GShard-style capacity-factor dispatch/combine einsums over
    token groups.  Robust SPMD sharding behaviour (the dispatch einsums give
    XLA a clean all-to-all pattern) at the cost of ~2*T*E*C*d extra FLOPs.
    This is the paper-era baseline.
  * ``sort``   — argsort-based token permutation into per-expert capacity
    buffers (MegaBlocks-flavoured, scatter/gather instead of one-hot
    matmuls).  Near-zero FLOP overhead; used by the perf hillclimb.
  * ``dropless`` — every (token, expert) pair sorted by expert and run
    through a grouped matmul (``jax.lax.ragged_dot``) over the experts
    held: no capacity, no dropped token, each routed expert's weights
    read once per call.  Holds every expert (one device).

Routing runs under ``jax.named_scope("moe_route")``, the expert FFN under
``jax.named_scope("moe_experts")``, so a profiler trace attributes them.

Expert weights carry a leading E dim with logical axis "experts": sharded on
the "model" mesh axis when ``E % model_size == 0`` (expert parallelism,
deepseek-moe 64e), otherwise replicated with the expert FFN hidden dim
TP-sharded ("expert_mlp", mixtral 8e over 16-way model).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import accum_dtype as _accum, dense, dense_decl
from repro.models.params import ParamDecl
from repro.sharding.partition import constrain

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def moe_decl(cfg) -> dict:
    d, ff, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    decl = {
        "router": dense_decl(d, (e,), "embed", (None,), scale=0.02),
        "experts": {
            "w_gate": ParamDecl((e, d, ff), ("experts", "embed", "expert_mlp"), init="normal"),
            "w_up": ParamDecl((e, d, ff), ("experts", "embed", "expert_mlp"), init="normal"),
            "w_down": ParamDecl((e, ff, d), ("experts", "expert_mlp", "embed"), init="normal"),
        },
    }
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * ff
        decl["shared"] = {
            "w_gate": dense_decl(d, (sf,), "embed", ("mlp",)),
            "w_up": dense_decl(d, (sf,), "embed", ("mlp",)),
            "w_down": dense_decl(sf, (d,), "mlp", ("embed",)),
        }
    return decl


def _expert_ffn(experts, h, act, accum=jnp.float32):
    """h: [E, n, d] -> [E, n, d] through per-expert gated FFN."""
    up = jnp.einsum("end,edf->enf", h, experts["w_up"].astype(h.dtype),
                    preferred_element_type=jnp.float32).astype(h.dtype)
    gate = jnp.einsum("end,edf->enf", h, experts["w_gate"].astype(h.dtype),
                      preferred_element_type=jnp.float32)
    mid = (act(gate) * up.astype(jnp.float32)).astype(h.dtype)
    # under EP both "act_experts" and "act_ff" map to "model"; pspec de-dup
    # keeps the experts axis sharded and leaves ff replicated (and vice versa
    # in TP-expert mode, where "act_experts" maps to None).
    mid = constrain(mid, ("act_experts", None, "act_ff"))
    out = jnp.einsum("enf,efd->end", mid, experts["w_down"].astype(h.dtype),
                     preferred_element_type=accum).astype(h.dtype)
    return out


def _router(params, x, cfg):
    """x: [..., d] -> (gates [..., K], idx [..., K], aux_loss scalar)."""
    logits = dense(params["router"], x.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss: E * sum_e f_e * p_e
    e = cfg.num_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [..., K, E]
    f = onehot.mean(axis=tuple(range(onehot.ndim - 1)))  # fraction per expert (over tokens*K)
    p = probs.mean(axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(f * p)
    return gates, idx, aux


def moe_block(params, x, cfg):
    """x: [B, S, d] -> (y, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    # the capacity paths dispatch per group of tokens; dropless needs none
    g = t if cfg.moe_impl == "dropless" else min(getattr(cfg, "moe_group", 512), t)
    xg = x.reshape(t // g, g, d)  # [G, g, d]; G dim carries the batch sharding
    xg = constrain(xg, ("act_batch", None, "act_embed"))

    with jax.named_scope("moe_route"):
        gates, idx, aux = _router(params, xg, cfg)

    with jax.named_scope("moe_experts"):
        if cfg.moe_impl == "einsum":
            y = _dispatch_einsum(params, xg, gates, idx, cfg)
        elif cfg.moe_impl == "sort":
            y = _dispatch_sort(params, xg, gates, idx, cfg)
        elif cfg.moe_impl == "dropless":
            y = _dispatch_dropless(params, xg, gates, idx, cfg)
        else:
            raise ValueError(f"unknown moe_impl {cfg.moe_impl!r}")

    y = y.reshape(b, s, d)
    if "shared" in params:
        act = _ACTS[cfg.act]
        up = dense(params["shared"]["w_up"], x)
        gate = dense(params["shared"]["w_gate"], x)
        mid = (act(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(x.dtype)
        mid = constrain(mid, ("act_batch", "act_seq", "act_ff"))
        y = y + dense(params["shared"]["w_down"], mid, accum=_accum(cfg))
    y = constrain(y, ("act_batch", "act_seq", "act_embed"))
    return y, aux


def _capacity(g: int, cfg) -> int:
    c = int(g * cfg.experts_per_token / cfg.num_experts * cfg.capacity_factor)
    return max(c, 1)


def _dispatch_einsum(params, xg, gates, idx, cfg):
    """GShard dispatch: [G,g,d] -> [E,G,C,d] -> expert FFN -> combine."""
    G, g, d = xg.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    c = _capacity(g, cfg)
    act = _ACTS[cfg.act]

    oh_e = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [G,g,K,E]
    # position of each (token, k) slot within its expert, token-major priority
    flat = oh_e.reshape(G, g * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1.0  # [G, g*K, E]
    pos = pos.reshape(G, g, k, e)
    pos_in = jnp.sum(pos * oh_e, axis=-1)  # [G,g,K]
    keep = (pos_in < c).astype(jnp.float32)
    oh_c = jax.nn.one_hot(pos_in.astype(jnp.int32), c, dtype=jnp.float32)  # [G,g,K,C]

    combine = jnp.einsum("GsKE,GsKC->GsEC", oh_e * (gates * keep)[..., None], oh_c)
    dispatch = jnp.einsum("GsKE,GsKC->GsEC", oh_e * keep[..., None], oh_c)

    dtype = xg.dtype
    expert_in = jnp.einsum("GsEC,Gsd->EGCd", dispatch.astype(dtype), xg,
                           preferred_element_type=jnp.float32).astype(dtype)
    expert_in = constrain(expert_in, ("act_experts", "act_batch", None, None))
    h = expert_in.reshape(e, G * c, d)
    out = _expert_ffn(params["experts"], h, act,
                      accum=_accum(cfg)).reshape(e, G, c, d)
    out = constrain(out, ("act_experts", "act_batch", None, None))
    y = jnp.einsum("EGCd,GsEC->Gsd", out, combine.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    return y


def _dispatch_sort(params, xg, gates, idx, cfg):
    """Sort-based dispatch: permute token copies into [E, C_e, d] buffers.

    FLOP-clean (no one-hot matmuls); relies on scatter/gather lowering.
    """
    G, g, d = xg.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = G * g
    act = _ACTS[_act_name(cfg)]
    ce = max(int(t * k / e * cfg.capacity_factor), 1)

    x_flat = xg.reshape(t, d)
    flat_e = idx.reshape(t * k)
    flat_gates = gates.reshape(t * k)
    tok_of_slot = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)

    keep = rank < ce
    safe_rank = jnp.where(keep, rank, 0)
    safe_e = jnp.where(keep, flat_e, 0)

    buf = jnp.zeros((e, ce, d), xg.dtype)
    vals = jnp.where(keep[:, None], x_flat[tok_of_slot], 0)
    buf = buf.at[safe_e, safe_rank].add(vals)  # add: dropped slots write 0 to (0,0)
    buf = constrain(buf, ("act_experts", None, None))

    out = _expert_ffn(params["experts"], buf, act, accum=_accum(cfg))  # [E, Ce, d]
    y_slots = out[safe_e, safe_rank] * (flat_gates * keep)[:, None]
    y = jax.ops.segment_sum(y_slots, tok_of_slot, num_segments=t)
    return y.astype(xg.dtype).reshape(G, g, d)


def _dispatch_dropless(params, xg, gates, idx, cfg):
    """Grouped-matmul dispatch: the t*k (token, expert) pairs sorted by
    expert, each expert's rows through its gated FFN by ``ragged_dot``,
    then weighted by their gates back onto their tokens."""
    G, g, d = xg.shape
    k, t = cfg.experts_per_token, G * g
    ex = params["experts"]
    act = _ACTS[_act_name(cfg)]
    flat_e = idx.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)
    rows = xg.reshape(t, d)[order // k]  # [t*k, d], grouped by expert
    sizes = jnp.bincount(flat_e, length=cfg.num_experts).astype(jnp.int32)

    def gmm(lhs, w, accum=jnp.float32):
        return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), sizes,
                                  preferred_element_type=accum)

    mid = (act(gmm(rows, ex["w_gate"])) * gmm(rows, ex["w_up"])).astype(xg.dtype)
    out = gmm(mid, ex["w_down"], _accum(cfg)).astype(jnp.float32)
    out = out[jnp.argsort(order)].reshape(t, k, d)  # back to (token, k)
    y = (out * gates.reshape(t, k, 1).astype(jnp.float32)).sum(axis=1)
    return y.astype(xg.dtype).reshape(G, g, d)


def _act_name(cfg):
    return cfg.act if cfg.act in _ACTS else "silu"
