"""Decoder-only LM stack, generic over layer families.

The stack is a ``jax.lax.scan`` over *units* of stacked layer parameters, so
compile time is independent of depth (88-layer mistral-large compiles as fast
as 2 layers).  A unit is:

  * dense / moe / ssm families: one layer;
  * hybrid (griffin): one super-block following ``cfg.block_pattern``
    (e.g. ("rec","rec","attn")); layers that don't fill a whole super-block
    form a separately-scanned "tail" (recurrentgemma-9b: 12x(r,r,a) + 2r);
  * moe with a ``block_pattern`` of sliding and full layers (mellum2-12b:
    3 x "moe_sliding" + "moe_full"): one period, each sub-layer with its
    own window and rotary embedding (:func:`layer_window`,
    :func:`layer_yarn`) and its own stacked cache.

Three modes share one code path: "train" (no caches), "prefill" (caches
collected as scan outputs) and "decode" (caches threaded through the scan).
Remat (``cfg.remat``) wraps the unit body for training.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, mlp_decl, norm_decl
from repro.models.params import stack_decls
from repro.sharding.partition import constrain

# ----------------------------------------------------------------------
# Structure
# ----------------------------------------------------------------------


# an attention + MoE layer: windowed as configured ("moe"), or in a mixed
# pattern, windowed ("moe_sliding") or attending everything ("moe_full")
MOE_KINDS = ("moe", "moe_sliding", "moe_full")


def unit_kinds(cfg) -> tuple[str, ...]:
    if cfg.family in ("dense", "vlm"):
        return ("dense",)
    if cfg.family == "moe":
        return tuple(cfg.block_pattern) or ("moe",)
    if cfg.family == "ssm":
        return ("ssm",)
    if cfg.family == "hybrid":
        return tuple(cfg.block_pattern)
    raise ValueError(cfg.family)


def layer_window(cfg, kind: str) -> int | None:
    """A layer's attention window: ``cfg.attention_window`` on every
    attention layer but a mixed pattern's full layers."""
    return None if kind == "moe_full" else cfg.attention_window


def layer_yarn(cfg, kind: str):
    """A layer's YaRN parameters: ``cfg.rope_yarn`` on the layers that
    attend without a window, plain RoPE (None) on the others."""
    return cfg.rope_yarn if layer_window(cfg, kind) is None else None


def scan_counts(cfg) -> tuple[int, int]:
    """(number of scanned units, number of remainder tail layers)."""
    k = len(unit_kinds(cfg))
    return cfg.num_layers // k, cfg.num_layers % k


def layer_decl(cfg, kind: str) -> dict:
    if kind == "ssm":
        return {"mamba": ssm_mod.mamba2_decl(cfg)}
    decl = {"ln1": norm_decl(cfg), "ln2": norm_decl(cfg)}
    if kind == "rec":
        decl["rec"] = rglru_mod.griffin_rec_decl(cfg)
        decl["mlp"] = mlp_decl(cfg)
    elif kind in ("dense", "attn"):
        decl["attn"] = attn_mod.attn_decl(cfg)
        decl["mlp"] = mlp_decl(cfg)
    elif kind in MOE_KINDS:
        decl["attn"] = attn_mod.attn_decl(cfg)
        decl["moe"] = moe_mod.moe_decl(cfg)
    else:
        raise ValueError(kind)
    return decl


def unit_decl(cfg) -> dict:
    kinds = unit_kinds(cfg)
    if len(kinds) == 1:
        return layer_decl(cfg, kinds[0])
    return {f"sub{i}": layer_decl(cfg, k) for i, k in enumerate(kinds)}


def stack_decl(cfg) -> dict:
    """Decl for the whole stack: scanned units + optional tail layers."""
    nb, rem = scan_counts(cfg)
    decl = {"units": stack_decls(unit_decl(cfg), nb)}
    if rem:
        # tail = one pseudo-unit of `rem` sub-layers, scanned once (length-1
        # stack keeps the params/caches structurally uniform with `units`)
        kinds = unit_kinds(cfg)[:rem]
        tail = {f"sub{i}": layer_decl(cfg, k) for i, k in enumerate(kinds)}
        decl["tail"] = stack_decls(tail, 1)
    return decl


# ----------------------------------------------------------------------
# Cache specs
# ----------------------------------------------------------------------


def layer_cache_spec(cfg, kind: str, batch: int, max_len: int, dtype):
    if kind == "ssm":
        return ssm_mod.mamba2_state_spec(cfg, batch, dtype)
    if kind == "rec":
        return rglru_mod.griffin_rec_state_spec(cfg, batch, dtype)
    return attn_mod.init_cache_spec(cfg, batch, max_len, dtype,
                                    layer_window(cfg, kind))


def layer_cache_axes(kind: str):
    if kind == "ssm":
        return ssm_mod.MAMBA2_STATE_AXES
    if kind == "rec":
        return rglru_mod.GRIFFIN_REC_STATE_AXES
    return attn_mod.CACHE_AXES


def _stack_spec(spec, n):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), spec
    )


def _stack_axes(axes, n):
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)
    return jax.tree.map(lambda a: ("layers",) + a, axes, is_leaf=is_axes)


def stack_cache_spec(cfg, batch: int, max_len: int, dtype):
    kinds = unit_kinds(cfg)
    nb, rem = scan_counts(cfg)
    if len(kinds) == 1:
        unit = layer_cache_spec(cfg, kinds[0], batch, max_len, dtype)
    else:
        unit = {
            f"sub{i}": layer_cache_spec(cfg, k, batch, max_len, dtype)
            for i, k in enumerate(kinds)
        }
    spec = {"units": _stack_spec(unit, nb)}
    if rem:
        tail = {
            f"sub{i}": layer_cache_spec(cfg, k, batch, max_len, dtype)
            for i, k in enumerate(kinds[:rem])
        }
        spec["tail"] = _stack_spec(tail, 1)
    return spec


def stack_cache_axes(cfg):
    kinds = unit_kinds(cfg)
    nb, rem = scan_counts(cfg)
    if len(kinds) == 1:
        unit = layer_cache_axes(kinds[0])
    else:
        unit = {f"sub{i}": layer_cache_axes(k) for i, k in enumerate(kinds)}
    axes = {"units": _stack_axes(unit, nb)}
    if rem:
        tail = {f"sub{i}": layer_cache_axes(k) for i, k in enumerate(kinds[:rem])}
        axes["tail"] = _stack_axes(tail, 1)
    return axes


# ---- paged variants: attention K/V lives in a shared block pool; ssm/rec
# state stays per-slot (it is O(1) per request — nothing to page) ----


def _layer_paged_spec(cfg, kind, num_slots, num_blocks, block_size, dtype):
    if kind in ("ssm", "rec"):
        return layer_cache_spec(cfg, kind, num_slots, 0, dtype)
    return attn_mod.paged_cache_spec(cfg, num_blocks, block_size, dtype)


def _layer_paged_mask(cfg, kind, dtype):
    if kind in ("ssm", "rec"):
        return jax.tree.map(lambda _: False, layer_cache_spec(cfg, kind, 1, 1, dtype))
    return attn_mod.paged_leaf_mask(cfg)


def _layer_paged_axes(cfg, kind: str):
    if kind in ("ssm", "rec"):
        return layer_cache_axes(kind)
    return attn_mod.paged_cache_axes(cfg)


def _per_unit(cfg, kinds, fn):
    if len(kinds) == 1:
        return fn(kinds[0])
    return {f"sub{i}": fn(k) for i, k in enumerate(kinds)}


def stack_paged_cache_spec(cfg, num_slots, num_blocks, block_size, dtype):
    """Like :func:`stack_cache_spec` but with pooled attention storage:
    attn leaves ``[layers, num_blocks, block_size, Kh, D]``, recurrent
    leaves ``[layers, num_slots, ...]`` (slot-indexed as before)."""
    kinds = unit_kinds(cfg)
    nb, rem = scan_counts(cfg)
    mk = lambda k: _layer_paged_spec(cfg, k, num_slots, num_blocks, block_size, dtype)
    spec = {"units": _stack_spec(_per_unit(cfg, kinds, mk), nb)}
    if rem:
        spec["tail"] = _stack_spec(_per_unit(cfg, kinds[:rem], mk), 1)
    return spec


def stack_paged_leaf_mask(cfg, dtype):
    """Bool tree matching the cache structure: True = leaf is pooled
    (block-addressed), False = leaf stays slot-indexed."""
    kinds = unit_kinds(cfg)
    _, rem = scan_counts(cfg)
    mk = lambda k: _layer_paged_mask(cfg, k, dtype)
    mask = {"units": _per_unit(cfg, kinds, mk)}
    if rem:
        mask["tail"] = _per_unit(cfg, kinds[:rem], mk)
    return mask


def stack_paged_cache_axes(cfg):
    """Logical-axes tree matching :func:`stack_paged_cache_spec` — what the
    serve engine hands to ``Rules.tree_shardings`` to place the pooled KV
    leaves (kv-head sharded when divisible) and the slot-indexed recurrent
    leaves (replicated batch) on the mesh."""
    kinds = unit_kinds(cfg)
    _, rem = scan_counts(cfg)
    mk = lambda k: _layer_paged_axes(cfg, k)
    axes = {"units": _stack_axes(_per_unit(cfg, kinds, mk), 0)}
    if rem:
        axes["tail"] = _stack_axes(_per_unit(cfg, kinds[:rem], mk), 0)
    return axes


# ----------------------------------------------------------------------
# Apply
# ----------------------------------------------------------------------


def apply_layer(params, x, cfg, kind, *, positions, cache, index, cache_len=None,
                block_tables=None, ring=True, row_len=None):
    """One layer. Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h = apply_norm(params["mamba"]["norm"], x, cfg.norm_eps)
        y, new_cache = ssm_mod.mamba2_block(params["mamba"], h, cfg, state=cache)
        return x + y, new_cache, aux

    h = apply_norm(params["ln1"], x, cfg.norm_eps)
    if kind == "rec":
        y, new_cache = rglru_mod.griffin_rec_block(params["rec"], h, cfg, state=cache)
    else:
        y, new_cache = attn_mod.attention_block(
            params["attn"], h, cfg, positions=positions, cache=cache,
            index=index, window=layer_window(cfg, kind), causal=cfg.causal,
            use_rope=cfg.use_rope, cache_len=cache_len,
            block_tables=block_tables, ring=ring, row_len=row_len,
            yarn=layer_yarn(cfg, kind),
        )
    x = x + y
    x = constrain(x, ("act_batch", "act_seq_resid", "act_embed"))

    h = apply_norm(params["ln2"], x, cfg.norm_eps)
    if kind in MOE_KINDS:
        y, aux = moe_mod.moe_block(params["moe"], h, cfg)
    else:
        y = apply_mlp(params["mlp"], h, cfg)
    x = x + y
    x = constrain(x, ("act_batch", "act_seq_resid", "act_embed"))
    return x, new_cache, aux


def apply_unit(params, x, cfg, kinds, *, positions, cache, index, cache_len=None,
               block_tables=None, ring=True, row_len=None):
    aux = jnp.zeros((), jnp.float32)
    if len(kinds) == 1:
        return apply_layer(params, x, cfg, kinds[0], positions=positions,
                           cache=cache, index=index, cache_len=cache_len,
                           block_tables=block_tables, ring=ring, row_len=row_len)
    new_cache = {}
    for i, kind in enumerate(kinds):
        sub = f"sub{i}"
        x, c, a = apply_layer(
            params[sub], x, cfg, kind, positions=positions,
            cache=None if cache is None else cache[sub], index=index,
            cache_len=cache_len, block_tables=block_tables, ring=ring,
            row_len=row_len,
        )
        new_cache[sub] = c
        aux = aux + a
    return x, new_cache, aux


_REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def apply_stack(params, x, cfg, *, positions, caches=None, index=None, mode="train",
                cache_len=None, block_tables=None, ring=True, row_len=None):
    """Run the whole stack.  Returns (x, new_caches_or_None, aux).

    ``block_tables`` routes decode-time attention through the pooled paged
    cache; ``ring=False`` makes prefill keep full-length K/V under SWA
    (paged storage holds absolute positions).  "decode" mode also serves
    chunked tail prefill (caches given, ``index=None``, Sq > 1) and — with
    ``row_len`` [B] given — per-row query spans for the unified serve step
    (row b: ``row_len[b]`` tokens at absolute positions ``index[b] + j``).
    """
    kinds = unit_kinds(cfg)
    nb, rem = scan_counts(cfg)

    def run(stack_params, stack_caches, x, aux, sub_kinds):
        if mode == "train":
            def body(carry, p):
                xc, auxc = carry
                xo, _, a = apply_unit(p, xc, cfg, sub_kinds, positions=positions,
                                      cache=None, index=index, cache_len=cache_len)
                return (xo, auxc + a), None

            if cfg.remat != "none":
                policy = _REMAT_POLICIES[cfg.remat]
                body = jax.checkpoint(body, policy=policy, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux), stack_params)
            return x, None, aux
        if mode == "prefill":
            def body(carry, p):
                xc, auxc = carry
                xo, cache_out, a = apply_unit(p, xc, cfg, sub_kinds, positions=positions,
                                              cache=None, index=index,
                                              cache_len=cache_len, ring=ring)
                return (xo, auxc + a), cache_out

            (x, aux), caches_out = jax.lax.scan(body, (x, aux), stack_params)
            return x, caches_out, aux
        # decode (and chunked prefill: index=None, caches = gathered prefix)
        def body(carry, inp):
            xc, auxc = carry
            p, c = inp
            xo, cache_out, a = apply_unit(p, xc, cfg, sub_kinds, positions=positions,
                                          cache=c, index=index, cache_len=cache_len,
                                          block_tables=block_tables, row_len=row_len)
            return (xo, auxc + a), cache_out

        (x, aux), caches_out = jax.lax.scan(body, (x, aux), (stack_params, stack_caches))
        return x, caches_out, aux

    aux = jnp.zeros((), jnp.float32)
    unit_caches = None if caches is None else caches.get("units")
    x, new_unit_caches, aux = run(params["units"], unit_caches, x, aux, kinds)

    new_caches = None
    if mode != "train":
        new_caches = {"units": new_unit_caches}
    if rem:
        tail_caches = None if caches is None else caches.get("tail")
        x, new_tail, aux = run(params["tail"], tail_caches, x, aux, kinds[:rem])
        if mode != "train":
            new_caches["tail"] = new_tail
    return x, new_caches, aux
