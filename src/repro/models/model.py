"""Model facade: build_model(cfg) -> object with init / loss / prefill / decode.

All ten assigned architectures resolve to one of two classes:

  * :class:`DecoderLM`  — dense, moe, ssm, hybrid, vlm families
  * :class:`EncDecLM`   — whisper (encoder stub + decoder)

Every entry point comes with matching *_specs / *_axes methods producing
``ShapeDtypeStruct`` trees and logical-axis trees, which is all the multi-pod
dry-run needs (no allocation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import attention as att_mod
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf_mod
from repro.models.layers import (
    apply_norm, dense, dense_decl, embed_tokens, embedding_decl, lm_logits,
    norm_decl,
)
from repro.models.params import (
    abstract_params, init_params, logical_axes, param_bytes, param_count,
)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def build_model(cfg: ModelConfig):
    if cfg.family == "encdec":
        return EncDecLM(cfg)
    return DecoderLM(cfg)


def token_xent(logits, targets, mask, z_coef: float = 0.0):
    """Masked token cross-entropy over (possibly padded/sharded) vocab."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    vi = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    pick = jnp.sum(jnp.where(vi == targets[..., None], lg, 0.0), axis=-1)
    nll = lse - pick
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    xent = jnp.sum(nll * mask) / denom
    z = jnp.sum(jnp.square(lse) * mask) / denom
    return xent + z_coef * z, {"xent": xent, "z_loss": z}


class _Base:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = _DTYPES[cfg.dtype]
        self._decl = self.decl()

    # ---- parameters ----
    def init(self, key, shardings=None):
        """Random parameters in the model dtype; ``shardings`` places each
        leaf as it is made (see :func:`repro.models.params.init_params`)."""
        return init_params(key, self._decl, self.dtype, shardings)

    def abstract_params(self):
        return abstract_params(self._decl, self.dtype)

    def param_axes(self):
        return logical_axes(self._decl)

    def param_count(self) -> int:
        return param_count(self._decl)

    def param_bytes(self) -> int:
        return param_bytes(self._decl, self.dtype)

    # ---- shape plumbing shared by dryrun/tests ----
    def batch_specs(self, shape: ShapeSpec) -> dict:
        raise NotImplementedError

    def batch_axes(self) -> dict:
        raise NotImplementedError


class DecoderLM(_Base):
    """Decoder-only LM over the generic family stack."""

    def decl(self):
        cfg = self.cfg
        d = {
            "embed": embedding_decl(cfg),
            "stack": tf_mod.stack_decl(cfg),
            "final_norm": norm_decl(cfg),
        }
        if cfg.family == "vlm":
            d["vision_proj"] = dense_decl(
                cfg.vision_dim, (cfg.d_model,), None, ("embed",), bias=True
            )
        return d

    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = embed_tokens(params["embed"], batch["tokens"], self.dtype)
        if cfg.family == "vlm":
            patches = dense(params["vision_proj"], batch["patch_embeds"].astype(self.dtype))
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def forward(self, params, batch, mode="train", cache_len=None, ring=True):
        """-> (logits, caches_or_None, aux)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        positions = np.arange(x.shape[1], dtype=np.int32)
        x, caches, aux = tf_mod.apply_stack(
            params["stack"], x, cfg, positions=positions, mode=mode,
            cache_len=cache_len, ring=ring,
        )
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_logits(params["embed"], x, cfg)
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return logits, caches, aux

    def loss(self, params, batch, z_coef: float = 0.0):
        cfg = self.cfg
        logits, _, aux = self.forward(params, batch, mode="train")
        if cfg.family == "vlm":
            logits = logits[:, cfg.num_patches:]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(batch["targets"].shape, jnp.float32)
        xent, metrics = token_xent(logits, batch["targets"], mask, z_coef)
        loss = xent + cfg.router_aux_coef * aux
        metrics["aux_loss"] = aux
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len=None, ring=True):
        """-> (caches, last_logits [B, V]).  ``max_len`` sets the cache
        capacity (defaults to the prompt length).  ``ring=False`` keeps
        full-length K/V even under SWA (paged prefill: the pool stores
        absolute positions and the window is enforced by masking)."""
        logits, caches, _ = self.forward(params, batch, mode="prefill",
                                         cache_len=max_len, ring=ring)
        return caches, logits[:, -1]

    def prefill_chunk(self, params, batch, prefix, start: int):
        """Tail prefill after a prefix-cache hit: only ``batch["tokens"]``
        (the prompt TAIL, positions start..start+S-1) runs through the
        stack; ``prefix`` carries the gathered K/V of positions [0, start).
        -> (tail_caches [layers, B, S, ...], last_logits [B, V])."""
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), \
            "chunked prefill requires attention-only caches"
        tokens = batch["tokens"]
        x = embed_tokens(params["embed"], tokens, self.dtype)
        positions = np.arange(start, start + tokens.shape[1], dtype=np.int32)
        x, tail_caches, _ = tf_mod.apply_stack(
            params["stack"], x, cfg, positions=positions, caches=prefix,
            index=None, mode="decode",
        )
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_logits(params["embed"], x, cfg)
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return tail_caches, logits[:, -1]

    def span_step(self, params, caches, tokens, row_start, row_len,
                  block_tables, *, micro_batches: int = 1):
        """Per-row query spans through the paged pool: the chunked-prefill
        half of the unified serve step.  tokens: [B, Q] int32 — row ``b``
        holds ``row_len[b]`` valid tokens at absolute positions
        ``row_start[b] + j`` (padding columns are scattered into the NULL
        block and produce garbage logits the caller discards).  Requires an
        attention-only stack (recurrent/cross state cannot be chunk-resumed).
        ``micro_batches > 1`` runs the rows as contiguous groups through
        :func:`repro.models.attention.span_pipeline` (communication/compute
        overlap under tensor parallelism — bit-identical by construction).
        -> (new_caches, logits [B, Q, V])."""
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), \
            "span_step requires attention-only caches"
        row_start = jnp.asarray(row_start, jnp.int32)
        row_len = jnp.asarray(row_len, jnp.int32)

        def one_span(caches, tokens, row_start, row_len, block_tables):
            x = embed_tokens(params["embed"], tokens, self.dtype,
                             method=cfg.decode_embed_lookup)
            positions = row_start[:, None] + jnp.arange(
                tokens.shape[1], dtype=jnp.int32)[None, :]
            x, new_caches, _ = tf_mod.apply_stack(
                params["stack"], x, cfg, positions=positions, caches=caches,
                index=row_start, mode="decode", block_tables=block_tables,
                row_len=row_len,
            )
            x = apply_norm(params["final_norm"], x, cfg.norm_eps)
            logits = lm_logits(params["embed"], x, cfg)
            if cfg.logit_softcap:
                c = cfg.logit_softcap
                logits = jnp.tanh(logits / c) * c
            return new_caches, logits

        return att_mod.span_pipeline(
            one_span, caches, (tokens, row_start, row_len, block_tables),
            micro_batches=micro_batches)

    def decode_step(self, params, caches, tokens, index, block_tables=None):
        """tokens: [B] int32; index: int32 absolute position — scalar
        (lockstep batch) or [B] (per-slot positions, continuous batching).
        ``block_tables`` ([B, W] int32) switches attention layers to the
        pooled paged cache layout."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens[:, None], self.dtype,
                         method=cfg.decode_embed_lookup)
        index = jnp.asarray(index, jnp.int32)
        positions = index[:, None] if index.ndim else jnp.full((1,), index, jnp.int32)
        x, new_caches, _ = tf_mod.apply_stack(
            params["stack"], x, cfg, positions=positions, caches=caches,
            index=index, mode="decode", block_tables=block_tables,
        )
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_logits(params["embed"], x, cfg)
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return new_caches, logits[:, 0]

    # ------------------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int):
        return tf_mod.stack_cache_spec(self.cfg, batch, max_len, self.dtype)

    def cache_axes(self):
        return tf_mod.stack_cache_axes(self.cfg)

    def paged_cache_specs(self, num_slots: int, num_blocks: int, block_size: int):
        """Cache tree with attention K/V pooled into ``num_blocks`` blocks;
        recurrent state (ssm/rec leaves) stays slot-indexed."""
        return tf_mod.stack_paged_cache_spec(
            self.cfg, num_slots, num_blocks, block_size, self.dtype)

    def paged_leaf_mask(self):
        """Bool tree: True where the cache leaf is block-pooled."""
        return tf_mod.stack_paged_leaf_mask(self.cfg, self.dtype)

    def paged_cache_axes(self):
        """Logical-axes tree matching :meth:`paged_cache_specs` (serve-mesh
        placement of the pooled/recurrent decode state)."""
        return tf_mod.stack_paged_cache_axes(self.cfg)

    def fully_paged(self) -> bool:
        """True when EVERY cache leaf is pooled — the precondition for
        prefix reuse (a prefix hit must restore the complete layer state)."""
        return all(jax.tree.leaves(self.paged_leaf_mask()))

    def batch_specs(self, shape: ShapeSpec) -> dict:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
        s_text = s - (cfg.num_patches if cfg.family == "vlm" else 0)
        specs = {"tokens": jax.ShapeDtypeStruct((b, s_text), jnp.int32)}
        if cfg.family == "vlm":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, cfg.vision_dim), self.dtype
            )
        if shape.kind == "train":
            specs["targets"] = jax.ShapeDtypeStruct((b, s_text), jnp.int32)
            specs["loss_mask"] = jax.ShapeDtypeStruct((b, s_text), jnp.float32)
        return specs

    def batch_axes(self) -> dict:
        cfg = self.cfg
        axes = {
            "tokens": ("act_batch", None),
            "targets": ("act_batch", None),
            "loss_mask": ("act_batch", None),
        }
        if cfg.family == "vlm":
            axes["patch_embeds"] = ("act_batch", None, None)
        return axes


class EncDecLM(_Base):
    """Whisper-style encoder-decoder (encoder frontend stubbed)."""

    def decl(self):
        return encdec_mod.encdec_decl(self.cfg)

    def forward(self, params, batch, mode="train", cache_len=None):
        cfg = self.cfg
        enc = encdec_mod.encode(params, batch["frames"].astype(self.dtype), cfg)
        tokens = batch["tokens"]
        positions = np.arange(tokens.shape[1], dtype=np.int32)
        x = encdec_mod.decoder_embed(params, tokens, positions, cfg, self.dtype)
        x, caches = encdec_mod.decode_stack(
            params, x, cfg, positions=positions, enc_out=enc, mode=mode,
            cache_len=cache_len,
        )
        logits = encdec_mod.decoder_logits(params, x, cfg)
        return logits, caches, jnp.zeros((), jnp.float32)

    def loss(self, params, batch, z_coef: float = 0.0):
        logits, _, _ = self.forward(params, batch, mode="train")
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(batch["targets"].shape, jnp.float32)
        xent, metrics = token_xent(logits, batch["targets"], mask, z_coef)
        metrics["aux_loss"] = jnp.zeros((), jnp.float32)
        metrics["loss"] = xent
        return xent, metrics

    def prefill(self, params, batch, max_len=None, ring=True):
        logits, caches, _ = self.forward(params, batch, mode="prefill",
                                         cache_len=max_len)
        return caches, logits[:, -1]

    def decode_step(self, params, caches, tokens, index, block_tables=None):
        cfg = self.cfg
        index = jnp.asarray(index, jnp.int32)
        positions = index[:, None] if index.ndim else jnp.full((1,), index, jnp.int32)
        x = encdec_mod.decoder_embed(params, tokens[:, None], positions, cfg, self.dtype)
        x, new_caches = encdec_mod.decode_stack(
            params, x, cfg, positions=positions, caches=caches, index=index,
            mode="decode", block_tables=block_tables,
        )
        logits = encdec_mod.decoder_logits(params, x, cfg)
        return new_caches, logits[:, 0]

    def cache_specs(self, batch: int, max_len: int):
        return encdec_mod.decoder_cache_spec(self.cfg, batch, max_len, self.dtype)

    def cache_axes(self):
        return encdec_mod.decoder_cache_axes()

    def paged_cache_specs(self, num_slots: int, num_blocks: int, block_size: int):
        return encdec_mod.decoder_paged_cache_spec(
            self.cfg, num_slots, num_blocks, block_size, self.dtype)

    def paged_leaf_mask(self):
        return encdec_mod.decoder_paged_leaf_mask()

    def paged_cache_axes(self):
        return encdec_mod.decoder_paged_cache_axes()

    def fully_paged(self) -> bool:
        return False  # cross-attention K/V is slot-resident

    def batch_specs(self, shape: ShapeSpec) -> dict:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
        specs = {
            "frames": jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), self.dtype),
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        if shape.kind == "train":
            specs["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            specs["loss_mask"] = jax.ShapeDtypeStruct((b, s), jnp.float32)
        return specs

    def batch_axes(self) -> dict:
        return {
            "frames": ("act_batch", None, None),
            "tokens": ("act_batch", None),
            "targets": ("act_batch", None),
            "loss_mask": ("act_batch", None),
        }
