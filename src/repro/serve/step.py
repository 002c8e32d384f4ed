"""Unified token-budget serve step: chunked prefill + decode in one batch.

:class:`UnifiedServeEngine` collapses the legacy engine's two jitted paths —
grouped same-length prefill and K-step decode bursts — into ONE scheduler
iteration under a configurable token budget (``max_step_tokens``):

  * every decode-active slot gets 1 token;
  * the remainder of the budget goes to prefill **chunks**: up to
    ``chunk_rows`` in-flight prompts (admitted or preemption-resumed)
    stream fixed-size ``chunk_size`` slices into the paged pool over
    several iterations, interleaved with decode — a long prompt no longer
    head-of-line-blocks the active decode slots, and the chunk shape
    ``[chunk_rows, chunk_size]`` is the ONLY prefill compile shape (the
    legacy engine mints one executable per distinct prompt length);
  * one jitted :meth:`UnifiedServeEngine._unified_impl` executes the whole
    mixed batch — the decode sub-batch scans exactly like the legacy burst
    (bit-identical math by construction) and the chunk sub-batch runs the
    per-row query-span attention path
    (:func:`repro.models.attention._paged_span_attend`), scattering into the
    pool and sampling ONLY rows that completed their prompt.

Block allocation is just-in-time per chunk: admission demands blocks for the
request's FIRST chunk only (+1 decode headroom), later chunks allocate as
they stream, and a dry pool preempts decode slots newest-first exactly like
the legacy engine.  Prefix-cache hits skip whole leading chunks (the cursor
starts at the hit boundary); full prompt blocks are registered when the
prompt completes, so a preemption-resumed request re-hits its own prompt.

Chunked streaming requires an attention-only, fully-paged stack (dense/moe —
the same gate as the prefix cache): recurrent and cross-attention state
cannot be chunk-resumed, and MoE capacity dispatch couples tokens across the
batch (drop-free at test scale, see docs/chunked_prefill.md).  Other
families keep budget-looped whole-prompt admission through the inherited
grouped-prefill path while their decode flows through the unified step.

Every budget decision is a first-class trace event: per-iteration
``EV_STEP_BUDGET`` / ``EV_CHUNK_TOKENS`` / ``EV_DECODE_TOKENS`` counters
paint the prefill/decode interleave straight into the ``.prv``/chrome
timeline.  The legacy two-path :class:`ContinuousServeEngine` survives as
the equivalence oracle — greedy decode through the unified step must match
it bit-for-bit (tests/test_serve_unified.py).

**Speculative decoding** (``spec=`` a :mod:`repro.serve.spec` proposer)
refactors the decode lane once more, from fixed one-token steps to
variable-width verified spans: each decode-active slot proposes up to
``K`` draft tokens, and ONE span pass per dispatch scores all ``K + 1``
positions per slot — the same :func:`_paged_span_attend` path the chunk
sub-batch uses, so draft verification and chunked prefill ride one
executable.  On-device accept/reject
(:func:`repro.core.sampling.spec_accept`: greedy longest-argmax-prefix,
Leviathan rejection sampling for temperature > 0) commits the accepted
prefix plus one correction/bonus token.  Rejected drafts leave garbage
K/V in the pool, which is provably inert: the committed frontier never
passes a garbage position without overwriting it first (the next span
starts at the frontier and spans are contiguous), and absolute-position
causal masking keeps queries from ever weighting positions past their
own span.  Trailing blocks holding ONLY rejected-draft garbage are rolled
back to the pool after each dispatch; draft + verify positions are
charged against ``max_step_tokens``, and the per-dispatch
``EV_SPEC_DRAFTED`` / ``EV_SPEC_ACCEPTED`` / ``EV_SPEC_K`` counter triple
makes the draft economy a first-class trace.  Greedy spec decode is
bit-identical to the non-spec unified engine (tests/test_serve_spec.py).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core.sampling import fork_key, sample_logits, spec_accept
from repro.core.tracer import span
from repro.serve.block_pool import NULL_BLOCK
from repro.serve.engine import ContinuousServeEngine
from repro.serve.queue import Request, _now_ns


@dataclasses.dataclass
class ChunkPlan:
    """One prefill chunk scheduled into the current unified step."""
    slot: int
    req: Request
    start: int  # absolute position of the chunk's first token
    length: int  # valid tokens (<= chunk_size)
    tokens: np.ndarray  # [length] int32
    sample: bool  # True when this chunk completes the prompt
    # fork children adopted into free slots when this chunk completed a
    # fan-out parent's prompt — the fetch side appends each child's first
    # token from its own fan column (serve/queue.py fork_children)
    forked: list[Request] = dataclasses.field(default_factory=list)


class UnifiedServeEngine(ContinuousServeEngine):
    """Continuous batching through the unified token-budget step."""

    def __init__(self, cfg, params, *, max_step_tokens: int | None = None,
                 chunk_size: int | None = None, chunk_rows: int = 2,
                 mixed_burst: int = 4, spec=None, spec_k: int = 4,
                 spec_adaptive: bool = False, **kwargs):
        super().__init__(cfg, params, **kwargs)
        self.chunk_size = int(chunk_size or max(2 * self.block_size, 16))
        if self.chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        # chunk_rows: concurrent prefill streams per step (the chunk
        # sub-batch is [chunk_rows, chunk_size]); mixed_burst: decode steps
        # scanned in a chunk-carrying dispatch (1 = strict one-iteration
        # steps; higher amortizes dispatch overhead — the chunk rides the
        # first iteration of the burst)
        self.chunk_rows = max(1, int(chunk_rows))
        self.mixed_burst = max(1, min(int(mixed_burst), self.max_decode_burst))
        self.max_step_tokens = int(
            max_step_tokens
            or (self.num_slots + self.chunk_size * self.chunk_rows))
        if self.max_step_tokens < self.num_slots:
            # decode slots always get their token; with budget >= num_slots a
            # pending prefill (which itself occupies a non-decoding slot) is
            # guaranteed >= 1 chunk token per iteration — no starvation
            raise ValueError(
                f"max_step_tokens {self.max_step_tokens} < num_slots "
                f"{self.num_slots}: decode alone would overrun the budget")
        self.chunkable = (self.pool is not None and self.model.fully_paged()
                          and cfg.family in ("dense", "moe"))
        # per-slot prefill cursors (chunked streaming state)
        self._progress = np.zeros((self.num_slots,), np.int64)
        self._target = np.zeros((self.num_slots,), np.int64)
        self._prefilling = np.zeros((self.num_slots,), bool)
        # whole-prompt tokens prefilled since the last dispatch (non-chunkable
        # families) — folded into the next dispatch's counter triple so the
        # one-triple-per-iteration cadence holds for every engine config
        self._whole_tokens = 0
        if self.tracer is not None:
            for code in (ev.EV_STEP_BUDGET, ev.EV_CHUNK_TOKENS,
                         ev.EV_DECODE_TOKENS):
                self.tracer.register(code, ev.SERVE_CTR_LABELS[code])
            self.tracer.register(
                ev.EV_FORK, "CoW fork: child stream minted (parent rid+1)")
        if self.meshstate is not None:
            r = self.meshstate.replicated
            self._unified = jax.jit(
                self._unified_impl, donate_argnums=(1,),  # caches
                static_argnames=("steps", "chunk"),
                out_shardings=(self._cache_sh, r, r, r, r))
            self._beam_prefill = jax.jit(
                self._beam_prefill_impl, donate_argnums=(1,),
                static_argnames=("width",),
                out_shardings=(self._cache_sh, r, r))
            self._beam_step = jax.jit(
                self._beam_step_impl, donate_argnums=(1,),
                static_argnames=("width",),
                out_shardings=(self._cache_sh, r, r))
        else:
            self._unified = jax.jit(self._unified_impl, donate_argnums=(1,),
                                    static_argnames=("steps", "chunk"))
            self._beam_prefill = jax.jit(self._beam_prefill_impl,
                                         donate_argnums=(1,),
                                         static_argnames=("width",))
            self._beam_step = jax.jit(self._beam_step_impl,
                                      donate_argnums=(1,),
                                      static_argnames=("width",))
        # --- speculative decoding: draft/verify spans through the span path
        self.spec = spec
        self.spec_k_max = max(1, int(spec_k))
        self.spec_adaptive = bool(spec_adaptive)
        self._spec_k = self.spec_k_max  # current width (adaptive shrinks it)
        self._accept_ema = 1.0  # optimistic start: first dispatches run wide
        if spec is not None:
            if not self.chunkable:
                raise ValueError(
                    "speculative decoding needs the fully-paged span path "
                    f"(dense/moe families); {cfg.family!r} cannot run it")
            self.stats.update(spec_dispatches=0, spec_drafted=0,
                              spec_accepted=0, spec_rollback_blocks=0)
            if self.tracer is not None:
                for code in (ev.EV_SPEC_DRAFTED, ev.EV_SPEC_ACCEPTED,
                             ev.EV_SPEC_K):
                    self.tracer.register(code, ev.SERVE_CTR_LABELS[code])
            if self.meshstate is not None:
                r = self.meshstate.replicated
                self._spec_step = jax.jit(
                    self._spec_impl, donate_argnums=(1,),  # caches
                    static_argnames=("chunk",),
                    out_shardings=(self._cache_sh, r, r, r, r, r))
            else:
                self._spec_step = jax.jit(self._spec_impl, donate_argnums=(1,),
                                          static_argnames=("chunk",))

    @property
    def supports_fork(self) -> bool:
        # n-way fan-out rides the chunk-sampling fork path (sibling fan
        # columns + slot adoption at prompt completion) — chunkable configs
        # only; other families inherit the base class's loud rejection
        return self.chunkable

    # ------------------------------------------------------------------
    # the jitted mixed-batch step
    # ------------------------------------------------------------------
    def _unified_impl(self, params, caches, tok, idx, active, tables,
                      ck_tokens, ck_start, ck_len, ck_slot, ck_sample, key,
                      *, steps, chunk):
        """One token-budget iteration in ONE executable.

        Decode sub-batch: ``steps`` scanned iterations over the slot pool,
        byte-equivalent to the legacy burst for active rows; inactive rows'
        block tables are masked to the NULL block so a mid-prefill slot's
        stale registers can never scribble on blocks its chunks are
        streaming into.  Chunk sub-batch (``chunk=True``): up to
        ``chunk_rows`` span rows scatter into the pool (slots disjoint from
        every decode write) and sample only where ``ck_sample`` marks a
        completed prompt; each sampled first token and its decode position
        are folded into the slot registers on device — the slot starts
        decoding next dispatch without a host round-trip.
        """
        bt = (jnp.where(active[:, None], tables, NULL_BLOCK)
              if self._has_paged else None)
        if steps:
            with jax.named_scope("decode"):
                caches, tok, idx, toks = self._decode_scan(
                    params, caches, tok, idx, active, bt, key, steps)
        else:
            toks = jnp.zeros((0, self.num_slots), jnp.int32)

        ck_fan = jnp.zeros(ck_start.shape + (self.num_slots,), jnp.int32)
        if chunk:
            with jax.named_scope("chunk"):
                ck_tables = tables[ck_slot]  # [C, W]
                caches, logits = self.model.span_step(
                    params, caches, ck_tokens, ck_start, ck_len, ck_tables,
                    micro_batches=self.overlap.micro_batches)
                tok, idx, ck_fan = self._fold_chunk_rows(
                    logits, ck_start, ck_len, ck_slot, ck_sample, key, tok,
                    idx)
        return caches, tok, idx, toks, ck_fan

    def _fold_chunk_rows(self, logits, ck_start, ck_len, ck_slot, ck_sample,
                         key, tok, idx):
        """Sample completed-prompt chunk rows and fold their first token +
        decode position into the slot registers — the trickiest on-device
        logic in the engine, shared verbatim by the unified and spec
        executables (exact: <= 1 chunk per slot per step, int one-hot
        sum)."""
        last = jnp.take_along_axis(
            logits, jnp.maximum(ck_len - 1, 0)[:, None, None], axis=1)[:, 0]
        ck_key = (key if self.temperature <= 0.0
                  else jax.random.fold_in(key, 1 << 18))
        ck_tok = sample_logits(last, ck_key, self.temperature,
                               self.cfg.vocab_size, self.top_k, self.top_p)
        # sibling fan: column i of ck_fan is the first token fork-child i
        # would start with (n-way sampling forks at prompt completion).
        # Column 0 IS the ck_tok sample above — key derivation untouched,
        # so the parent stream stays bit-identical to an unforked run;
        # sibling columns draw from per-fork keys (core/sampling.fork_key)
        # and greedy columns all collapse to the same argmax.  The extra
        # samples cost C * S categoricals per dispatch — noise next to the
        # span matmuls — and keep the executable's shape independent of
        # how many forks the host actually seats.
        fan = [ck_tok]
        for i in range(1, self.num_slots):
            fan.append(sample_logits(last, fork_key(ck_key, i),
                                     self.temperature, self.cfg.vocab_size,
                                     self.top_k, self.top_p))
        ck_fan = jnp.stack(fan, axis=1)  # [C, S]
        onehot = ((ck_slot[:, None] == jnp.arange(self.num_slots)[None, :])
                  & ck_sample[:, None])  # [C, S]
        hit = onehot.any(axis=0)
        tok = jnp.where(hit, (onehot * ck_tok[:, None]).sum(0)
                        .astype(tok.dtype), tok)
        idx = jnp.where(hit, (onehot * (ck_start + ck_len)[:, None]).sum(0)
                        .astype(idx.dtype), idx)
        return tok, idx, ck_fan

    # ------------------------------------------------------------------
    # the jitted draft/verify span step (spec mode)
    # ------------------------------------------------------------------
    def _spec_impl(self, params, caches, tok, idx, active, tables, drafts,
                   draft_q, spec_len, ck_tokens, ck_start, ck_len, ck_slot,
                   ck_sample, key, *, chunk):
        """One speculative dispatch in ONE span pass.

        Every slot contributes a row ``[tok, d_0 .. d_{K-1}]`` at absolute
        positions ``idx .. idx + K`` with ``spec_len`` valid tokens
        (``k_eff + 1`` for decode-active slots, 0 otherwise — inactive rows
        scatter only NULL-routed padding and their outputs are discarded);
        up to ``chunk_rows`` prefill-chunk rows ride the SAME span batch.
        The target scores all span positions at once, `spec_accept` commits
        the accepted draft prefix + one correction/bonus token, and
        completed-prompt chunk rows sample their first token — all on
        device, one executable, one fetch.
        """
        s, kmax = self.num_slots, self.spec_k_max
        width = max(kmax + 1, self.chunk_size) if chunk else kmax + 1
        spec_toks = jnp.concatenate([tok[:, None], drafts], axis=1)
        spec_toks = jnp.pad(spec_toks, ((0, 0), (0, width - (kmax + 1))))
        spec_bt = jnp.where(active[:, None], tables, NULL_BLOCK)
        row_tokens, row_start, row_len, row_bt = \
            spec_toks, idx, spec_len, spec_bt
        if chunk:
            ck_pad = jnp.pad(ck_tokens,
                             ((0, 0), (0, width - self.chunk_size)))
            row_tokens = jnp.concatenate([spec_toks, ck_pad])
            row_start = jnp.concatenate([idx, ck_start])
            row_len = jnp.concatenate([spec_len, ck_len])
            row_bt = jnp.concatenate([spec_bt, tables[ck_slot]])
        # the verify pass is the decode lane's (chunk rows ride it)
        with jax.named_scope("decode"):
            caches, logits = self.model.span_step(
                params, caches, row_tokens, row_start, row_len, row_bt,
                micro_batches=self.overlap.micro_batches)

            k_acc = (key if self.temperature <= 0.0
                     else jax.random.fold_in(key, 1 << 17))
            out_toks, n_acc = spec_accept(
                logits[:s, :kmax + 1], drafts, jnp.maximum(spec_len - 1, 0),
                draft_q, k_acc, self.temperature, self.cfg.vocab_size,
                self.top_k, self.top_p)
            # belt-and-braces: gate on `active` too, so a slot whose span
            # was dropped host-side after planning can never advance its
            # registers
            spec_active = (spec_len > 0) & active
            final = jnp.take_along_axis(out_toks, n_acc[:, None], axis=1)[:, 0]
            tok = jnp.where(spec_active, final, tok)
            idx = jnp.where(spec_active, idx + n_acc + 1, idx)

        ck_fan = jnp.zeros(ck_start.shape + (self.num_slots,), jnp.int32)
        if chunk:
            with jax.named_scope("chunk"):
                tok, idx, ck_fan = self._fold_chunk_rows(
                    logits[s:, :self.chunk_size], ck_start, ck_len, ck_slot,
                    ck_sample, key, tok, idx)
        return caches, tok, idx, out_toks, n_acc, ck_fan

    # ------------------------------------------------------------------
    # admission policy: blocks for the FIRST chunk only (JIT per chunk)
    # ------------------------------------------------------------------
    def can_admit(self, req: Request) -> bool:
        if not self.chunkable:
            return super().can_admit(req)
        pool = self.pool
        hits, _ = self._lookup_hits(req)
        start = len(hits) * self.block_size
        first = min(self.chunk_size, self._start_index(req) - start)
        need = pool.blocks_for(start + first) - len(hits)
        evictable_hits = sum(1 for b in hits if pool.ref(b) == 0)
        ok = pool.available() >= need + evictable_hits + 1
        if not ok:
            self._admit_plan = None
        return ok

    def on_admit(self, slot: int, req: Request):
        if self.spec is not None:
            # every occupant change passes through here — the proposer's
            # per-slot drafting state (draft-model cache cursor) resets
            self.spec.reset_slot(slot)
        if not self.chunkable:
            return super().on_admit(slot, req)
        pool = self.pool
        hits, hashes = self._lookup_hits(req)
        self._admit_plan = None
        self._chain_memo.pop(req.rid, None)
        if self.prefix_cache:
            self._req_hashes[req.rid] = hashes
        pool.claim(hits)
        self._slot_blocks[slot] = list(hits)
        self._tables[slot] = NULL_BLOCK
        self._tables[slot, :len(hits)] = hits
        self._tables_dirty = True
        req.prefix_hit_tokens = len(hits) * self.block_size
        self.stats["prefix_hit_tokens"] += req.prefix_hit_tokens
        if self.tracer is not None:
            self.tracer.emit(ev.EV_PREFIX_HIT_TOKENS, req.prefix_hit_tokens)
        # the prefill cursor starts at the hit boundary: resident chunks
        # are never recomputed
        self._progress[slot] = req.prefix_hit_tokens
        self._target[slot] = self._start_index(req)
        self._slot_start[slot] = self._target[slot]
        self._slot_sched0[slot] = len(req.tokens)  # re-prefilled on resume
        self._prefilling[slot] = True
        self.stats["prefills"] += 1

    # ------------------------------------------------------------------
    # per-iteration budget planning
    # ------------------------------------------------------------------
    def _plan_one_chunk(self, slot, req, budget, pairs) -> ChunkPlan | None:
        """Size one slot's next chunk to the remaining budget, with
        just-in-time block allocation — preempting decode slots (newest
        first) when the pool runs dry, or shrinking the chunk to what
        fits."""
        progress, target = int(self._progress[slot]), int(self._target[slot])
        length = min(self.chunk_size, budget, target - progress)
        if length < 1:
            return None
        pool = self.pool
        missing = pool.blocks_for(progress + length) - len(self._slot_blocks[slot])
        while missing > pool.available() and pairs:
            self._preempt_one(pairs)  # mutates pairs in place
        if missing > pool.available():
            fit = (len(self._slot_blocks[slot]) + pool.available()) \
                * self.block_size - progress
            length = min(length, fit)
            if length < 1:
                return None
            missing = pool.blocks_for(progress + length) \
                - len(self._slot_blocks[slot])
        if missing > 0:
            self._grow_slot_blocks(slot, missing)
        tokens = np.asarray(req.input_ids()[progress:progress + length],
                            np.int32)
        return ChunkPlan(slot, req, progress, length, tokens,
                         sample=progress + length >= target)

    def _plan_chunks(self, pairs, decode_tokens: int | None = None
                     ) -> list[ChunkPlan]:
        """Pick this iteration's prefill chunks — resumes first (oldest
        admission first), then FIFO admissions — up to ``chunk_rows``
        streams sharing the budget left after decode.  ``decode_tokens``
        overrides the decode charge (spec mode charges draft + verify
        positions, not one token per slot)."""
        if not self.chunkable:
            return []
        if decode_tokens is None:
            decode_tokens = len(pairs)
        budget = self.max_step_tokens - decode_tokens
        plans: list[ChunkPlan] = []
        live = sorted((s for s in range(self.num_slots) if self._prefilling[s]),
                      key=lambda s: self.scheduler.slots[s].admit_seq)
        for slot in live:
            if len(plans) >= self.chunk_rows or budget < 1:
                break
            plan = self._plan_one_chunk(slot, self.scheduler.slots[slot],
                                        budget, pairs)
            if plan is not None:
                plans.append(plan)
                budget -= plan.length
        admitted_any = False
        while len(plans) < self.chunk_rows and budget >= 1 and self.queue:
            admitted = self.scheduler.admit_one()
            if admitted is None:
                break
            admitted_any = True
            slot, req = admitted
            plan = self._plan_one_chunk(slot, req, budget, pairs)
            if plan is not None:
                plans.append(plan)
                budget -= plan.length
            else:
                break  # admitted but unfundable this step: resume next step
        if admitted_any and self.tracer is not None:
            self.tracer.emit(ev.EV_QUEUE_DEPTH, len(self.queue))
            self.tracer.emit(ev.EV_SLOTS_ACTIVE, self.scheduler.occupancy())
        return plans

    def _relieve_stalled_prefill(self):
        """Forward-progress safety valve: if nothing is dispatchable while
        several prefill streams jointly hold the pool dry, preempt the
        NEWEST stream (its blocks return to the pool; the request requeues
        for recompute resume) so the oldest can finish."""
        live = sorted((s for s in range(self.num_slots) if self._prefilling[s]),
                      key=lambda s: self.scheduler.slots[s].admit_seq)
        if len(live) < 2:
            return False
        slot = live[-1]
        victim = self.scheduler.slots[slot]
        self._prefilling[slot] = False
        self._release_blocks(slot)
        self.scheduler.preempt(victim)
        self._preempted.append(victim)
        self.stats["preemptions"] += 1
        return True

    # ------------------------------------------------------------------
    # dispatch / fetch
    # ------------------------------------------------------------------
    def _prep_dispatch(self, chunks: list[ChunkPlan]):
        """Shared dispatch preamble (unified AND spec): derive the step's
        RNG key, refresh dirty device registers, and pack the chunk plans
        into the fixed-shape [chunk_rows, chunk_size] buffers."""
        key = (self._key if self.temperature <= 0.0
               else jax.random.fold_in(self._key, self._dispatches))
        self._dispatches += 1
        if self._active_dirty:
            self._active_dev = self._upload(self._active)
            self._active_dirty = False
        if self._tables_dirty:
            self._tables_dev = self._upload(self._tables)
            self._tables_dirty = False
        rows = self.chunk_rows
        ck_tokens = np.zeros((rows, self.chunk_size), np.int32)
        ck_start = np.zeros((rows,), np.int32)
        ck_len = np.zeros((rows,), np.int32)
        ck_slot = np.zeros((rows,), np.int32)
        ck_sample = np.zeros((rows,), bool)
        for i, c in enumerate(chunks):
            ck_tokens[i, :c.length] = c.tokens
            ck_start[i] = c.start
            ck_len[i] = c.length
            ck_slot[i] = c.slot
            ck_sample[i] = c.sample
        return key, ck_tokens, ck_start, ck_len, ck_slot, ck_sample

    def _phase(self, phase_id: int, on: bool = True):
        """The tracer's ``EV_PHASE`` region, where a tracer is bound and
        ``on`` holds."""
        tr = self.tracer
        return tr.phase(phase_id) if tr and on else contextlib.nullcontext()

    def _dispatch(self, pairs, steps, chunks: list[ChunkPlan]):
        tr = self.tracer
        if not pairs and not chunks:
            return None
        # per-ITERATION values (a burst is `steps` iterations in one
        # dispatch, emitted once; its chunks ride the first iteration):
        # STEP_BUDGET == CHUNK + DECODE at every sample, and chunkable
        # prefill never pushes it past max_step_tokens — whole-prompt
        # admissions (non-chunkable families, folded in here to keep the
        # triple cadence) are the documented budget bypass
        n_chunk = sum(c.length for c in chunks) + self._whole_tokens
        self._whole_tokens = 0
        with span("serve.unified_step", tr, steps=steps,
                  decode_rows=len(pairs), chunk_tokens=n_chunk,
                  kv_pages=self._decode_pages(pairs, steps),
                  kv_pages_window=self._window_pages(pairs, steps)):
            key, ck_tokens, ck_start, ck_len, ck_slot, ck_sample = \
                self._prep_dispatch(chunks)
            t_dispatch = _now_ns()
            with self._phase(ev.PHASE_DECODE):
                (self._caches, self._tok, self._idx, toks, ck_fan), \
                    coll_ops = self._traced_call(
                        "unified", self._unified,
                        (self.params, self._caches, self._tok, self._idx,
                         self._active_dev, self._tables_dev,
                         self._upload(ck_tokens),
                         self._upload(ck_start),
                         self._upload(ck_len),
                         self._upload(ck_slot),
                         self._upload(ck_sample), key),
                        {"steps": steps, "chunk": bool(chunks)})
            if pairs:
                self._note_kernel("paged_decode")  # decode sub-batch scan
            if steps:
                # mirrors decode_syncs exactly: the fetch side bumps it iff
                # this dispatch carried decode rows (tests assert the two
                # stay equal)
                self.stats["decode_dispatches"] += 1
            if chunks:
                # chunk rows run the span variant
                self._note_kernel("paged_span")
            for slot, req in pairs:
                req.scheduled += steps
                if req.scheduled >= req.max_new_tokens:
                    self._active[slot] = False
                    self._active_dirty = True
            self._advance_chunks(chunks, t_dispatch, ck_fan)
            if tr:
                tr.emit(ev.EV_STEP_BUDGET, len(pairs) + n_chunk)
                tr.emit(ev.EV_CHUNK_TOKENS, n_chunk)
                tr.emit(ev.EV_DECODE_TOKENS, len(pairs))
            return toks, ck_fan, pairs, chunks, t_dispatch, coll_ops

    def _window_pages(self, pairs, steps: int) -> int:
        """:meth:`_decode_pages` in one sliding-window layer; 0 where no
        layer has a window."""
        window = self.cfg.attention_window
        return self._decode_pages(pairs, steps, window) if window else 0

    def _decode_pages(self, pairs, steps: int, window: int | None = None) -> int:
        """Table entries the paged decode kernel reads for this dispatch's
        decode rows, summed over its ``steps`` iterations, in one layer: a
        row at position p reads entries lo .. min(p // bs, W - 1), where lo
        is 0 in a full-attention layer and, in a layer of ``window``
        positions, the entry of position p - window + 1 (the kernel's
        ``_live_pages``)."""
        if not self._has_paged:
            return 0
        bs, w = self.block_size, self._tables.shape[1]

        def upto(n):  # entries min(q // bs, W - 1) + 1 for q < n, summed
            n = max(n, 0)
            k, r = divmod(min(n, w * bs), bs)
            return bs * k * (k + 1) // 2 + r * (k + 1) + max(n - w * bs, 0) * w

        def first(n):  # entries lo for q < n, summed (each q - window + 1)
            m = max(n - window + 1, 0) if window else 0
            return upto(m) - m

        pages = 0
        for slot, req in pairs:
            # the row's first position this dispatch (as _ensure_blocks)
            p = (int(self._slot_start[slot]) + req.scheduled
                 - int(self._slot_sched0[slot]) - 1)
            pages += upto(p + steps) - upto(p) - (first(p + steps) - first(p))
        return pages

    def _advance_chunks(self, chunks: list[ChunkPlan], t_dispatch,
                        ck_fan=None) -> None:
        """Dispatch-side chunk bookkeeping (cursor advance, prompt-block
        registration at completion, fan-out forking).  ``ck_fan`` is the
        dispatch's [C, S] sibling-token fan — possibly still on device
        (pipelined unified path): the fork hook seeds child registers from
        it without a host sync."""
        for row, c in enumerate(chunks):
            slot, req = c.slot, c.req
            self._progress[slot] += c.length
            self.stats["prefill_tokens"] += c.length
            if req.t_admit_ns < 0:
                req.t_admit_ns = t_dispatch
            if c.sample:
                self._prefilling[slot] = False
                req.scheduled += 1
                if req.scheduled < req.max_new_tokens:
                    self._active[slot] = True
                    self._active_dirty = True
                if self.prefix_cache:
                    # publish full PROMPT blocks, now fully streamed in
                    # (generated tokens are never shared)
                    hashes = self._req_hashes.pop(req.rid, [])
                    for j, h in enumerate(hashes[:req.prompt_len
                                                 // self.block_size]):
                        self.pool.register(self._slot_blocks[slot][j], h)
                if req.n_samples > 1 and req.fork_of < 0 and not req.forks:
                    # the ONE prefill of an n-way fan-out just completed:
                    # fork the siblings (a preemption-resumed parent keeps
                    # its existing forks — re-forking would double-serve)
                    self._fork_fanout(row, c, ck_fan, t_dispatch)

    def _fork_fanout(self, row: int, c: ChunkPlan, ck_fan, t_dispatch):
        """Fan a completing fan-out prompt into its sibling decode streams.

        Each child adopted into a free slot costs ZERO block copies: its
        table aliases every parent block — full prompt blocks AND the
        partial tail — via ``pool.fork`` (one extra ref each), and the
        shared tail copies lazily at the child's first decode write
        (``_ensure_blocks``/``_plan_spec`` CoW).  Its registers seed from
        the dispatch still in flight: first token = fan column
        ``fork_index``, position = the parent's first decode write
        position.  Children that find no free slot requeue at the FRONT —
        they re-admit like any request and prefix-hit the prompt blocks
        their parent just registered, so the fan degrades to a cache hit
        instead of n-way recompute."""
        slot, req = c.slot, c.req
        tr = self.tracer
        kids = self.queue.fork_children(req)
        start = int(self._slot_start[slot])  # first decode write position
        bs = self.block_size
        overflow: list[Request] = []
        for kid in kids:
            if tr is not None:
                tr.emit(ev.EV_FORK, req.rid + 1)
            target = next((s for s in range(self.num_slots)
                           if self.scheduler.slots[s] is None), None)
            if target is None:
                overflow.append(kid)
                continue
            self.scheduler.adopt(target, kid)
            if self.spec is not None:
                self.spec.reset_slot(target)
            self._slot_blocks[target] = self.pool.fork(self._slot_blocks[slot])
            self._tables[target] = self._tables[slot]
            self._tables_dirty = True
            self._slot_start[target] = start
            self._slot_sched0[target] = 0
            self._progress[target] = self._target[target] = start
            self._prefilling[target] = False
            kid.scheduled = 1  # the fan token, in flight right now
            kid.t_admit_ns = t_dispatch
            hit = req.prompt_len // bs * bs  # full blocks served by aliasing
            kid.prefix_hit_tokens = hit
            self.stats["prefix_hit_tokens"] += hit
            if tr is not None:
                tr.emit(ev.EV_PREFIX_HIT_TOKENS, hit)
            if kid.max_new_tokens > 1:
                self._active[target] = True
                self._active_dirty = True
            # device-lazy register seed: the fan is an output of the
            # dispatch in flight — no host sync, the child decodes in the
            # very next dispatch
            self._tok = self._tok.at[target].set(ck_fan[row, kid.fork_index])
            self._idx = self._idx.at[target].set(start)
            c.forked.append(kid)
        for kid in reversed(overflow):
            self.queue.requeue(kid)  # front, ascending fork order
        if overflow and tr is not None:
            tr.emit(ev.EV_QUEUE_DEPTH, len(self.queue))

    def _emit_chunk_tokens(self, chunks: list[ChunkPlan], ck) -> None:
        """Fetch-side chunk bookkeeping: append the first sampled token of
        each completed prompt and of every fork child seated at dispatch;
        retire single-token requests.

        The ROW OWNER always reads fan column 0 — that is the value the
        dispatch wrote into the slot's token register — even when the owner
        is an overflow fork child re-admitted through the normal path (its
        ``fork_index`` has no column: the fan only covers siblings adopted
        at their parent's dispatch, so an overflow child re-samples its
        first token on the standard path after its prefix-cache hit)."""
        for i, c in enumerate(chunks):
            if not c.sample:
                continue
            for req in [c.req] + c.forked:
                if req.t_first_ns < 0:
                    req.t_first_ns = _now_ns()  # resumes keep their TTFT
                col = 0 if req is c.req else req.fork_index
                req.tokens.append(int(ck[i, col]))
                self.stats["tokens_decoded"] += 1
                if self.tracer is not None:
                    self.tracer.emit(ev.EV_TOKENS_TOTAL,
                                     self.stats["tokens_decoded"])
                if len(req.tokens) >= req.max_new_tokens \
                        and self.scheduler.slots[req.slot] is req:
                    self._finish(req)

    def _process_unified(self, toks_dev, ck_dev, pairs, chunks, t_dispatch,
                         coll_ops):
        """Fetch one unified step's tokens (the single host sync, overlapped
        with the next step's device compute) and run retirement/latency
        bookkeeping — including the first tokens of prompts whose final
        chunks rode this step."""
        with span("serve.fetch", self.tracer):
            toks, ck = jax.device_get((toks_dev, ck_dev))
        with span("serve.retire", self.tracer):
            self._process_tokens(toks, pairs, t_dispatch, coll_ops)
            self._emit_chunk_tokens(chunks, ck)

    # ------------------------------------------------------------------
    # speculative decoding (spec mode)
    # ------------------------------------------------------------------
    def _slot_pos(self, slot: int, req: Request) -> int:
        """Absolute position of the slot's pending token — the last sampled,
        not-yet-written token the next draft/verify span roots at."""
        return int(self._slot_start[slot]) + len(req.tokens) \
            - int(self._slot_sched0[slot]) - 1

    def _plan_spec(self, pairs):
        """Clamp each decode-active slot's draft width to the step budget /
        remaining generation / cache capacity, then allocate the blocks its
        span will write — just-in-time, oldest admissions first, each span
        shrinking to what the pool can fund (width 0 is a plain one-token
        decode) and the NEWEST request preempted when even the pending
        token cannot be funded.  Returns (surviving pairs, spec_len [S])
        where ``spec_len[slot] = k_eff + 1`` for planned slots."""
        pool = self.pool
        while True:
            spec_len = np.zeros((self.num_slots,), np.int32)
            if not pairs:
                return pairs, spec_len
            k_base = max(0, min(self._spec_k,
                                self.max_step_tokens // len(pairs) - 1))
            ok = True
            for slot, req in sorted(pairs, key=lambda sr: sr[1].admit_seq):
                pos = self._slot_pos(slot, req)
                rem = req.max_new_tokens - len(req.tokens)
                k = max(0, min(k_base, rem - 1, self.capacity - 1 - pos))

                def span_cost(k):
                    # growth for positions pos..pos+k, PLUS one block per
                    # CoW copy: a span scattering into a block another
                    # fork still references must copy it first, charged
                    # against availability like the growth (conservatively
                    # — the last writer inherits the original in place)
                    owned = len(self._slot_blocks[slot])
                    missing = pool.blocks_for(pos + k + 1) - owned
                    bs = self.block_size
                    shared = [w for w in range(pos // bs,
                                               min((pos + k) // bs,
                                                   owned - 1) + 1)
                              if pool.ref(self._slot_blocks[slot][w]) > 1]
                    return missing, shared

                missing, shared = span_cost(k)
                while k > 0 and max(missing, 0) + len(shared) > pool.available():
                    k -= 1
                    missing, shared = span_cost(k)
                if max(missing, 0) + len(shared) > pool.available():
                    ok = False  # even the pending token cannot be funded
                    break
                if missing > 0:
                    self._grow_slot_blocks(slot, missing)
                for w in shared:
                    old = self._slot_blocks[slot][w]
                    fresh, copied = pool.cow(old)
                    if copied:
                        self._slot_blocks[slot][w] = fresh
                        self._tables[slot, w] = fresh
                        self._tables_dirty = True
                        self._cow_pairs.append((old, fresh))
                spec_len[slot] = k + 1
            if ok:
                return pairs, spec_len
            # blocks granted to older slots this attempt stay owned (they
            # are needed regardless; unused tails roll back after the
            # dispatch) — evict the newest request and replan
            self._preempt_one(pairs)

    def _rollback_spec_blocks(self, slot: int, next_pos: int) -> None:
        """Return trailing blocks holding ONLY rejected-draft garbage to
        the pool.  Committed content occupies positions [0, next_pos) and
        the pending token writes AT ``next_pos``, so every block past
        ``next_pos``'s own block is pure speculation residue — freeing it
        here is the rewind that keeps worst-case pool pressure at the
        committed frontier, not the drafted one."""
        keep = self.pool.blocks_for(next_pos + 1)
        blocks = self._slot_blocks[slot]
        if len(blocks) > keep:
            extra = blocks[keep:]
            del blocks[keep:]
            self._tables[slot, keep:] = NULL_BLOCK
            self._tables_dirty = True
            self.pool.free(extra)
            self.stats["spec_rollback_blocks"] += len(extra)

    def _run_spec(self) -> dict[int, np.ndarray]:
        """Speculative serving loop: per iteration, ONE draft/verify span
        dispatch covers every decode-active slot (up to ``K`` drafts each,
        all ``K + 1`` positions scored in one target pass) with prefill
        chunks riding the same span batch.  Synchronous by construction —
        the next span's drafts depend on this dispatch's committed tokens,
        so there is nothing to pipeline; the win is committing up to
        ``K + 1`` tokens per target forward instead of one."""
        tr = self.tracer
        done0 = len(self.scheduler.completed)
        t_run0 = time.perf_counter()
        while not self.scheduler.drained():
            with span("serve.plan", tr):
                pairs = [(s, r) for s, r in self.scheduler.active()
                         if self._active[s]]
                pairs, spec_len = self._plan_spec(pairs)
                decode_tokens = int(spec_len.sum())
                with self._phase(ev.PHASE_ADMIT,
                                 bool(self.queue or self._prefilling.any())):
                    chunks = self._plan_chunks(pairs,
                                               decode_tokens=decode_tokens)
                # chunk planning can itself preempt a spec-planned decode
                # victim (just-in-time chunk allocation, newest-first): drop
                # the victim's span so the budget counters never charge
                # positions that will not dispatch and its registers stay
                # frozen
                live = {s for s, _ in pairs}
                for s in np.nonzero(spec_len)[0]:
                    if int(s) not in live:
                        spec_len[s] = 0
                decode_tokens = int(spec_len.sum())
                self.stats["peak_active"] = max(self.stats["peak_active"],
                                                self.scheduler.occupancy())
                self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                                self.pool.num_active())
                self.stats["peak_shared"] = max(self.stats["peak_shared"],
                                                self.pool.num_shared())
                if not pairs and not chunks:
                    if not self.scheduler.drained() and not self._preempted:
                        if not self._relieve_stalled_prefill():
                            raise RuntimeError(
                                "serve loop stalled: nothing dispatchable "
                                "but the scheduler is not drained")
                    self._drain_preempted()
                    continue

                # ---- host drafts from each slot's committed context ----
                kmax = self.spec_k_max
                drafts_all = np.zeros((self.num_slots, kmax), np.int32)
                q_all = None
                k_ask = max((int(spec_len[s]) - 1 for s, _ in pairs),
                            default=0)
                if k_ask > 0:
                    slots_ = [s for s, _ in pairs]
                    dr, q = self.spec.propose(
                        slots_, [r.input_ids() for _, r in pairs], k_ask)
                    drafts_all[slots_, :k_ask] = dr[:, :k_ask]
                    if q is not None and self.temperature > 0.0:
                        # device-side scatter: q may be a device array
                        # straight from the draft model's propose scan
                        q_all = jnp.zeros(
                            (self.num_slots, kmax, self.cfg.vocab_size),
                            jnp.float32)
                        q_all = q_all.at[
                            jnp.asarray(slots_, jnp.int32), :k_ask].set(
                            jnp.asarray(q, jnp.float32)[:, :k_ask])

            # ---- one span dispatch, fetched synchronously ----
            with span("serve.blocks", tr):
                self._flush_cow()  # CoW copies land before the span writes
            n_chunk = sum(c.length for c in chunks)
            with self._phase(ev.PHASE_DECODE):
                with span("serve.spec_step", tr, steps=1,
                          decode_rows=len(pairs), chunk_tokens=n_chunk):
                    key, ck_tokens, ck_start, ck_len, ck_slot, ck_sample = \
                        self._prep_dispatch(chunks)
                    t_dispatch = _now_ns()
                    (self._caches, self._tok, self._idx, out_toks, n_acc,
                     ck_fan), coll_ops = self._traced_call(
                        "spec", self._spec_step,
                        (self.params, self._caches, self._tok, self._idx,
                         self._active_dev, self._tables_dev,
                         self._upload(drafts_all),
                         None if q_all is None else self._dev(q_all),
                         self._upload(spec_len),
                         self._upload(ck_tokens),
                         self._upload(ck_start),
                         self._upload(ck_len),
                         self._upload(ck_slot),
                         self._upload(ck_sample), key),
                        {"chunk": bool(chunks)})
                with span("serve.fetch", tr):
                    out, nacc, ck = jax.device_get((out_toks, n_acc, ck_fan))
            with span("serve.retire", tr):
                self._note_kernel("paged_span")  # draft/verify rides the span
                self.stats["host_syncs"] += 1
                self._replay(coll_ops, t_dispatch, _now_ns())
                self._advance_chunks(chunks, t_dispatch, ck)

                # ---- commit accepted prefixes + correction/bonus tokens ----
                drafted = accepted = 0
                for slot, req in pairs:
                    if spec_len[slot] == 0:
                        continue
                    m = int(nacc[slot]) + 1
                    drafted += int(spec_len[slot]) - 1
                    accepted += int(nacc[slot])
                    req.tokens.extend(int(t) for t in out[slot, :m])
                    req.scheduled = len(req.tokens)
                    self.stats["tokens_decoded"] += m
                    if len(req.tokens) >= req.max_new_tokens:
                        # releases every block, garbage incl.
                        self._finish(req)
                    else:
                        self._rollback_spec_blocks(slot,
                                                   self._slot_pos(slot, req))
                self._emit_chunk_tokens(chunks, ck)
                self.stats["spec_dispatches"] += 1 if pairs else 0
                self.stats["spec_drafted"] += drafted
                self.stats["spec_accepted"] += accepted
                if pairs:
                    self.stats["iterations"] += 1
                    self.stats["decode_syncs"] += 1
                    # the spec lane fetches synchronously, so dispatch and
                    # sync coincide — but the invariant stays the same
                    self.stats["decode_dispatches"] += 1
                k_used = self._spec_k  # width actually in effect this dispatch
                if drafted > 0:
                    self._accept_ema = (0.7 * self._accept_ema
                                        + 0.3 * accepted / drafted)
                    if self.spec_adaptive:
                        if self._accept_ema > 0.7:
                            self._spec_k = min(self._spec_k + 1,
                                               self.spec_k_max)
                        elif self._accept_ema < 0.35:
                            self._spec_k = max(1, self._spec_k - 1)
                self._since_flush += 1
                if tr:
                    tr.emit(ev.EV_STEP_BUDGET, decode_tokens + n_chunk)
                    tr.emit(ev.EV_CHUNK_TOKENS, n_chunk)
                    tr.emit(ev.EV_DECODE_TOKENS, decode_tokens)
                    if pairs:
                        tr.emit(ev.EV_SPEC_DRAFTED, drafted)
                        tr.emit(ev.EV_SPEC_ACCEPTED, accepted)
                        tr.emit(ev.EV_SPEC_K, k_used)
                    tr.emit(ev.EV_TOKENS_TOTAL, self.stats["tokens_decoded"])
                    tr.emit(ev.EV_QUEUE_DEPTH, len(self.queue))
                    if self.flush_every \
                            and self._since_flush >= self.flush_every:
                        tr.flush(self.flush_base,
                                 split_tasks=self.meshstate is not None)
                        self._since_flush = 0
                self._drain_preempted()
        self.stats["seconds"] += time.perf_counter() - t_run0
        return {r.rid: np.asarray(r.tokens, np.int32)
                for r in self.scheduler.completed[done0:]}

    # ------------------------------------------------------------------
    # beam search: fork + per-step score/prune on the same CoW mechanism
    # ------------------------------------------------------------------
    def _beam_prefill_impl(self, params, caches, tokens, table, *, width):
        """Prompt prefill through the span path (one [1, L] row writing
        into the beam's block table) -> (caches, top-``width`` first-token
        log-probs, their ids)."""
        length = tokens.shape[0]
        caches, logits = self.model.span_step(
            params, caches, tokens[None], jnp.zeros((1,), jnp.int32),
            jnp.full((1,), length, jnp.int32), table[None],
            micro_batches=1)
        lp = jax.nn.log_softmax(logits[0, length - 1].astype(jnp.float32))
        val, ids = jax.lax.top_k(lp, width)
        return caches, val, ids

    def _beam_step_impl(self, params, caches, tok, idx, active, tables, *,
                        width):
        """One beam decode step: the SAME paged decode the serve loop runs
        (every beam is a slot row; inactive rows NULL-masked), then
        per-beam top-``width`` log-prob candidates for the host to prune.
        log_softmax preserves the argmax, so width=1 reduces to greedy
        decode bit-for-bit."""
        bt = jnp.where(active[:, None], tables, NULL_BLOCK)
        caches, logits = self.model.decode_step(params, caches, tok, idx,
                                                block_tables=bt)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        val, ids = jax.lax.top_k(lp, width)  # [S, width]
        return caches, val, ids

    def beam_search(self, prompt, num_tokens: int, *, width: int = 4
                    ) -> list[tuple[np.ndarray, float]]:
        """Beam-search ``num_tokens`` continuations of ``prompt``; returns
        [(tokens, cumulative log-prob)] best-first, ``width`` entries.

        Beams ARE forks: the prompt prefills ONCE into beam 0's blocks,
        beams 1..W-1 alias them via ``pool.fork`` (zero copies), and every
        per-step prune that reseats beam b onto source s is another fork —
        release b's refs, alias s's (EV_FORK per reseat, value = source
        beam + 1).  The only copies are CoW on the shared write-frontier
        block, exactly like n-way sampling; peak ACTIVE blocks stay at
        prompt + W tails instead of W full contexts.  Runs standalone on an
        idle engine (the beams borrow the slot rows)."""
        if not self.chunkable:
            raise ValueError(
                "beam_search needs the fully-paged span path (dense/moe "
                f"families); {self.cfg.family!r} cannot run it")
        if not 1 <= width <= self.num_slots:
            raise ValueError(f"width must be in [1, {self.num_slots}]")
        if self.queue or self.scheduler.any_active():
            raise RuntimeError("beam_search needs an idle engine "
                               "(no queued or active requests)")
        prompt = np.asarray(prompt, np.int32)
        plen = int(prompt.shape[0])
        if plen + num_tokens > self.capacity:
            raise ValueError(
                f"prompt {plen} + {num_tokens} beam tokens needs cache "
                f"capacity {plen + num_tokens} > {self.capacity}")
        t_beam0 = time.perf_counter()
        pool, bs, tr = self.pool, self.block_size, self.tracer
        w = width
        # beam 0 owns the prompt blocks; 1..W-1 alias them (zero copies)
        blocks: list[list[int]] = [pool.alloc(pool.blocks_for(plen))]
        tables = np.full((self.num_slots, self.blocks_per_slot),
                         NULL_BLOCK, np.int32)
        tables[0, :len(blocks[0])] = blocks[0]
        for b in range(1, w):
            blocks.append(pool.fork(blocks[0]))
            tables[b] = tables[0]
            if tr is not None:
                tr.emit(ev.EV_FORK, 0 + 1)
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += plen
        with (tr.phase(ev.PHASE_PREFILL) if tr
              else contextlib.nullcontext()), \
                (tr.user_function(name="beam_prefill") if tr
                 else contextlib.nullcontext()), self._with_rules():
            self._caches, val, ids = self._beam_prefill(
                self.params, self._caches, jnp.asarray(prompt),
                self._upload(tables[0]), width=w)
        val, ids = np.asarray(val, np.float64), np.asarray(ids)
        self._note_kernel("paged_span")
        self.stats["host_syncs"] += 1
        scores = val.copy()  # [w] cumulative log-probs
        seqs = [[int(t)] for t in ids]
        tok = np.zeros((self.num_slots,), np.int32)
        idx = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        tok[:w], idx[:w], active[:w] = ids, plen, True
        active_dev = self._upload(active)
        # num_tokens - 1 decode steps: the final token's KV is never
        # written, so its position needs no block and triggers no CoW
        for step in range(1, num_tokens):
            # fund + exclusively own each beam's write block (CoW): the
            # decode writes tok's KV at position idx == plen + step - 1
            wblk = (plen + step - 1) // bs
            for b in range(w):
                if wblk >= len(blocks[b]):
                    fresh = pool.alloc(1)
                    tables[b, len(blocks[b])] = fresh[0]
                    blocks[b].extend(fresh)
                elif pool.ref(blocks[b][wblk]) > 1:
                    old = blocks[b][wblk]
                    fresh, copied = pool.cow(old)
                    if copied:
                        blocks[b][wblk] = fresh
                        tables[b, wblk] = fresh
                        self._cow_pairs.append((old, fresh))
            self._flush_cow()
            self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                            pool.num_active())
            self.stats["peak_shared"] = max(self.stats["peak_shared"],
                                            pool.num_shared())
            with (tr.phase(ev.PHASE_DECODE) if tr
                  else contextlib.nullcontext()), \
                    (tr.user_function(name="beam_step") if tr
                     else contextlib.nullcontext()), self._with_rules():
                self._caches, val, ids = self._beam_step(
                    self.params, self._caches, self._upload(tok),
                    self._upload(idx), active_dev,
                    self._upload(tables), width=w)
            val = np.asarray(val, np.float64)[:w]
            ids = np.asarray(ids)[:w]
            self._note_kernel("paged_decode")
            self.stats["host_syncs"] += 1
            total = scores[:, None] + val  # [w, w] candidate scores
            flat = np.argsort(-total, axis=None, kind="stable")[:w]
            src, pick = flat // w, flat % w
            # reseat pruned beams: alias the surviving source's blocks
            # (fork) BEFORE releasing the old rows, so a row that is both
            # replaced and someone's source never drops to ref 0
            old_blocks = [blocks[b] for b in range(w)]
            old_tables = tables[:w].copy()
            for b in range(w):
                s = int(src[b])
                if s != b:
                    blocks[b] = pool.fork(old_blocks[s])
                    tables[b] = old_tables[s]
                    if tr is not None:
                        tr.emit(ev.EV_FORK, s + 1)
            for b in range(w):
                if int(src[b]) != b:
                    pool.free(old_blocks[b])
            seqs = [seqs[int(s)] + [int(ids[int(s), int(p)])]
                    for s, p in zip(src, pick)]
            scores = total.reshape(-1)[flat]
            tok[:w] = [ids[int(s), int(p)] for s, p in zip(src, pick)]
            idx[:w] = plen + step
        for b in range(w):
            pool.free(blocks[b])  # unhashed -> straight back to FREE
        self.stats["tokens_decoded"] += w * num_tokens
        self.stats["seconds"] += time.perf_counter() - t_beam0
        order = np.argsort(-scores, kind="stable")
        return [(np.asarray(seqs[int(r)], np.int32), float(scores[int(r)]))
                for r in order]

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------
    def run(self) -> dict[int, np.ndarray]:
        """Serve until queue and slots drain; one unified token-budget step
        per iteration, pipelined (the fetch of step i overlaps the device
        compute of step i+1).  Pure-decode dispatches burst up to
        ``max_decode_burst`` scanned steps; chunk-carrying dispatches scan
        up to ``mixed_burst`` decode steps (default 4, the chunks riding
        the first iteration — set ``mixed_burst=1`` for strict
        one-iteration budget accounting).  Returns {rid: [new_tokens]} for
        requests completed by THIS call."""
        if self.spec is not None:
            return self._run_spec()
        tr = self.tracer
        done0 = len(self.scheduler.completed)
        # double-buffered dispatch pipeline: with the overlap plan's host
        # pipeline on, up to TWO dispatches stay unfetched, so the host
        # plans dispatch N+1 (admission, chunk planning, block allocation)
        # while the device still executes dispatch N — the fetch of N-1 is
        # the only sync.  depth 1 reproduces the classic one-deep pipeline.
        depth = 2 if self.overlap.host_pipeline else 1
        inflight: collections.deque = collections.deque()
        t_run0 = time.perf_counter()
        while inflight or not self.scheduler.drained():
            with span("serve.plan", tr):
                if not self.chunkable:
                    # state-carrying families: budget-looped whole-prompt
                    # admission through the inherited grouped-prefill path
                    with self._phase(ev.PHASE_ADMIT, bool(self.queue)):
                        admissions = self.scheduler.admissions()
                    for members in self._prefill_groups(admissions):
                        # count BEFORE the prefill call: it appends the
                        # first sampled token, growing input_ids()
                        self._whole_tokens += sum(
                            self._start_index(r) - r.prefix_hit_tokens
                            for _, r in members)
                        self._do_prefill(members)
                pairs = [(s, r) for s, r in self.scheduler.active()
                         if self._active[s]]
                with self._phase(ev.PHASE_ADMIT, self.chunkable and bool(
                        self.queue or self._prefilling.any())):
                    chunks = self._plan_chunks(pairs)
            with span("serve.blocks", tr):
                pairs, steps = self._ensure_blocks(
                    pairs, max_steps=self.mixed_burst if chunks else None)
                self._flush_cow()  # CoW copies land before the burst writes
                self.stats["peak_active"] = max(self.stats["peak_active"],
                                                self.scheduler.occupancy())
                if self.pool is not None:
                    self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                                    self.pool.num_active())
                    self.stats["peak_shared"] = max(self.stats["peak_shared"],
                                                    self.pool.num_shared())
            dispatched = self._dispatch(pairs, steps, chunks)
            if dispatched is None and self._whole_tokens and tr:
                # whole-prompt prefills with nothing left to decode (e.g.
                # max_new_tokens == 1 retiring at prefill): emit their
                # triple now — no later dispatch will fold it in
                tr.emit(ev.EV_STEP_BUDGET, self._whole_tokens)
                tr.emit(ev.EV_CHUNK_TOKENS, self._whole_tokens)
                tr.emit(ev.EV_DECODE_TOKENS, 0)
                self._whole_tokens = 0
            if dispatched is None and not inflight \
                    and not self.scheduler.drained():
                # several prefill streams can jointly wedge the pool with no
                # decode victims left — preempt the newest so work resumes
                if not self._relieve_stalled_prefill():
                    raise RuntimeError(
                        "serve loop stalled: nothing dispatchable but the "
                        "scheduler is not drained")
            if dispatched is not None:
                if len(inflight) >= 2:
                    # genuinely planned ahead: this dispatch was built with
                    # two earlier bursts still unfetched
                    self.stats["planned_ahead"] += 1
                inflight.append(dispatched)
            # a stall (nothing dispatched) or a preemption flushes the whole
            # queue: victims must drain their in-flight tokens before
            # _drain_preempted requeues them
            keep = depth if (dispatched is not None
                             and not self._preempted) else 0
            while len(inflight) > keep:
                self._process_unified(*inflight.popleft())
            if self._preempted:
                with span("serve.retire", tr):
                    self._drain_preempted()
        self.stats["seconds"] += time.perf_counter() - t_run0
        return {r.rid: np.asarray(r.tokens, np.int32)
                for r in self.scheduler.completed[done0:]}
