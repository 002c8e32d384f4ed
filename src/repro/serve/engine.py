"""Serving engines: continuous batching over a paged KV pool + legacy fixed batch.

The production serve path is :class:`repro.serve.step.UnifiedServeEngine`
(one token-budget mixed chunk+decode step per iteration — see
docs/chunked_prefill.md); it subclasses :class:`ContinuousServeEngine` for
the pool/admission/preemption machinery below, while this class's own
two-path loop (grouped same-length prefill + decode bursts) survives as
the unified step's bit-exact equivalence oracle.

:class:`ContinuousServeEngine` admits variable-length
requests from a :class:`~repro.serve.queue.RequestQueue` into a fixed pool of
``num_slots`` decode slots whose attention K/V lives in a shared **paged
block pool** (``serve/block_pool.py``): fixed-size blocks, ref-counted,
content-hashed for prefix reuse.  Slot count stops being the memory bound —
admission is gated on *block availability*, so many short requests can share
the HBM budget one worst-case contiguous slot layout would reserve.  Each
engine iteration interleaves:

  1. *admission* — the scheduler pops queued requests while enough
     free/evictable blocks exist; prompt blocks already resident in the
     prefix cache are ref-bumped and skipped, only the tail is prefilled
     (chunked prefill against the gathered prefix);
  2. *decode* — ONE fused jit call advances every slot a burst of tokens
     through the paged attention path (per-slot block tables, absolute
     positions); fresh blocks are allocated just-in-time before each burst,
     and when the pool runs dry the latest-admitted request is *preempted*
     (blocks freed, request requeued for recompute-style resume);
  3. *retirement* — finished requests free their slots and decref their
     blocks; prompt blocks stay cached (evictable) for future prefix hits.

Every scheduler AND allocator decision emits tracer events (queue depth,
slot occupancy, blocks free/cached/active, prefix-hit tokens, evictions,
preemptions) so served traffic — and its memory pressure — is analyzable in
Paraver exactly like training, and ``flush_every`` streams full record
buffers to disk mid-run via ``Tracer.flush`` (EV_FLUSH-bracketed).

:class:`ServeEngine` keeps the original fixed-batch ``generate`` API over
per-request contiguous caches — it is the *contiguous equivalence oracle*
the paged engine is tested against (greedy decode must match bit-for-bit).

Both engines optionally run **tensor-parallel over a JAX mesh**: pass
``mesh=`` (and optionally ``rules=``; defaults to
:func:`repro.sharding.partition.make_serve_rules`) and parameters, the
paged KV block pool and recurrent leaves are placed per the serve rules
(GQA kv-heads split across the "model" axis when divisible), the jitted
prefill/admit/burst executables become mesh-aware with explicit in/out
shardings, and — when a tracer is attached — the engine binds the
tracer's process model to the mesh (``mesh_data``: TASK = data
coordinate, THREAD = model coordinate), captures each burst executable's
compiled collective schedule (:mod:`repro.core.hlo_comm`) and replays it
per decode window onto the correct (task, thread) endpoints, exactly like
the training-side distributed trace.  The pipelined ≤1-host-sync-per-
decode-iteration structure is unchanged by sharding.
"""
from __future__ import annotations

import collections
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import events as ev
from repro.kernels.attention import dispatch as kdispatch
from repro.core.comm_replay import device_endpoint_map, replay_step
from repro.core.hlo_comm import parse_collectives
from repro.core.sampling import sample_logits
from repro.core.tracer import Tracer
from repro.models.model import build_model
from repro.serve.block_pool import NULL_BLOCK, BlockPool
from repro.serve.queue import Request, RequestQueue, _now_ns
from repro.serve.scheduler import Scheduler
from repro.sharding.overlap import plan_overlap, resolve_mode
from repro.sharding.partition import make_serve_rules, use_rules

EV_TOKENS_DECODED = 84_001  # user event: tokens decoded so far (one run)

SERVE_TASK_AXES = ("pod", "data")  # trace process model: TASK = data coord
SERVE_THREAD_AXES = ("model",)  # THREAD = model coord


class _MeshState:
    """Sharding + trace-replay state for a mesh-parallel engine."""

    def __init__(self, cfg, model, mesh, rules, tracer):
        from jax.sharding import NamedSharding, PartitionSpec

        self.mesh = mesh
        self.rules = rules if rules is not None else make_serve_rules(cfg, mesh)
        self.param_sh = self.rules.tree_shardings(model.param_axes())
        self.replicated = NamedSharding(mesh, PartitionSpec())
        self.endpoints = None
        if tracer is not None:
            # per-task record streams keyed by the mesh_data mapping; the
            # host thread emits as (task 0, thread 0), device-side
            # collectives are injected per (task, thread) endpoint
            tracer.pm.set_mode("mesh_data")
            tracer.pm.bind_mesh(mesh, task_axes=SERVE_TASK_AXES,
                                thread_axes=SERVE_THREAD_AXES)
            self.endpoints = device_endpoint_map(
                mesh, task_axes=SERVE_TASK_AXES, thread_axes=SERVE_THREAD_AXES)

    def put_replicated(self, x):
        return jax.device_put(x, self.replicated)


class ContinuousServeEngine:
    """Continuous-batching engine over a paged KV-block pool."""

    # n-way CoW fan-out (``submit(n_samples=...)``) needs the chunk-sampling
    # path that forks sibling rows off a completing prompt — only the
    # unified token-budget step implements it (serve/step.py flips this on
    # when the config is chunkable).  The legacy two-path engine rejects
    # fan-out loudly instead of silently serving n sequential requests.
    supports_fork = False

    def __init__(self, cfg: ModelConfig, params, *, num_slots: int, max_len: int,
                 block_size: int = 16, num_blocks: int | None = None,
                 prefix_cache: bool = True, tracer: Tracer | None = None,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0,
                 max_prefills_per_iter: int = 1, max_decode_burst: int = 8,
                 flush_every: int = 0, flush_base=None,
                 mesh=None, rules=None, overlap: str | None = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.meshstate = (_MeshState(cfg, self.model, mesh, rules, tracer)
                          if mesh is not None else None)
        # communication/compute overlap plan (sharding/overlap.py): decides
        # the span-path micro-batch count and whether the dispatch queue
        # runs two deep; ``overlap`` overrides cfg.comm_overlap
        self.overlap = plan_overlap(
            self.meshstate.rules if self.meshstate is not None else None,
            mode=resolve_mode(overlap, cfg))
        if self.meshstate is not None:
            params = jax.device_put(params, self.meshstate.param_sh)
        self.params = params
        self.num_slots = int(num_slots)
        self.block_size = bs = int(block_size)
        self.capacity = -(-int(max_len) // bs) * bs  # block-aligned
        self.blocks_per_slot = self.capacity // bs
        self.tracer = tracer
        self.temperature = float(temperature)  # fixed per engine (jit-traced)
        self.top_k = int(top_k)  # sampling filters, traced like temperature
        self.top_p = float(top_p)
        self.max_decode_burst = max(1, int(max_decode_burst))
        self.flush_every = int(flush_every)
        self.flush_base = flush_base
        self._since_flush = 0  # decode iterations since the last trace flush
        if flush_every and flush_base is None:
            raise ValueError("flush_every requires flush_base")
        if tracer is not None:
            tracer.register(EV_TOKENS_DECODED, "Tokens decoded")
            tracer.register(ev.EV_TOKENS_TOTAL,
                            ev.SERVE_CTR_LABELS[ev.EV_TOKENS_TOTAL])
            tracer.register(ev.EV_REQ_TTFT_US, ev.SERVE_CTR_LABELS[ev.EV_REQ_TTFT_US])
            tracer.register(ev.EV_REQ_TPOT_US, ev.SERVE_CTR_LABELS[ev.EV_REQ_TPOT_US])
            tracer.register(ev.EV_PREFIX_HIT_TOKENS,
                            ev.SERVE_CTR_LABELS[ev.EV_PREFIX_HIT_TOKENS])
            tracer.register(ev.EV_COMM_OVERLAP_US,
                            ev.SERVE_CTR_LABELS[ev.EV_COMM_OVERLAP_US])
            tracer.register(ev.EV_COMM_BLOCKED_US,
                            ev.SERVE_CTR_LABELS[ev.EV_COMM_BLOCKED_US])
            for code, label in ev.KERNEL_EVENT_LABELS.items():
                tracer.register(code, label)
            # autotune decisions resolve at trace time inside jit — route
            # them into this engine's trace (process-global; last engine wins)
            kdispatch.set_observer(tracer.emit)

        # --- paged pool: attention K/V is block-addressed, recurrent state
        # (ssm/rec/cross leaves) stays slot-indexed ---
        self._paged_mask = self.model.paged_leaf_mask()
        self._has_paged = any(jax.tree.leaves(self._paged_mask))
        if num_blocks is None:
            # default budget == the old contiguous layout (one full-capacity
            # region per slot) + the reserved NULL block; floor keeps one
            # max-length request admissible even with a single slot
            num_blocks = max(self.num_slots * self.blocks_per_slot + 1,
                             self.blocks_per_slot + 2)
        self.num_blocks = int(num_blocks)
        if self._has_paged and self.num_blocks < self.blocks_per_slot + 2:
            raise ValueError(
                f"num_blocks {self.num_blocks} cannot hold one max-length "
                f"request ({self.blocks_per_slot} blocks + null + headroom)")
        # pooled storage cost, from the abstract specs (covers every paged
        # leaf incl. quantization scale leaves): bytes per block across all
        # layers — the pool reports it as occupancy gauges / CLI stats
        specs = self.model.paged_cache_specs(self.num_slots, self.num_blocks, bs)
        block_bytes = sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize // self.num_blocks
            for s, m in zip(jax.tree.leaves(specs),
                            jax.tree.leaves(self._paged_mask)) if m)
        self.kv_bytes_per_token = block_bytes // bs if self._has_paged else 0
        self.pool = (BlockPool(self.num_blocks, bs, tracer=tracer,
                               kv_dtype=cfg.kv_dtype, block_bytes=block_bytes)
                     if self._has_paged else None)
        # prefix reuse needs every leaf pooled AND token-only prompts (vlm
        # patches would shift block contents off the token-hash grid)
        self.prefix_cache = (bool(prefix_cache) and self.model.fully_paged()
                             and cfg.family in ("dense", "moe"))

        self.queue = RequestQueue()
        self.scheduler = Scheduler(
            self.num_slots, self.queue, tracer=tracer,
            max_prefills_per_iter=max_prefills_per_iter,
            admission=self if self.pool is not None else None)

        # --- device state: pooled caches + per-slot registers ---
        if self.meshstate is not None:
            self._cache_sh = self.meshstate.rules.tree_shardings(
                self.model.paged_cache_axes())
            self._caches = jax.tree.map(
                lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
                specs, self._cache_sh)
        else:
            self._cache_sh = None
            self._caches = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), specs)
        self._tok = self._dev(jnp.zeros((self.num_slots,), jnp.int32))
        self._idx = self._dev(jnp.zeros((self.num_slots,), jnp.int32))
        self._active = np.zeros((self.num_slots,), bool)  # host-side mirror
        self._active_dev = self._upload(self._active)
        self._active_dirty = False
        # per-slot block tables; entry w maps positions [w*bs, (w+1)*bs).
        # NULL rows make stale frozen-slot writes land in the garbage block.
        self._tables = np.full((self.num_slots, self.blocks_per_slot),
                               NULL_BLOCK, np.int32)
        self._tables_dev = self._upload(self._tables)
        self._tables_dirty = False
        self._slot_blocks: list[list[int]] = [[] for _ in range(self.num_slots)]
        # prefill-time start position per slot (request input_ids() grows as
        # generated tokens drain — decode block math needs the pinned start)
        self._slot_start = np.zeros((self.num_slots,), np.int64)
        # tokens already folded INTO the start position (a preemption-resumed
        # request re-prefills its generated tokens, but req.scheduled keeps
        # counting them — position math must not count them twice)
        self._slot_sched0 = np.zeros((self.num_slots,), np.int64)
        self._admit_plan = None  # (req, hits, hashes): can_admit -> on_admit
        self._req_hashes: dict[int, list[int]] = {}  # rid -> prompt hash chain
        self._chain_memo: dict[int, tuple[int, list[int]]] = {}  # rid -> (len, chain)
        self._preempted: list[Request] = []  # requeue deferred past token drain
        # multi-turn sessions: id -> {"context": np[int32], "blocks": [bid],
        # "tokens": int} — the blocks are the session's PIN (one extra ref
        # per full context block, taken at turn retirement), so turn k+1
        # prefix-hits the whole prior conversation even under pool pressure
        self._sessions: dict[str, dict] = {}
        # copy-on-write transfers planned by _ensure_blocks / the spec lane:
        # (src, dst) block pairs whose device contents must be replicated
        # before the next dispatch scatters into dst (serve/block_pool.py)
        self._cow_pairs: list[tuple[int, int]] = []
        self._key = jax.random.PRNGKey(seed)
        self._dispatches = 0  # burst dispatch counter (drives the RNG stream)

        self._prefill = jax.jit(self._prefill_impl, static_argnames=("cache_len",))
        self._chunk = jax.jit(self._chunk_impl, static_argnames=("start", "cache_len"))
        # tok/idx buffers are NOT donated: the pipelined fetch of the previous
        # burst's tokens may still reference them
        if self.meshstate is not None:
            # explicit in/out shardings pin the steady-state placement: the
            # donated pool keeps its kv-head sharding, per-slot registers and
            # block tables stay replicated — no silent resharding per burst
            # input placement is pinned by committed arrays (params/caches
            # device_put at init, registers through _dev); this jax rejects
            # in_shardings alongside static kwargs, so outputs carry the
            # explicit specs
            r = self.meshstate.replicated
            self._admit = jax.jit(self._admit_impl, donate_argnums=(0,),
                                  out_shardings=(self._cache_sh, r, r))
            self._burst = jax.jit(
                self._burst_impl, donate_argnums=(1,),  # caches
                static_argnames=("steps",),
                out_shardings=(self._cache_sh, r, r, r))
        else:
            self._admit = jax.jit(self._admit_impl, donate_argnums=(0,))
            self._burst = jax.jit(self._burst_impl, donate_argnums=(1,),  # caches
                                  static_argnames=("steps",))
        # CoW block replication (device half of pool.cow): caches donated,
        # pair lists padded to a power of two with NULL -> NULL self-copies
        # so the jit cache stays O(log max_pairs)
        if self.meshstate is not None:
            self._copy_blocks = jax.jit(self._copy_blocks_impl,
                                        donate_argnums=(0,),
                                        out_shardings=self._cache_sh)
        else:
            self._copy_blocks = jax.jit(self._copy_blocks_impl,
                                        donate_argnums=(0,))
        self._aot_cache: dict = {}  # signature -> (compiled, collective ops)

        # --- run statistics ---
        self.stats = {"iterations": 0, "prefills": 0, "tokens_decoded": 0,
                      "prefill_tokens": 0, "prefix_hit_tokens": 0,
                      "preemptions": 0, "peak_active": 0, "peak_blocks": 0,
                      "peak_shared": 0,
                      "host_syncs": 0, "decode_syncs": 0,
                      "decode_dispatches": 0, "planned_ahead": 0,
                      "comm_overlap_us": 0, "comm_blocked_us": 0,
                      "seconds": 0.0,
                      "prefill_seconds": 0.0, "kernel_dispatch": {}}

        # --- attention-kernel dispatch plan: one resolve() per variant,
        # mirroring what the traced model will decide at its call sites ---
        hd_shards = 1
        if self.meshstate is not None:
            r = self.meshstate.rules
            hd_shards = r.axis_size(r.axis("cache_hd"))
        self.kernel_plan = kdispatch.engine_plan(
            cfg, block_size=bs, hd_shards=hd_shards)

    # ------------------------------------------------------------------
    # mesh plumbing
    # ------------------------------------------------------------------
    def _dev(self, x):
        """Place an engine register on device — replicated over the mesh
        when one is attached (host-mastered state is never sharded)."""
        return self.meshstate.put_replicated(x) if self.meshstate else x

    def _upload(self, host):
        """Place a host-built register on device from a PRIVATE copy.

        A transfer may read its numpy source after ``device_put`` returns
        (XLA:CPU aliases aligned buffers or copies asynchronously), and the
        engine edits its host mirrors (``_active``, ``_tables``) in place
        right after a dispatch.  Uploading the live buffer let an in-flight
        burst see its slot frozen early, and that stream then repeated one
        token to the end.  The copy belongs to JAX alone.
        """
        host = np.array(host)
        return jax.device_put(
            host, self.meshstate.replicated if self.meshstate else None)

    def _with_rules(self):
        return (use_rules(self.meshstate.rules) if self.meshstate
                else contextlib.nullcontext())

    def _note_kernel(self, variant: str):
        """Account one engine dispatch of an attention-kernel variant:
        bump ``stats["kernel_dispatch"]`` and stamp EV_KERNEL_VARIANT so
        the backend that actually ran is readable in the merged trace."""
        if not self._has_paged:
            return  # no attention layers -> no attention dispatch
        d = self.kernel_plan[variant]
        counts = self.stats["kernel_dispatch"]
        counts[d.tag] = counts.get(d.tag, 0) + 1
        if self.tracer is not None:
            self.tracer.emit(ev.EV_KERNEL_VARIANT, d.event_value)

    def _traced_call(self, tag: str, jitfn, args: tuple, statics: dict):
        """Run a jitted engine kernel; returns (outputs, collective_ops).

        On the traced-mesh path the kernel goes through an AOT-compiled
        executable (cached per shape signature) so the optimized HLO's
        collective schedule is extracted once — the caller replays it onto
        the (task, thread) mesh endpoints over the measured window, the
        serving analogue of the training-side distributed trace.
        """
        ms = self.meshstate
        if ms is None or ms.endpoints is None:
            with self._with_rules():
                return jitfn(*args, **statics), None
        key = (tag, tuple(sorted(statics.items())),
               tuple(tuple(x.shape) for x in jax.tree.leaves(args)
                     if hasattr(x, "shape")))
        ent = self._aot_cache.get(key)
        if ent is None:
            with self._with_rules():
                compiled = jitfn.lower(*args, **statics).compile()
            ops = parse_collectives(compiled.as_text(),
                                    total_devices=ms.mesh.size)
            ent = self._aot_cache[key] = (compiled, ops)
        compiled, ops = ent
        return compiled(*args), ops

    def _replay(self, ops, t0: int, t1: int):
        """Inject one executable's collective schedule over [t0, t1) and
        book the overlapped/blocked split into the engine stats."""
        ms = self.meshstate
        if ops and ms is not None and ms.endpoints is not None \
                and self.tracer is not None and self.tracer.active:
            split = replay_step(self.tracer, ops, t0, t1, ms.endpoints)
            # same 1us floor as the injected EV_COMM_* counters, so the
            # engine stats agree with the merged trace at any time scale
            for key, ns in (("comm_overlap_us", split["overlap_ns"]),
                            ("comm_blocked_us", split["blocked_ns"])):
                self.stats[key] += max(ns // 1000, 1) if ns else 0

    # ------------------------------------------------------------------
    # jitted kernels
    # ------------------------------------------------------------------
    def _prefill_impl(self, params, batch, key, *, cache_len):
        """Cold prefill of a same-shape group ([k, L] tokens) at block-aligned
        cache length -> (caches for k slots, first sampled tokens [k]).
        ring=False: SWA archs keep FULL-length K/V (the pool stores absolute
        positions; the window is a mask, not a ring)."""
        caches, last_logits = self.model.prefill(params, batch,
                                                 max_len=cache_len, ring=False)
        tok = sample_logits(last_logits, key, self.temperature,
                            self.cfg.vocab_size, self.top_k, self.top_p)
        return caches, tok

    def _chunk_impl(self, params, pool, batch, prefix_ids, key, *, start, cache_len):
        """Prefix-hit prefill: gather the resident prefix blocks
        (``prefix_ids`` [k, m]) into [k, start, ...] per layer, run only the
        prompt TAIL through the stack, and return block-aligned tail K/V
        (padded to ``cache_len - start``) + first sampled tokens."""
        prefix = jax.tree.map(
            lambda leaf: leaf[:, prefix_ids].reshape(
                leaf.shape[0], prefix_ids.shape[0], start, *leaf.shape[3:]),
            pool)
        tail, last_logits = self.model.prefill_chunk(params, batch, prefix,
                                                     start=start)
        pad = cache_len - start - batch["tokens"].shape[1]
        tail = jax.tree.map(
            lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3)),
            tail)
        tok = sample_logits(last_logits, key, self.temperature,
                            self.cfg.vocab_size, self.top_k, self.top_p)
        return tail, tok

    def _admit_impl(self, pool, new, tok_buf, idx_buf, slots, block_ids,
                    first_toks, start_idxs):
        """Scatter a prefilled group's caches into the pool and seed the
        slots' token/position registers.  Paged leaves land in their blocks
        (``block_ids`` [k, nblk]); slot-indexed leaves land at ``slots``.
        Leaves are [layers, k|num_blocks, ...] — group axis is 1."""
        bs = self.block_size
        nblk = block_ids.shape[1]

        def scatter(pl, nw, paged):
            if paged:
                nw = nw.reshape(nw.shape[0], nw.shape[1] * nblk, bs, *nw.shape[3:])
                return pl.at[:, block_ids.reshape(-1)].set(nw.astype(pl.dtype))
            return pl.at[:, slots].set(nw.astype(pl.dtype))

        pool = jax.tree.map(scatter, pool, new, self._paged_mask)
        return (pool, tok_buf.at[slots].set(first_toks),
                idx_buf.at[slots].set(start_idxs))

    def _decode_scan(self, params, caches, tok, idx, active, bt, key, steps):
        """``steps`` scanned decode iterations: batched paged decode
        (``bt`` block tables, per-slot absolute positions) + on-device
        sampling; inactive slots are frozen (token/index don't advance).
        ONE definition shared by the legacy burst AND the unified step's
        decode sub-batch — the unified-vs-legacy bit-exactness contract
        rests on these being the same traced ops, so don't fork it."""
        def body(carry, k):
            caches, tok, idx = carry
            new_caches, logits = self.model.decode_step(
                params, caches, tok, idx, block_tables=bt)
            sub = key if self.temperature <= 0.0 else jax.random.fold_in(key, k)
            nxt = sample_logits(logits, sub, self.temperature,
                                self.cfg.vocab_size, self.top_k, self.top_p)
            tok = jnp.where(active, nxt, tok)
            idx = jnp.where(active, idx + 1, idx)
            return (new_caches, tok, idx), tok

        (caches, tok, idx), toks = jax.lax.scan(
            body, (caches, tok, idx), jnp.arange(steps))
        return caches, tok, idx, toks

    def _burst_impl(self, params, caches, tok, idx, active, tables, key, *, steps):
        """``steps`` decode iterations over the whole pool in ONE executable
        (:meth:`_decode_scan`); frozen slots' stale writes land in blocks
        they still own, or the NULL block once retired.  Returns the
        [steps, num_slots] token block for a single host fetch."""
        bt = tables if self._has_paged else None
        return self._decode_scan(params, caches, tok, idx, active, bt, key, steps)

    def _copy_blocks_impl(self, caches, src, dst):
        """Replicate pool blocks ``src[i] -> dst[i]`` across every paged
        leaf (data + quantization scales) — the device half of copy-on-
        write: a fork's writer reference moved to ``dst`` on the host
        (pool.cow), and this makes ``dst``'s contents bit-identical to the
        shared ``src`` before the write dispatches."""
        from repro.models import cache_utils

        return jax.tree.map(
            lambda leaf, paged: (cache_utils.copy_pool_blocks(leaf, src, dst)
                                 if paged else leaf),
            caches, self._paged_mask)

    def _flush_cow(self):
        """Apply pending CoW block copies in ONE jitted call before the
        next dispatch.  Pairs pad to a power of two with NULL -> NULL
        self-copies (block 0 is garbage by contract) so distinct pair
        counts share executables."""
        if not self._cow_pairs:
            return
        pairs = self._cow_pairs
        self._cow_pairs = []
        n = 1
        while n < len(pairs):
            n *= 2
        pairs = pairs + [(NULL_BLOCK, NULL_BLOCK)] * (n - len(pairs))
        src = self._upload(np.asarray([p[0] for p in pairs], np.int32))
        dst = self._upload(np.asarray([p[1] for p in pairs], np.int32))
        with self._with_rules():
            self._caches = self._copy_blocks(self._caches, src, dst)

    # ------------------------------------------------------------------
    # admission policy (Scheduler callback): blocks, not slots, gate entry
    # ------------------------------------------------------------------
    def _start_index(self, req: Request) -> int:
        patches = self.cfg.num_patches if self.cfg.family == "vlm" else 0
        return len(req.input_ids()) + patches

    def _lookup_hits(self, req: Request) -> tuple[list[int], list[int]]:
        """(prefix-hit blocks, full hash chain) for this request.  The chain
        is content-determined and memoized per (rid, input length) — a
        blocked queue head re-walks residency every iteration without
        re-hashing its whole prompt; the plan cache covers the atomic
        can_admit -> on_admit pair, and the chain survives to registration."""
        if not self.prefix_cache or req.extras:
            return [], []
        plan = self._admit_plan
        if plan is not None and plan[0] is req:
            return plan[1], plan[2]
        ids = req.input_ids()
        memo = self._chain_memo.get(req.rid)
        if memo is None or memo[0] != len(ids):
            memo = (len(ids), self.pool.hash_chain(ids))
            self._chain_memo[req.rid] = memo
        hashes = memo[1]
        hits = self.pool.resolve_hits(hashes, len(ids))
        self._admit_plan = (req, hits, hashes)
        return hits, hashes

    def can_admit(self, req: Request) -> bool:
        """Enough free/evictable blocks for this prompt (+1 decode headroom)?
        Prefix-hit blocks are discounted — but hits that are currently
        evictable consume availability when pinned, so they count back in."""
        pool = self.pool
        w0 = pool.blocks_for(self._start_index(req))
        hits, _ = self._lookup_hits(req)
        evictable_hits = sum(1 for b in hits if pool.ref(b) == 0)
        need = (w0 - len(hits)) + evictable_hits + 1
        ok = pool.available() >= need
        if not ok:
            # the plan must not outlive this can_admit -> on_admit pair:
            # by the next attempt, evictions may have invalidated the hits
            self._admit_plan = None
        return ok

    def on_admit(self, slot: int, req: Request):
        """Pin prefix hits, allocate the remaining prompt blocks, and build
        the slot's block table."""
        pool = self.pool
        w0 = pool.blocks_for(self._start_index(req))
        hits, hashes = self._lookup_hits(req)
        self._admit_plan = None
        self._chain_memo.pop(req.rid, None)
        if self.prefix_cache:
            self._req_hashes[req.rid] = hashes
        pool.claim(hits)
        bids = hits + pool.alloc(w0 - len(hits))
        self._slot_blocks[slot] = bids
        self._tables[slot] = NULL_BLOCK
        self._tables[slot, :w0] = bids
        self._tables_dirty = True
        req.prefix_hit_tokens = len(hits) * self.block_size
        self.stats["prefix_hit_tokens"] += req.prefix_hit_tokens
        if self.tracer is not None:
            self.tracer.emit(ev.EV_PREFIX_HIT_TOKENS, req.prefix_hit_tokens)

    def _release_blocks(self, slot: int):
        if self.pool is not None:
            self.pool.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._tables[slot] = NULL_BLOCK
            self._tables_dirty = True

    def _grow_slot_blocks(self, slot: int, missing: int):
        """Append ``missing`` freshly-allocated blocks to a slot's table
        (the ONE place the table/ownership/dirty-flag bookkeeping lives —
        decode bursts, prefill chunks, and speculative spans all grow
        through here)."""
        fresh = self.pool.alloc(missing)
        a = len(self._slot_blocks[slot])
        self._tables[slot, a:a + missing] = fresh
        self._slot_blocks[slot].extend(fresh)
        self._tables_dirty = True

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, extras: dict | None = None,
               arrival_ns: int | None = None, n_samples: int = 1,
               session: str | None = None) -> Request:
        # reject BEFORE enqueueing: a rejected request must not linger in the
        # queue and get served anyway.  Paged storage holds ABSOLUTE
        # positions, so the capacity bound applies to SWA archs too (the
        # window is a mask; out-of-window blocks are not yet reclaimed).
        if self._has_paged:
            plen = int(np.asarray(prompt).shape[0])
            patches = self.cfg.num_patches if self.cfg.family == "vlm" else 0
            need = plen + patches + int(max_new_tokens) - 1
            if need > self.capacity:
                raise ValueError(
                    f"prompt {plen} + {max_new_tokens} new tokens needs cache "
                    f"capacity {need} > {self.capacity}")
        if n_samples > 1:
            # loud exclusion, not silent degradation: fan-out needs the
            # chunk-sampling fork path of the unified step (serve/step.py)
            if not self.supports_fork:
                raise ValueError(
                    f"n_samples={n_samples} needs CoW forking, which "
                    f"{type(self).__name__} does not support for "
                    f"family={self.cfg.family!r} (unified engine + chunkable "
                    f"config only)")
            if session is not None:
                raise ValueError("n_samples > 1 and session are mutually "
                                 "exclusive (a session persists ONE stream)")
        if session is not None:
            if not self.prefix_cache:
                raise ValueError(
                    "sessions persist context through the prefix cache; "
                    "enable prefix_cache (token-only prompts, fully-paged "
                    "model) to use session ids")
            held = self._sessions.get(session)
            if held is not None:
                ctx = held["context"]
                p = np.asarray(prompt, np.int32)
                if len(p) <= len(ctx) or not np.array_equal(p[:len(ctx)], ctx):
                    raise ValueError(
                        f"session {session!r}: the new prompt must extend the "
                        f"stored {len(ctx)}-token context (turn k+1 = full "
                        f"conversation so far + new tokens)")
        req = self.queue.submit(prompt, max_new_tokens, extras=extras,
                                arrival_ns=arrival_ns, n_samples=n_samples,
                                session=session)
        if self.tracer is not None:
            self.tracer.emit(ev.EV_QUEUE_DEPTH, len(self.queue))
        return req

    # ------------------------------------------------------------------
    # multi-turn sessions: pin the full context across requests
    # ------------------------------------------------------------------
    def _session_pin(self, req: Request):
        """At a session turn's retirement, publish + pin its full context.

        The context written to the pool is ``prompt ++ tokens[:-1]`` (the
        last sampled token's KV is never written — it would be the next
        step's input); every FULL block of it is registered under the
        chained hash and given one extra reference, so the conversation
        survives eviction until the next turn claims it (or the session
        closes).  The previous turn's pin — a prefix of this one — is
        released after the new pin is taken, so the session never drops to
        zero references in between."""
        sid = req.session
        context = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        written = len(context) - 1  # last token's KV not in the pool
        nfull = written // self.block_size
        blocks = self._slot_blocks[req.slot][:nfull]
        hashes = self.pool.hash_chain(context[:nfull * self.block_size])
        for bid, h in zip(blocks, hashes):
            self.pool.register(bid, h)
        self.pool.incref(blocks)  # the session's pin
        prev = self._sessions.get(sid)
        self._sessions[sid] = {"context": context, "blocks": list(blocks),
                               "tokens": written}
        if prev is not None:
            self.pool.free(prev["blocks"])  # hand over turn k's pin

    def close_session(self, session: str) -> int:
        """Release a session's pin: its context blocks drop to the prefix
        cache (CACHED, evictable — a re-opened conversation may still hit
        them) and the pool conserves FREE/ACTIVE/CACHED.  Returns the
        number of pinned blocks released; unknown ids are a no-op 0."""
        held = self._sessions.pop(session, None)
        if held is None:
            return 0
        self.pool.free(held["blocks"])
        return len(held["blocks"])

    # ------------------------------------------------------------------
    # prefix-block handoff (prefill/decode disaggregation, serve/router.py)
    # ------------------------------------------------------------------
    def export_prefix(self, tokens) -> tuple[list[int], list] | None:
        """Gather the resident prefix-cache blocks covering ``tokens``'s
        chained full-block hashes into HOST arrays.

        Returns ``(hashes, leaves)`` — the chain hashes of the resident run
        and, per paged cache leaf (tree-flatten order), the ``[layers,
        n_blocks, ...]`` device content pulled to host — or None when
        nothing is resident.  This is the prefill side of the
        prefill->decode KV handoff: a prefill-only replica serves the
        prompt once (max_new_tokens=1 retires at prefill, publishing every
        full prompt block into its prefix cache), exports here, and the
        decode replica :meth:`import_prefix`-es the payload so its own
        admission prefix-hits the transferred blocks instead of
        recomputing the prompt."""
        if self.pool is None or not self.prefix_cache:
            return None
        hashes = self.pool.hash_chain(np.asarray(tokens, np.int32))
        bids: list[int] = []
        for h in hashes:
            bid = self.pool.resident(h)
            if bid is None:
                break
            bids.append(bid)
        if not bids:
            return None
        sel = jnp.asarray(bids, jnp.int32)
        leaves = [np.asarray(leaf[:, sel])
                  for leaf, paged in zip(jax.tree.leaves(self._caches),
                                         jax.tree.leaves(self._paged_mask))
                  if paged]
        self.stats["host_syncs"] += 1
        return hashes[:len(bids)], leaves

    def import_prefix(self, hashes: list[int], leaves: list) -> int:
        """Scatter exported prefix blocks into this pool's cache and
        publish them under their chain hashes (refcount 0 -> CACHED, so
        the next admission of the same prompt claims them like any other
        prefix hit).  Returns the number of blocks imported (0 when the
        pool cannot host them without evicting ACTIVE work)."""
        if self.pool is None or not self.prefix_cache or not hashes:
            return 0
        n = len(hashes)
        if n > self.pool.available():
            return 0
        fresh = [h for h in hashes if self.pool.resident(h) is None]
        if len(fresh) < n:
            # partial residency: only import the missing tail if the whole
            # prefix run stays contiguous; otherwise blocks already here win
            if fresh != hashes[n - len(fresh):]:
                return 0
            keep = n - len(fresh)
            leaves = [lf[:, keep:] for lf in leaves]
            hashes = hashes[keep:]
            n = len(fresh)
            if n == 0:
                return 0
        bids = self.pool.alloc(n)
        sel = jnp.asarray(bids, jnp.int32)
        it = iter(leaves)

        def scatter(c, paged):
            if not paged:
                return c
            return c.at[:, sel].set(jnp.asarray(next(it)).astype(c.dtype))

        self._caches = jax.tree.map(scatter, self._caches, self._paged_mask)
        for bid, h in zip(bids, hashes):
            self.pool.register(bid, h)
        self.pool.free(bids)  # hashed at refcount 0 == CACHED, claimable
        return n

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------
    def _prefill_groups(self, admissions: list[tuple[int, Request]]):
        """Group same-shape admissions so they prefill as ONE batched jit
        call (a (length, prefix-hit) bucket); mixed shapes degrade to
        singleton groups."""
        groups: dict[tuple, list[tuple[int, Request]]] = {}
        for slot, req in admissions:
            sig = (len(req.input_ids()), req.prefix_hit_tokens,
                   tuple(sorted((k, v.shape) for k, v in req.extras.items())))
            groups.setdefault(sig, []).append((slot, req))
        return list(groups.values())

    def _do_prefill(self, members: list[tuple[int, Request]]):
        t_wall0 = time.perf_counter()
        tr = self.tracer
        reqs = [r for _, r in members]
        slots = [s for s, _ in members]
        inputs = [r.input_ids() for r in reqs]
        starts = [self._start_index(r) for r in reqs]
        start_total = starts[0]
        bs = self.block_size
        cache_len = (-(-start_total // bs) * bs if self._has_paged
                     else start_total)
        w0 = cache_len // bs if self._has_paged else 0
        hit = reqs[0].prefix_hit_tokens  # same within a group (signature)
        key = jax.random.fold_in(self._key, (1 << 20) + reqs[0].rid)
        t_admit = _now_ns()
        with (tr.phase(ev.PHASE_PREFILL) if tr else contextlib.nullcontext()), \
                (tr.user_function(name="prefill") if tr else contextlib.nullcontext()):
            if hit:
                # tail-only prefill: resident prefix blocks are ref-bumped,
                # their K/V gathered on device; no recompute for hit tokens
                m = hit // bs
                batch = {"tokens": jnp.asarray(
                    np.stack([ids[hit:] for ids in inputs]), jnp.int32)}
                prefix_ids = jnp.asarray(
                    [self._slot_blocks[s][:m] for s in slots], jnp.int32)
                (new_caches, tok1), coll_ops = self._traced_call(
                    "chunk", self._chunk,
                    (self.params, self._caches, batch, prefix_ids, key),
                    {"start": hit, "cache_len": cache_len})
                block_ids = np.asarray(
                    [self._slot_blocks[s][m:w0] for s in slots], np.int32)
            else:
                batch = {"tokens": jnp.asarray(np.stack(inputs), jnp.int32)}
                for k in reqs[0].extras:
                    batch[k] = jnp.asarray(np.stack([r.extras[k] for r in reqs]))
                (new_caches, tok1), coll_ops = self._traced_call(
                    "prefill", self._prefill, (self.params, batch, key),
                    {"cache_len": cache_len})
                block_ids = np.asarray(
                    [self._slot_blocks[s][:w0] for s in slots], np.int32
                ).reshape(len(slots), w0)
        with self._with_rules():
            self._caches, self._tok, self._idx = self._admit(
                self._caches, new_caches, self._tok, self._idx,
                jnp.asarray(slots, jnp.int32), jnp.asarray(block_ids, jnp.int32),
                tok1, jnp.asarray(starts, jnp.int32),
            )
        self._note_kernel("dense")  # prefill/chunk run the dense variant
        for slot, st, req in zip(slots, starts, reqs):
            self._slot_start[slot] = st
            self._slot_sched0[slot] = len(req.tokens)  # re-prefilled tokens
        firsts = np.asarray(tok1)  # TTFT: first tokens materialized here
        self.stats["host_syncs"] += 1
        self.stats["prefills"] += len(reqs)
        self.stats["prefill_tokens"] += sum(
            st - r.prefix_hit_tokens for st, r in zip(starts, reqs))
        if self.prefix_cache:
            # publish full PROMPT blocks for future prefix hits (generated
            # tokens are never shared; hit blocks no-op re-register); the
            # chain was already computed at admission
            for slot, req in zip(slots, reqs):
                hashes = self._req_hashes.pop(req.rid)[:req.prompt_len // bs]
                for j, h in enumerate(hashes):
                    self.pool.register(self._slot_blocks[slot][j], h)
        t_first = _now_ns()
        self._replay(coll_ops, t_admit, t_first)
        # wall spent blocked on prefill while decode slots waited — the
        # grouped-prefill engine's head-of-line stall (mixed-load bench)
        self.stats["prefill_seconds"] += time.perf_counter() - t_wall0
        for (slot, req), first in zip(members, firsts):
            req.t_admit_ns = t_admit
            if req.t_first_ns < 0:
                req.t_first_ns = t_first  # resumed requests keep their TTFT
            req.tokens.append(int(first))
            req.scheduled = len(req.tokens)
            self.stats["tokens_decoded"] += 1
            self._active[slot] = True
            self._active_dirty = True
            if len(req.tokens) >= req.max_new_tokens:
                self._finish(req)

    def _finish(self, req: Request):
        req.t_done_ns = _now_ns()
        self._active[req.slot] = False
        self._active_dirty = True
        if req.session is not None and self.prefix_cache:
            self._session_pin(req)  # before the slot's refs drop
        self._release_blocks(req.slot)
        req.extras.clear()  # prefill inputs (frames/patches) are dead weight now
        if self.tracer is not None:
            self.tracer.emit(ev.EV_REQ_TTFT_US, max(req.ttft_ns() // 1000, 0))
            self.tracer.emit(ev.EV_REQ_TPOT_US, req.tpot_ns() // 1000)
        self.scheduler.retire(req)

    # ------------------------------------------------------------------
    # decode-time block management
    # ------------------------------------------------------------------
    def _preempt_one(self, pairs):
        """Evict the latest-admitted in-flight request: free its blocks now
        (requeue is deferred until its in-flight tokens are drained)."""
        slot, victim = max(pairs, key=lambda sr: sr[1].admit_seq)
        pairs.remove((slot, victim))
        self._active[slot] = False
        self._active_dirty = True
        self._release_blocks(slot)
        self.scheduler.preempt(victim)
        self._preempted.append(victim)
        self.stats["preemptions"] += 1
        return pairs

    def _ensure_blocks(self, pairs, max_steps: int | None = None):
        """Allocate the blocks this burst will write, preempting (newest
        first) when the pool cannot cover every active slot.  Returns the
        surviving pairs and the burst length.  ``max_steps`` caps the burst
        below ``max_decode_burst`` (the unified step dispatches single
        iterations whenever prefill chunks share the batch)."""
        cap = self.max_decode_burst if max_steps is None else max_steps
        while pairs:
            need = min(r.max_new_tokens - r.scheduled for _, r in pairs)
            steps = 1
            while steps < need:
                steps *= 2
            steps = min(steps, cap)
            if self.pool is None:
                return pairs, steps
            # the power-of-two bucket may overshoot a slot's remaining cache
            # capacity (writes land at start+(scheduled-sched0)-1 .. +steps-2,
            # sched0 = tokens already re-prefilled into the start): clamp so
            # no burst ever demands a block-table entry past W.  The
            # submit() capacity check guarantees headroom >= need >= 1.
            steps = min(steps, min(
                self.capacity + 1 - int(self._slot_start[s])
                - (r.scheduled - int(self._slot_sched0[s]))
                for s, r in pairs))
            shortfall: list[tuple[int, int]] = []  # (slot, missing blocks)
            shared: list[tuple[int, int]] = []  # (slot, w): CoW before write
            total = 0
            for slot, req in pairs:
                first_pos = (int(self._slot_start[slot]) + req.scheduled
                             - int(self._slot_sched0[slot]) - 1)
                last_pos = first_pos + steps - 1
                owned = len(self._slot_blocks[slot])
                missing = last_pos // self.block_size + 1 - owned
                if missing > 0:
                    shortfall.append((slot, missing))
                    total += missing
                # copy-on-write: any block this burst writes while another
                # request still references it (a CoW fork's shared partial
                # tail) must be copied first — each copy costs one block,
                # charged against availability alongside the growth
                for w in range(first_pos // self.block_size,
                               min(last_pos // self.block_size, owned - 1) + 1):
                    if self.pool.ref(self._slot_blocks[slot][w]) > 1:
                        shared.append((slot, w))
                        total += 1
            if total <= self.pool.available():
                for slot, missing in shortfall:
                    self._grow_slot_blocks(slot, missing)
                for slot, w in shared:
                    old = self._slot_blocks[slot][w]
                    fresh, copied = self.pool.cow(old)
                    if copied:
                        self._slot_blocks[slot][w] = fresh
                        self._tables[slot, w] = fresh
                        self._tables_dirty = True
                        self._cow_pairs.append((old, fresh))
                return pairs, steps
            pairs = self._preempt_one(pairs)
        return pairs, 0

    def _process_tokens(self, toks_dev, pairs, t_dispatch=None, coll_ops=None):
        """Record one decode burst's [steps, num_slots] token block.  Called
        while the NEXT burst computes on device, so the blocking fetch
        overlaps compute and host bookkeeping costs nothing on the critical
        path.  Preempted requests still drain their in-flight tokens here
        (they were computed against blocks that were valid at dispatch)."""
        tr = self.tracer
        toks = np.asarray(toks_dev)  # the ONE host sync of the burst
        if t_dispatch is not None:
            # the fetch completing bounds the burst's device window: replay
            # its compiled collective schedule onto the mesh endpoints
            self._replay(coll_ops, t_dispatch, _now_ns())
        self.stats["host_syncs"] += 1
        if len(toks):  # chunk-only unified dispatches carry no decode rows
            self.stats["decode_syncs"] += 1
        for row in toks:
            for slot, req in pairs:
                if req.done or len(req.tokens) >= req.max_new_tokens:
                    continue
                req.tokens.append(int(row[slot]))
                self.stats["tokens_decoded"] += 1
                if len(req.tokens) >= req.max_new_tokens:
                    if self.scheduler.slots[req.slot] is req:
                        self._finish(req)
        self.stats["iterations"] += len(toks)
        # flush cadence counts DISPATCHES, floor 1: a prefill-dominated
        # phase of chunk-only steps (len(toks) == 0) must still stream its
        # records to disk instead of growing the buffers unbounded
        self._since_flush += max(len(toks), 1)
        if tr:
            tr.emit(EV_TOKENS_DECODED, self.stats["tokens_decoded"])
            tr.emit(ev.EV_TOKENS_TOTAL, self.stats["tokens_decoded"])
            tr.emit(ev.EV_QUEUE_DEPTH, len(self.queue))
            if self.flush_every and self._since_flush >= self.flush_every:
                # mesh runs stream one segment file PER TASK (Extrae's
                # per-rank .mpit discipline; merged mpi2prv-style at write)
                tr.flush(self.flush_base,
                         split_tasks=self.meshstate is not None)
                self._since_flush = 0

    def _drain_preempted(self):
        """Requeue preempted requests (front of queue, earliest-admitted
        first) once their in-flight tokens have been processed."""
        for req in sorted(self._preempted, key=lambda r: r.admit_seq,
                          reverse=True):
            req.scheduled = len(req.tokens)
            self.queue.requeue(req)
            if self.tracer is not None:
                self.tracer.emit(ev.EV_QUEUE_DEPTH, len(self.queue))
        self._preempted.clear()

    def run(self) -> dict[int, np.ndarray]:
        """Serve until queue and slots drain.  Returns {rid: [new_tokens]}
        for the requests completed by THIS call (the engine is reusable:
        later waves don't re-report earlier ones).

        The loop is pipelined and bursted: up to ``max_decode_burst`` decode
        iterations run in one executable (the burst length is clamped to the
        smallest remaining token budget among active slots, bucketed up to a
        power of two to bound distinct compiles), and burst i is dispatched
        before burst i-1's tokens are fetched — the fetch blocks only on
        whatever device time remains, and retirement/admission decisions lag
        the device by one burst.

        With ``overlap.host_pipeline`` the in-flight queue runs TWO deep:
        burst i+1's planning (admission, block allocation, dispatch) happens
        while bursts i-1 and i execute, so the host never sits between a
        fetch and the next dispatch.  A preemption flushes the queue first —
        a victim's in-flight tokens must drain before it can requeue."""
        tr = self.tracer
        done0 = len(self.scheduler.completed)
        depth = 2 if self.overlap.host_pipeline else 1
        inflight: collections.deque = collections.deque()  # unfetched bursts
        t_run0 = time.perf_counter()
        while inflight or not self.scheduler.drained():
            if self.queue and tr:
                with tr.phase(ev.PHASE_ADMIT):
                    admissions = self.scheduler.admissions()
            else:
                admissions = self.scheduler.admissions()
            for members in self._prefill_groups(admissions):
                self._do_prefill(members)
            self.stats["peak_active"] = max(self.stats["peak_active"],
                                            self.scheduler.occupancy())
            if self.pool is not None:
                self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                                self.pool.num_active())
                self.stats["peak_shared"] = max(self.stats["peak_shared"],
                                                self.pool.num_shared())
            dispatched = None
            pairs = [(s, r) for s, r in self.scheduler.active() if self._active[s]]
            pairs, steps = self._ensure_blocks(pairs)
            self._flush_cow()  # CoW copies land before the burst writes
            if pairs:
                # greedy decode consumes no randomness — skip the fold_in
                key = (self._key if self.temperature <= 0.0
                       else jax.random.fold_in(self._key, self._dispatches))
                self._dispatches += 1
                if self._active_dirty:
                    self._active_dev = self._upload(self._active)
                    self._active_dirty = False
                if self._tables_dirty:
                    self._tables_dev = self._upload(self._tables)
                    self._tables_dirty = False
                t_dispatch = _now_ns()
                with (tr.phase(ev.PHASE_DECODE) if tr else contextlib.nullcontext()), \
                        (tr.user_function(name="decode_step") if tr
                         else contextlib.nullcontext()):
                    (self._caches, self._tok, self._idx, toks), coll_ops = \
                        self._traced_call(
                            "burst", self._burst,
                            (self.params, self._caches, self._tok, self._idx,
                             self._active_dev, self._tables_dev, key),
                            {"steps": steps})
                self._note_kernel("paged_decode")
                self.stats["decode_dispatches"] += 1
                for slot, req in pairs:
                    req.scheduled += steps
                    if req.scheduled >= req.max_new_tokens:
                        # fully scheduled: freeze the slot for the next burst
                        # (it stays occupied until the tokens are processed)
                        self._active[slot] = False
                        self._active_dirty = True
                dispatched = (toks, pairs, t_dispatch, coll_ops)
                if len(inflight) >= 2:  # planned with 2 bursts unfetched
                    self.stats["planned_ahead"] += 1
                inflight.append(dispatched)
            # keep up to ``depth`` unfetched bursts in flight; a stalled
            # iteration (nothing dispatched) or a pending preemption flushes
            # the queue so retirement/requeue see fully-drained tokens
            keep = depth if (dispatched is not None and not self._preempted) \
                else 0
            while len(inflight) > keep:
                self._process_tokens(*inflight.popleft())
            self._drain_preempted()
        self.stats["seconds"] += time.perf_counter() - t_run0
        return {r.rid: np.asarray(r.tokens, np.int32)
                for r in self.scheduler.completed[done0:]}

    # ------------------------------------------------------------------
    def serve_batch(self, prompts: np.ndarray, *, num_tokens: int,
                    extras: dict | None = None) -> np.ndarray:
        """Convenience: submit a rectangular batch and run to completion.
        Returns [B, num_tokens] in submission order."""
        reqs = []
        for b in range(prompts.shape[0]):
            ex = {k: v[b] for k, v in (extras or {}).items()}
            reqs.append(self.submit(prompts[b], num_tokens, extras=ex))
        out = self.run()
        return np.stack([out[r.rid] for r in reqs])

    def sharding_summary(self) -> list[str]:
        """``path: PartitionSpec`` lines for every parameter and decode-state
        leaf — printed by the serve CLI *before* the first compile so a
        misconfigured mesh is visible (and fails loudly in make_serve_rules)
        rather than surfacing as an opaque XLA error."""
        if self.meshstate is None:
            return ["single-device (no mesh)"]
        from repro.sharding.partition import describe_shardings

        rules = self.meshstate.rules
        mesh = self.meshstate.mesh
        head = [f"mesh: {dict(mesh.shape)} over {mesh.size} devices"]
        return (head
                + describe_shardings(rules, self.model.param_axes(),
                                     prefix="param/")
                + describe_shardings(rules, self.model.paged_cache_axes(),
                                     prefix="kv-pool/"))

    def throughput_stats(self) -> dict:
        total, dt = self.stats["tokens_decoded"], self.stats["seconds"]
        out = {**self.stats, "tokens": total,
               "tok_per_s": total / dt if dt > 0 else float("nan")}
        # canonical sync-amortization metric: decode fetches per scanned
        # decode iteration.  Derived from decode_syncs (not host_syncs,
        # which also counts prefill fetches) so a dispatch window spanning
        # a trace flush cannot skew it; decode_syncs == decode_dispatches
        # is an engine invariant (tests/test_serve_sharded.py).
        out["host_syncs_per_decode_iter"] = (
            self.stats["decode_syncs"] / max(self.stats["iterations"], 1))
        comm = self.stats["comm_overlap_us"] + self.stats["comm_blocked_us"]
        out["comm_overlap_fraction"] = (
            self.stats["comm_overlap_us"] / comm if comm > 0 else 0.0)
        if self.pool is not None:
            out.update(blocks_free=self.pool.num_free(),
                       blocks_cached=self.pool.num_cached(),
                       evictions=self.pool.stats["evictions"],
                       hit_blocks=self.pool.stats["hit_blocks"],
                       forks=self.pool.stats["forks"],
                       cow_copies=self.pool.stats["cow_copies"])
        return out


class ServeEngine:
    """Fixed-batch engine over CONTIGUOUS per-request caches: one
    rectangular batch, lockstep decode.

    This is the paged engine's equivalence oracle — the legacy contiguous
    cache layout survives only here (greedy decode through the paged pool
    must match it bit-for-bit; tests/test_serve_paged.py).  Sampling is
    fused into the jitted decode step, so the loop performs one host sync
    per token."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 tracer: Tracer | None = None, mesh=None, rules=None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.meshstate = (_MeshState(cfg, self.model, mesh, rules, tracer)
                          if mesh is not None else None)
        if self.meshstate is not None:
            params = jax.device_put(params, self.meshstate.param_sh)
        self.params = params
        self.max_len = max_len
        self.tracer = tracer
        self.host_syncs = 0
        if tracer is not None:
            tracer.register(EV_TOKENS_DECODED, "Tokens decoded")
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, max_len=max_len)
        )
        self._decode_sample = jax.jit(
            self._decode_sample_impl,
            static_argnames=("temperature", "top_k", "top_p"))

    def _with_rules(self):
        return (use_rules(self.meshstate.rules) if self.meshstate
                else contextlib.nullcontext())

    def _decode_sample_impl(self, params, caches, tok, idx, key, *,
                            temperature, top_k=0, top_p=1.0):
        caches, logits = self.model.decode_step(params, caches, tok, idx)
        nxt = sample_logits(logits, key, temperature, self.cfg.vocab_size,
                            top_k, top_p)
        return caches, nxt

    def generate(self, prompts: np.ndarray, *, num_tokens: int,
                 extras: dict | None = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0) -> np.ndarray:
        """prompts: [B, S] int32.  Returns [B, num_tokens] generated ids."""
        b, s = prompts.shape
        start = s + (self.cfg.num_patches if self.cfg.family == "vlm" else 0)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32), **(extras or {})}
        tr = self.tracer
        if tr:
            with tr.phase(ev.PHASE_EVAL), tr.user_function(name="prefill"), \
                    self._with_rules():
                caches, logits = self._prefill(self.params, batch)
                jax.block_until_ready(logits)
        else:
            with self._with_rules():
                caches, logits = self._prefill(self.params, batch)

        key = jax.random.PRNGKey(seed)
        out = np.zeros((b, num_tokens), np.int32)
        tok = sample_logits(logits, jax.random.fold_in(key, 0), temperature,
                            self.cfg.vocab_size, top_k, top_p)
        out[:, 0] = np.asarray(tok)
        self.host_syncs += 1
        for i in range(1, num_tokens):
            idx = jnp.int32(start + i - 1)
            sub = jax.random.fold_in(key, i)
            if tr:
                with tr.user_function(name="decode_step"), self._with_rules():
                    caches, tok = self._decode_sample(
                        self.params, caches, tok, idx, sub,
                        temperature=temperature, top_k=top_k, top_p=top_p)
                tr.emit(EV_TOKENS_DECODED, i)
            else:
                with self._with_rules():
                    caches, tok = self._decode_sample(
                        self.params, caches, tok, idx, sub,
                        temperature=temperature, top_k=top_k, top_p=top_p)
            out[:, i] = np.asarray(tok)
            self.host_syncs += 1
        return out

    def throughput_stats(self, prompts, num_tokens: int, extras=None,
                         temperature: float = 0.0, top_k: int = 0,
                         top_p: float = 1.0, seed: int = 0) -> dict:
        syncs0 = self.host_syncs
        t0 = time.perf_counter()
        self.generate(prompts, num_tokens=num_tokens, extras=extras,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      seed=seed)
        dt = time.perf_counter() - t0
        total = prompts.shape[0] * num_tokens
        return {"tokens": total, "seconds": dt, "tok_per_s": total / dt,
                "host_syncs": self.host_syncs - syncs0}
