"""Serving throughput: seed loop vs the unified token-budget engine.

Five sections, all emitted as CSV rows AND collected into machine-readable
``BENCH_serve.json`` (repo root, gitignored; CI uploads it as an artifact so
the perf trajectory is tracked across PRs):

  1. seed fixed-batch loop vs the unified-step engine (tok/s, host
     round-trips) — the PR-1 comparison, now measuring the production
     unified hot path over the paged pool;
  2. equal KV-memory budget: a contiguous per-slot layout reserves
     ``max_len`` tokens per slot, so budget/max_len slots is the concurrency
     ceiling; the paged pool spends the SAME budget block-by-block on
     *actual* lengths and sustains more concurrent requests (peak active
     slots + blocks in use reported);
  2b. quantized equal-HBM budget: an int8 pool at the SAME byte budget as
     a native pool sustains >=1.8x the concurrent requests (per-position
     scale quantization, dequant fused into the decode paths — see
     docs/paged_cache.md); greedy outputs compared token-for-token;
  3. prefix-hit speedup on a shared-prompt workload (system-prompt shape):
     warm vs cold wall time and prefilled-token counts;
  4. mixed load (long-prompt + short-prompt blend, diverse lengths): the
     grouped-prefill engine vs the unified step — p95 TTFT (the grouped
     engine head-of-line-blocks decode behind whole prefills AND mints one
     compile per distinct prompt length), decode TPOT, and the
     decode-stall fraction (wall blocked in synchronous prefill / total);
  5. speculative: n-gram (prompt-lookup) drafting vs the unified baseline
     on a high-acceptance workload — tok/s, acceptance rate, verify-pass
     count, outputs asserted bit-identical;
  6. sharded: the mesh-parallel engine at mp=1 vs mp=2 on FORCED CPU
     devices (tok/s + host-syncs/iter; run in a subprocess so the forced
     device count cannot leak into this process's backend);
  7. kernels: the attention dispatch boundary end-to-end — the same wave
     served under ``kernel_mode=pallas`` (interpret mode on CPU) and
     ``kernel_mode=xla``, outputs asserted identical; plus the autotune
     cache cold-search vs warm-reload round trip;
  8. replica scaling: the multi-replica router (subprocess engines behind
     the frame protocol) on a prefix-heavy workload — aggregate tok/s at
     1 vs 2 replicas, and the routed prefix-hit fraction under
     ``route=prefix`` vs ``route=rr`` (the affinity scorer's value: rr
     scatters turn-2 traffic away from the replica holding its KV);
  9. CoW fork sampling: ``n_samples=4`` fan-out (one prefill, aliased
     prompt blocks, per-fork CoW write frontiers) vs 4 independent
     same-prompt requests at the SAME pool budget — tok/s, peak blocks
     vs a single request, and greedy fork-0 asserted bit-identical to
     the unforked oracle.

Every row and every section of the JSON names the device it ran on
(platform, device kind, device count).  The sharded and replica sections
run in CPU-pinned child processes and are labelled ``cpu``: this process
may hold the chip, and their numbers are never chip numbers.

Run as ``__main__`` the script also gates on ``BENCH_baseline.json``
(committed): a >15% regression of ``seed_vs_paged.speedup`` or
``speculative.speedup`` fails CI, as do a pallas-vs-xla output mismatch,
a cold autotune warm-reload miss, or the pallas/xla throughput ratio
falling below half its baseline (the kernel gate is deliberately loose on
CPU, where pallas runs under interpret-mode emulation — on TPU the same
gate tracks real kernel throughput).  The replica section gates 1->2
scaling at >=1.5x aggregate tok/s and prefix-routing beating rr on hit
tokens.

    PYTHONPATH=src python -m benchmarks.run        # all sections
    PYTHONPATH=src python benchmarks/bench_serve.py
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "granite-8b"
N_REQ = 8
PROMPT = 16
GEN = 32
ROOT = pathlib.Path(__file__).resolve().parents[1]
JSON_PATH = ROOT / "BENCH_serve.json"
# child sections pin the CPU: a parent that touched JAX may hold the chip
CPU_CHILD_ENV = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")}


def _device() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _tagged(rows, device):
    """Suffix every CSV row with the device it was measured on (``device``
    may be a callable, read as each row comes: a child section's device is
    known once the child has reported)."""
    for row in rows:
        d = device() if callable(device) else device
        yield f"{row} [{d['platform']} {d['device_kind']} x{d['device_count']}]"
BASELINE_PATH = ROOT / "BENCH_baseline.json"
REGRESSION_TOLERANCE = 0.15  # CI fails if speedup drops >15% vs baseline


def _seed_fixed_batch(cfg, model, params, prompts, num_tokens, max_len,
                      prefill, decode):
    """The seed ServeEngine.generate loop, verbatim: jitted decode + eager
    host-side argmax + per-token blocking fetch.  Per decoded token the host
    performs two round-trips — the eager sample chain dispatched on the
    decode output, then the blocking np.asarray — of which the fetch is a
    hard sync."""
    b, s = prompts.shape
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    caches, logits = prefill(params, batch)
    jax.block_until_ready(logits)

    fetches = eager_samples = 0
    out = np.zeros((b, num_tokens), np.int32)
    tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    eager_samples += 1
    out[:, 0] = np.asarray(tok)
    fetches += 1
    for i in range(1, num_tokens):
        caches, logits = decode(params, caches, tok, jnp.int32(s + i - 1))
        tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
        eager_samples += 1
        out[:, i] = np.asarray(tok)
        fetches += 1
    return out, fetches, eager_samples


def _bench_seed_vs_paged(cfg, model, params, results):
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (N_REQ, PROMPT)).astype(np.int32)
    max_len = PROMPT + GEN
    total = N_REQ * GEN
    REPS = 5

    from repro.serve.step import UnifiedServeEngine

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
    decode = jax.jit(model.decode_step)
    _seed_fixed_batch(cfg, model, params, prompts, GEN, max_len, prefill, decode)
    dt_seed = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        ref, fetches, eager = _seed_fixed_batch(cfg, model, params, prompts, GEN,
                                                max_len, prefill, decode)
        dt_seed = min(dt_seed, time.perf_counter() - t0)

    # throughput-tuned: 4 concurrent prefill streams (the legacy comparison
    # point used max_prefills_per_iter=N_REQ for the same reason)
    eng = UnifiedServeEngine(cfg, params, num_slots=N_REQ, max_len=max_len,
                             chunk_rows=4, max_prefills_per_iter=N_REQ)
    eng.serve_batch(prompts, num_tokens=GEN)  # warmup wave
    dt_cont = float("inf")
    for _ in range(REPS):
        syncs0, iters0 = eng.stats["decode_syncs"], eng.stats["iterations"]
        t0 = time.perf_counter()
        out = eng.serve_batch(prompts, num_tokens=GEN)
        dt_cont = min(dt_cont, time.perf_counter() - t0)
    stats = {"decode_syncs": eng.stats["decode_syncs"] - syncs0,
             "iterations": eng.stats["iterations"] - iters0}
    assert np.array_equal(out, ref), "unified engine diverged from seed loop"

    tok_s_seed = total / dt_seed
    tok_s_cont = total / dt_cont
    syncs_per_iter = stats["decode_syncs"] / max(stats["iterations"], 1)
    results["seed_vs_paged"] = {
        "tok_per_s_seed": tok_s_seed, "tok_per_s_paged": tok_s_cont,
        "speedup": tok_s_cont / tok_s_seed,
        "host_syncs_per_decode_iter": syncs_per_iter,
    }
    yield (f"serve_fixed_batch_seed,{dt_seed / total * 1e6:.1f},"
           f"{tok_s_seed:.0f} tok/s; {(fetches + eager) / GEN:.1f} host "
           f"round-trips/token ({fetches / GEN:.0f} blocking fetch + "
           f"{eager / GEN:.0f} eager sample)")
    yield (f"serve_unified_paged,{dt_cont / total * 1e6:.1f},"
           f"{tok_s_cont:.0f} tok/s; {syncs_per_iter:.2f} "
           f"host syncs/decode iteration")
    yield (f"serve_unified_speedup,,{tok_s_cont / tok_s_seed:.2f}x tok/s "
           f"({N_REQ} reqs x {GEN} tokens, {ARCH} reduced)")


def _bench_equal_budget(cfg, model, params, results):
    """Same KV token budget; short actual lengths.  Contiguous slot-math:
    budget // max_len concurrent requests.  Paged: block-gated admission."""
    from repro.serve.engine import ContinuousServeEngine

    max_len, bs = 128, 16
    n_req, prompt, gen = 12, 16, 16
    contig_slots = 4
    budget_tokens = contig_slots * max_len  # what contiguous would reserve
    num_blocks = budget_tokens // bs + 1  # same HBM spend, block granularity
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (n_req, prompt)).astype(np.int32)

    def run(engine):
        for i in range(n_req):
            engine.submit(prompts[i], gen)
        t0 = time.perf_counter()
        engine.run()
        return time.perf_counter() - t0

    # contiguous-equivalent: per-slot reserved regions (slots are the bound)
    contig = ContinuousServeEngine(
        cfg, params, num_slots=contig_slots, max_len=max_len, block_size=bs,
        prefix_cache=False, max_prefills_per_iter=contig_slots)
    run(contig)  # warmup/compile
    dt_contig = run(contig)
    # paged: same budget, slots no longer the bound
    paged = ContinuousServeEngine(
        cfg, params, num_slots=n_req, max_len=max_len, block_size=bs,
        num_blocks=num_blocks, prefix_cache=False, max_prefills_per_iter=n_req)
    run(paged)
    # report the measured run only: reset peaks, delta the counters
    paged.stats["peak_active"] = paged.stats["peak_blocks"] = 0
    preempt0 = paged.stats["preemptions"]
    dt_paged = run(paged)

    total = n_req * gen
    results["equal_budget"] = {
        "budget_tokens": budget_tokens,
        "contiguous_slots": contig_slots,
        "contiguous_tok_per_s": total / dt_contig,
        "paged_tok_per_s": total / dt_paged,
        "paged_peak_concurrent": paged.stats["peak_active"],
        "paged_peak_blocks": paged.stats["peak_blocks"],
        "paged_block_capacity": num_blocks - 1,
        "preemptions": paged.stats["preemptions"] - preempt0,
    }
    yield (f"serve_budget_contiguous,,{total / dt_contig:.0f} tok/s; "
           f"{contig_slots} slots sustained ({budget_tokens} KV tokens reserved)")
    yield (f"serve_budget_paged,,{total / dt_paged:.0f} tok/s; "
           f"{paged.stats['peak_active']} concurrent requests on the same "
           f"budget ({paged.stats['peak_blocks']}/{num_blocks - 1} blocks in use)")


def _bench_quantized_budget(cfg, model, params, results):
    """Equal HBM, quantized blocks: an int8 pool (int8 data + f32
    per-position scales) fits ~3.5x the blocks of the native f32 smoke
    pool, so a byte-matched budget sustains proportionally more concurrent
    requests.  Greedy outputs are compared token-for-token against the
    fp16 pool (bounded divergence, not bit equality — docs/paged_cache.md)."""
    from repro.serve.engine import ContinuousServeEngine

    # prompt-dominated footprint (2 of 3 blocks land at admission, so the
    # byte budget — not just-in-time decode growth — bounds concurrency)
    max_len, bs = 48, 16
    n_req, prompt, gen = 16, 32, 8
    fp16_blocks = 13  # 12 usable, 3-block requests
    prompts = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (n_req, prompt)).astype(np.int32)

    def make(kv_dtype, num_blocks):
        return ContinuousServeEngine(
            cfg.replace(kv_dtype=kv_dtype), params, num_slots=n_req,
            max_len=max_len, block_size=bs, num_blocks=num_blocks,
            prefix_cache=False, max_prefills_per_iter=n_req)

    def run(engine):
        reqs = [engine.submit(prompts[i], gen) for i in range(n_req)]
        t0 = time.perf_counter()
        out = engine.run()
        dt = time.perf_counter() - t0
        return dt, np.stack([out[r.rid] for r in reqs])

    native = make("fp16", fp16_blocks)
    budget_bytes = fp16_blocks * native.pool.block_bytes
    run(native)  # warmup/compile
    native.stats["peak_active"] = native.stats["peak_blocks"] = 0
    dt16, out16 = run(native)

    # same byte budget, int8 block granularity
    int8_blocks = budget_bytes // make("int8", fp16_blocks).pool.block_bytes
    quant = make("int8", int8_blocks)
    run(quant)
    quant.stats["peak_active"] = quant.stats["peak_blocks"] = 0
    dt8, out8 = run(quant)

    total = n_req * gen
    ratio = quant.stats["peak_active"] / max(native.stats["peak_active"], 1)
    greedy_match = float((out8 == out16).mean())
    results["quantized_equal_budget"] = {
        "budget_bytes": int(budget_bytes),
        "fp16_blocks": fp16_blocks - 1,
        "int8_blocks": int(int8_blocks) - 1,
        "bytes_per_token_fp16": native.kv_bytes_per_token,
        "bytes_per_token_int8": quant.kv_bytes_per_token,
        "fp16_tok_per_s": total / dt16,
        "int8_tok_per_s": total / dt8,
        "fp16_peak_concurrent": native.stats["peak_active"],
        "int8_peak_concurrent": quant.stats["peak_active"],
        "concurrency_ratio": ratio,
        "greedy_match": greedy_match,
    }
    yield (f"serve_quant_fp16,,{total / dt16:.0f} tok/s; "
           f"{native.stats['peak_active']} concurrent on "
           f"{budget_bytes // 1024} KiB ({native.kv_bytes_per_token} B/token)")
    yield (f"serve_quant_int8,,{total / dt8:.0f} tok/s; "
           f"{quant.stats['peak_active']} concurrent on the same bytes "
           f"({quant.kv_bytes_per_token} B/token) = {ratio:.2f}x concurrency; "
           f"greedy match {greedy_match:.1%}")


def _bench_prefix_hits(cfg, model, params, results):
    from repro.serve.engine import ContinuousServeEngine

    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, (48,)).astype(np.int32)
    n_req, gen = 8, 8
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)])
               for _ in range(n_req)]

    def run(prefix_cache):
        # one engine, two waves: wave 1 compiles the prefill shapes (and,
        # warm, populates the prefix cache); wave 2 is the measurement —
        # every warm request then hits the resident shared prefix
        eng = ContinuousServeEngine(
            cfg, params, num_slots=4, max_len=80, block_size=16,
            prefix_cache=prefix_cache, max_prefills_per_iter=4)
        for p in prompts:
            eng.submit(p, gen)
        eng.run()
        snap = dict(eng.stats)
        for p in prompts:
            eng.submit(p, gen)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        delta = {k: eng.stats[k] - snap[k]
                 for k in ("prefill_tokens", "prefix_hit_tokens")}
        return dt, delta

    dt_cold, st_cold = run(False)
    dt_warm, st_warm = run(True)
    results["prefix_hits"] = {
        "shared_prefix_tokens": int(shared.shape[0]), "requests": n_req,
        "cold_s": dt_cold, "warm_s": dt_warm,
        "speedup": dt_cold / dt_warm,
        "prefill_tokens_cold": st_cold["prefill_tokens"],
        "prefill_tokens_warm": st_warm["prefill_tokens"],
        "prefix_hit_tokens": st_warm["prefix_hit_tokens"],
    }
    yield (f"serve_prefix_cold,,{st_cold['prefill_tokens']} tokens prefilled, "
           f"{dt_cold * 1e3:.0f} ms wall")
    yield (f"serve_prefix_warm,,{st_warm['prefill_tokens']} tokens prefilled "
           f"({st_warm['prefix_hit_tokens']} served from cache), "
           f"{dt_warm * 1e3:.0f} ms wall = {dt_cold / dt_warm:.2f}x")


def _bench_mixed_load(cfg, model, params, results):
    """Long-prompt + short-prompt blend with DIVERSE lengths: the grouped
    engine head-of-line-blocks every decode slot behind each whole prefill
    and mints one prefill executable per distinct length; the unified step
    streams the long prompts in as fixed-size chunks between decode tokens
    (one compile shape).  Fresh engines, compile included — the compile
    cascade IS the grouped engine's tail latency on scenario-diverse
    traffic."""
    from repro.serve.engine import ContinuousServeEngine
    from repro.serve.step import UnifiedServeEngine

    lens = [64, 5, 9, 13, 64, 7, 11, 15]
    gen, max_len, slots = 16, 96, 4

    def run(make):
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        eng = make()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen) for p in prompts]
        eng.run()
        wall = time.perf_counter() - t0
        ttft_ms = np.array([r.ttft_ns() / 1e6 for r in reqs])
        tpot_ms = np.array([r.tpot_ns() / 1e6 for r in reqs])
        return {
            "wall_s": wall,
            "p95_ttft_ms": float(np.percentile(ttft_ms, 95)),
            "p50_ttft_ms": float(np.percentile(ttft_ms, 50)),
            "p50_tpot_ms": float(np.percentile(tpot_ms, 50)),
            "decode_stall_fraction":
                eng.stats["prefill_seconds"] / max(wall, 1e-9),
        }

    grouped = run(lambda: ContinuousServeEngine(
        cfg, params, num_slots=slots, max_len=max_len, block_size=16))
    unified = run(lambda: UnifiedServeEngine(
        cfg, params, num_slots=slots, max_len=max_len, block_size=16,
        chunk_size=16))
    results["mixed_load"] = {
        "lens": lens, "gen": gen, "grouped": grouped, "unified": unified,
        "p95_ttft_improvement": grouped["p95_ttft_ms"] / unified["p95_ttft_ms"],
        "tpot_ratio": unified["p50_tpot_ms"] / max(grouped["p50_tpot_ms"], 1e-9),
    }
    yield (f"serve_mixed_grouped,,p95 TTFT {grouped['p95_ttft_ms']:.0f} ms; "
           f"TPOT p50 {grouped['p50_tpot_ms']:.1f} ms; decode-stall "
           f"{grouped['decode_stall_fraction']:.0%} of wall")
    yield (f"serve_mixed_unified,,p95 TTFT {unified['p95_ttft_ms']:.0f} ms; "
           f"TPOT p50 {unified['p50_tpot_ms']:.1f} ms; decode-stall "
           f"{unified['decode_stall_fraction']:.0%} of wall")
    yield (f"serve_mixed_ttft_gain,,{grouped['p95_ttft_ms'] / unified['p95_ttft_ms']:.2f}x "
           f"p95 TTFT (long+short blend, {len(lens)} reqs, "
           f"{len(set(lens))} distinct prompt lengths)")


def _bench_speculative(cfg, model, params, results):
    """Speculative decoding (n-gram / prompt-lookup drafting) vs the
    unified baseline on a HIGH-ACCEPTANCE workload.

    Construction: candidate prompts are primed with the model's own greedy
    continuation (the serving analogue of grounded/summarization traffic,
    where the output substantially overlaps the input), then filtered to
    the ones whose continuation the n-gram proposer actually predicts —
    a pure host-side check, fully deterministic given the seeded params.
    Greedy spec decode must stay BIT-identical to the baseline while
    committing up to K+1 tokens per verify pass."""
    from repro.serve.spec import NGramProposer
    from repro.serve.step import UnifiedServeEngine

    gen, prime, spec_k, max_len = 24, 40, 11, 256
    prop = NGramProposer()
    rng = np.random.default_rng(2)
    cands = [rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
             for _ in range(12)]
    prim = UnifiedServeEngine(cfg, params, num_slots=4, max_len=max_len,
                              block_size=16)
    reqs = [prim.submit(s, prime + gen) for s in cands]
    po = prim.run()
    scored = []
    for s, r in zip(cands, reqs):
        full = po[r.rid]
        ctx = np.concatenate([s, full[:prime]])
        pred = prop._continuation(np.asarray(ctx), gen)
        scored.append(((pred == full[prime:prime + gen]).mean(), ctx))
    # single-stream on purpose: batching amortizes the baseline's narrow
    # forwards across slots, so the per-pass economics that speculation
    # improves are cleanest at one decode stream (interactive tail latency)
    scored.sort(key=lambda t: -t[0])
    prompts = [ctx for sc, ctx in scored if sc >= 0.9][:1] or [scored[0][1]]

    def run(eng, reps=7):
        for p in prompts:
            eng.submit(p, gen)
        eng.run()  # warmup/compile wave
        best, out = float("inf"), None
        for _ in range(reps):
            d0 = eng.stats.get("spec_drafted", 0)
            a0 = eng.stats.get("spec_accepted", 0)
            v0 = eng.stats.get("spec_dispatches", 0)
            rs = [eng.submit(p, gen) for p in prompts]
            t0 = time.perf_counter()
            res = eng.run()
            dt = time.perf_counter() - t0
            if dt < best:
                best, out = dt, [res[r.rid] for r in rs]
        return best, out, {
            "drafted": eng.stats.get("spec_drafted", 0) - d0,
            "accepted": eng.stats.get("spec_accepted", 0) - a0,
            "verify_dispatches": eng.stats.get("spec_dispatches", 0) - v0,
        }

    base = UnifiedServeEngine(cfg, params, num_slots=len(prompts),
                              max_len=max_len, block_size=16,
                              prefix_cache=False)
    spec = UnifiedServeEngine(cfg, params, num_slots=len(prompts),
                              max_len=max_len, block_size=16,
                              prefix_cache=False, spec=NGramProposer(),
                              spec_k=spec_k,
                              max_step_tokens=len(prompts) * (spec_k + 1) + 32)
    dt_b, out_b, _ = run(base)
    dt_s, out_s, sp = run(spec)
    for a, b in zip(out_b, out_s):
        assert np.array_equal(a, b), "spec decode diverged from the oracle"
    total = len(prompts) * gen
    acceptance = sp["accepted"] / max(sp["drafted"], 1)
    results["speculative"] = {
        "requests": len(prompts), "gen": gen, "spec_k": spec_k,
        "tok_per_s_base": total / dt_b, "tok_per_s_spec": total / dt_s,
        "speedup": dt_b / dt_s, "acceptance": acceptance,
        "verify_dispatches": sp["verify_dispatches"],
        "drafted": sp["drafted"], "accepted": sp["accepted"],
    }
    yield (f"serve_spec_base,,{total / dt_b:.0f} tok/s "
           f"(unified, {len(prompts)} reqs x {gen} tokens)")
    yield (f"serve_spec_ngram,,{total / dt_s:.0f} tok/s; acceptance "
           f"{acceptance:.0%}; {sp['verify_dispatches']} verify passes "
           f"(K={spec_k})")
    yield (f"serve_spec_speedup,,{dt_b / dt_s:.2f}x tok/s on the "
           f"high-acceptance workload (bit-identical outputs)")


def _sharded_child():
    """Child process (forced 2 CPU devices via the parent's env): paged
    engine at mp=1 vs mp=2, greedy-equal outputs asserted, one JSON line on
    stdout.  Then the unified engine at mp=2 with communication/compute
    overlap off vs on (micro-batched span pipeline + two-deep dispatch
    queue): outputs must stay greedy-equal, and a traced run of each mode
    reports the collective blocked/overlapped split measured from the
    MERGED ``.prv`` — the gate asserts the optimization from the same
    trace the paper's tooling reads."""
    import pathlib
    import tempfile

    from repro import core as xtrace
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.core.analysis import comm_overlap_summary
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousServeEngine
    from repro.serve.step import UnifiedServeEngine

    cfg = reduced(get_config(ARCH), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (N_REQ, PROMPT)).astype(np.int32)
    out: dict = {}
    ref = None
    for mp in (1, 2):
        mesh = make_mesh((1, mp), ("data", "model"))
        eng = ContinuousServeEngine(cfg, params, num_slots=N_REQ,
                                    max_len=PROMPT + GEN,
                                    max_prefills_per_iter=N_REQ, mesh=mesh)
        toks = eng.serve_batch(prompts, num_tokens=GEN)  # warmup/compile
        if ref is None:
            ref = toks
        else:
            assert np.array_equal(toks, ref), "mp=2 diverged from mp=1"
        syncs0, iters0 = eng.stats["decode_syncs"], eng.stats["iterations"]
        t0 = time.perf_counter()
        eng.serve_batch(prompts, num_tokens=GEN)
        dt = time.perf_counter() - t0
        out[f"mp{mp}"] = {
            "tok_per_s": N_REQ * GEN / dt,
            "host_syncs_per_decode_iter":
                (eng.stats["decode_syncs"] - syncs0)
                / max(eng.stats["iterations"] - iters0, 1),
            # overlap=auto: the two-deep dispatch queue engages at mp>1
            "planned_ahead": eng.stats["planned_ahead"],
        }

    # unified-engine family (the ratio must be apples-to-apples: the
    # unified step pays chunk planning the legacy burst engine does not)
    tmp = pathlib.Path(tempfile.mkdtemp())
    runs = [("unified_mp1", 1, "off"),
            ("mp2_overlap_off", 2, "off"),
            ("mp2_overlap", 2, "on")]
    uref = None
    for key, mp, mode in runs:
        kw = dict(num_slots=N_REQ, max_len=PROMPT + GEN,
                  mesh=make_mesh((1, mp), ("data", "model")), overlap=mode)
        eng = UnifiedServeEngine(cfg, params, **kw)
        toks = eng.serve_batch(prompts, num_tokens=GEN)  # warmup/compile
        if uref is None:
            uref = toks
        else:
            assert np.array_equal(toks, uref), f"{key} diverged"
        syncs0, iters0 = eng.stats["decode_syncs"], eng.stats["iterations"]
        t0 = time.perf_counter()
        eng.serve_batch(prompts, num_tokens=GEN)
        dt = time.perf_counter() - t0
        assert eng.stats["decode_syncs"] == eng.stats["decode_dispatches"]
        out[key] = {
            "tok_per_s": N_REQ * GEN / dt,
            "host_syncs_per_decode_iter":
                (eng.stats["decode_syncs"] - syncs0)
                / max(eng.stats["iterations"] - iters0, 1),
            "planned_ahead": eng.stats["planned_ahead"],
        }
        if mp == 1:
            continue
        # separate traced engine: the timed numbers above stay untraced so
        # the mode comparison is not skewed by trace overhead
        tracer = xtrace.init(f"bench-ovl-{mode}")
        teng = UnifiedServeEngine(cfg, params, tracer=tracer,
                                  flush_every=8,
                                  flush_base=tmp / f"ovl-{mode}", **kw)
        teng.serve_batch(prompts, num_tokens=GEN)
        segments = list(tracer.segments)
        trace = xtrace.finish()
        paths = xtrace.write_prv(trace, tmp / f"ovl-{mode}",
                                 segments=segments)
        comm = comm_overlap_summary(xtrace.parse_prv(paths["prv"]))
        out[key]["comm_blocked_fraction"] = comm["blocked_fraction"]
        out[key]["comm_overlap_fraction"] = comm["overlap_fraction"]
    # the gated scaling ratio measures the configuration as shipped:
    # overlap=auto engages the two-deep dispatch queue at mp>1 (mp1 keeps
    # the classic one-deep pipeline).  The unified triple above isolates
    # the device-layer micro-batch pipeline; its schedule-derived
    # comm_blocked_fraction is the deterministic half of the gate.
    out["overlap_ratio"] = out["mp2"]["tok_per_s"] / out["mp1"]["tok_per_s"]
    out["unified_overlap_speedup"] = (out["mp2_overlap"]["tok_per_s"]
                                      / out["mp2_overlap_off"]["tok_per_s"])
    out["device"] = _device()
    print(json.dumps(out))


def _bench_sharded(results):
    env = {**os.environ, **CPU_CHILD_ENV,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    r = subprocess.run([sys.executable, __file__, "--sharded-child"],
                       capture_output=True, text=True, env=env, timeout=560)
    if r.returncode != 0:
        # recorded so check_regression fails the run — a crashed child (or
        # its mp=2-vs-mp=1 equality assert) must not leave CI green
        results["sharded"] = {"failed": (r.stdout + r.stderr)[-400:]}
        yield f"serve_sharded,,FAILED: {(r.stdout + r.stderr)[-400:]}"
        return
    sharded = json.loads(r.stdout.strip().splitlines()[-1])
    results["sharded"] = sharded
    for mp in ("mp1", "mp2"):
        s = sharded[mp]
        yield (f"serve_sharded_{mp},,{s['tok_per_s']:.0f} tok/s; "
               f"{s['host_syncs_per_decode_iter']:.2f} host syncs/decode "
               f"iteration (2 forced CPU devices)")
    u = sharded["unified_mp1"]
    yield (f"serve_sharded_unified_mp1,,{u['tok_per_s']:.0f} tok/s "
           f"(unified engine, single device — the overlap ratio's "
           f"denominator)")
    for key, label in (("mp2_overlap_off", "mp2_unified"),
                       ("mp2_overlap", "mp2_overlap")):
        s = sharded[key]
        yield (f"serve_sharded_{label},,{s['tok_per_s']:.0f} tok/s; "
               f"comm blocked {s['comm_blocked_fraction']:.0%} / overlapped "
               f"{s['comm_overlap_fraction']:.0%} of collective time "
               f"(merged .prv); {s['planned_ahead']} planned-ahead "
               f"dispatches")
    yield (f"serve_sharded_overlap_ratio,,{sharded['overlap_ratio']:.2f}x "
           f"mp2/mp1 tok/s with overlap=auto (greedy bit-identical; "
           f"unified mp2 overlap speedup "
           f"{sharded['unified_overlap_speedup']:.2f}x)")


def _replicas_child():
    """Child process: the multi-replica router on a prefix-heavy workload.

    Two-wave construction (the router's own lesson: requests dispatched in
    ONE wave get zero actual prefix hits, because the second member of a
    shared-prefix pair is admitted before the first has registered its
    blocks).  Wave 1 seeds one member per prefix group — it compiles the
    engines AND populates each replica's prefix cache; wave 2 is the
    measurement: every request shares a warm 64-token prefix, so
    ``route=prefix`` sends it to the replica already holding that KV while
    ``route=rr`` scatters half the traffic cold.

    The scaling claim is AGGREGATE CAPACITY, the dimension that actually
    doubles when a second identical replica joins: the per-replica pool is
    sized so one replica offered the whole four-group load runs out of
    blocks — it evicts warm prefixes (recomputing them at the next hit)
    and preempts mid-decode (recomputing the whole prompt) — while two
    replicas hold two groups each with headroom.  On the single-core CI
    box that recompute is the measured wall-clock difference; with real
    cores per replica the compute-parallel term stacks on top.  One JSON
    line on stdout."""
    from repro.configs import get_config, reduced
    from repro.serve.router import Router

    bs, shared_blocks, gen = 16, 4, 8
    groups, per_group, reps = 4, 2, 3
    vocab = reduced(get_config(ARCH), num_layers=2).vocab_size
    rng = np.random.default_rng(7)
    heads = [rng.integers(0, vocab, (shared_blocks * bs,)).astype(np.int32)
             for _ in range(groups)]
    warm = [np.concatenate([heads[g],
                            rng.integers(0, vocab, (5 + g,)).astype(np.int32)])
            for g in range(groups)]
    wave = [np.concatenate([heads[g],
                            rng.integers(0, vocab,
                                         (6 + 2 * g + m,)).astype(np.int32)])
            for g in range(groups) for m in range(per_group)]
    # per-replica pool: two groups (8 shared + ~8 private blocks) fit with
    # headroom; all four groups + 8 in-flight requests do NOT — the
    # capacity term the second replica doubles
    eng = {"num_slots": 4, "max_len": shared_blocks * bs + 16 + gen,
           "block_size": bs, "chunk_size": bs, "num_blocks": 24}
    wenv = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}

    def run(n, route):
        with Router(ARCH, num_replicas=n, route=route,
                    reduced={"num_layers": 2}, engine=eng,
                    worker_env=wenv) as router:
            for p in warm:
                router.submit(p, gen)
            router.run()
            snap = dict(router.stats)
            best, frac = float("inf"), 0.0
            for rep in range(reps):
                for p in wave:
                    router.submit(p, gen)
                t0 = time.perf_counter()
                router.run()
                best = min(best, time.perf_counter() - t0)
                if rep == 0:
                    # hit fraction from the FIRST timed wave only: repeats
                    # re-register every prefix on whichever replica served
                    # it, converging rr toward all-hit
                    hit = (router.stats["prefix_hit_tokens"]
                           - snap["prefix_hit_tokens"])
                    tot = (router.stats["prompt_tokens"]
                           - snap["prompt_tokens"])
                    frac = hit / max(tot, 1)
            return {"tok_per_s": len(wave) * gen / best,
                    "hit_fraction": frac,
                    "route_decisions": router.stats["route_decisions"],
                    "bounces": router.stats["bounces"]}

    out = {"replicas1": run(1, "prefix"),
           "replicas2_prefix": run(2, "prefix"),
           "replicas2_rr": run(2, "rr")}
    out["scaling_ratio"] = (out["replicas2_prefix"]["tok_per_s"]
                            / out["replicas1"]["tok_per_s"])
    out["device"] = _device()  # the workers' env pins the same CPU backend
    print(json.dumps(out))


def _bench_replicas(results):
    env = {**os.environ, **CPU_CHILD_ENV}
    r = subprocess.run([sys.executable, __file__, "--replicas-child"],
                       capture_output=True, text=True, env=env, timeout=560)
    if r.returncode != 0:
        # recorded so check_regression fails the run — a crashed child
        # must not leave CI green
        results["replica_scaling"] = {"failed": (r.stdout + r.stderr)[-400:]}
        yield f"serve_replicas,,FAILED: {(r.stdout + r.stderr)[-400:]}"
        return
    rs = json.loads(r.stdout.strip().splitlines()[-1])
    results["replica_scaling"] = rs
    yield (f"serve_replicas_1,,{rs['replicas1']['tok_per_s']:.0f} tok/s "
           f"aggregate (1 replica, prefix route)")
    yield (f"serve_replicas_2,,{rs['replicas2_prefix']['tok_per_s']:.0f} "
           f"tok/s aggregate (2 replicas); prefix-hit fraction "
           f"{rs['replicas2_prefix']['hit_fraction']:.0%} (prefix route) vs "
           f"{rs['replicas2_rr']['hit_fraction']:.0%} (rr)")
    yield (f"serve_replicas_scaling,,{rs['scaling_ratio']:.2f}x aggregate "
           f"tok/s 1->2 replicas (shared-prefix waves, "
           f"{rs['replicas2_prefix']['route_decisions']} routed admits)")


def _bench_kernels(cfg, model, params, results):
    """Section 7: pallas-vs-xla dispatch on a served wave + autotune cache."""
    import tempfile

    from repro.kernels.attention import autotune
    from repro.serve.engine import ContinuousServeEngine

    gen, n_req = 16, 4
    prompts = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (n_req, PROMPT)).astype(np.int32)
    runs, outs = {}, {}
    for mode in ("xla", "pallas"):
        eng = ContinuousServeEngine(cfg.replace(kernel_mode=mode), params,
                                    num_slots=n_req, max_len=PROMPT + gen,
                                    block_size=16,
                                    max_prefills_per_iter=n_req)
        outs[mode] = eng.serve_batch(prompts, num_tokens=gen)  # warmup
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = eng.serve_batch(prompts, num_tokens=gen)
            best = min(best, time.perf_counter() - t0)
        assert np.array_equal(out, outs[mode])
        runs[mode] = {"tok_per_s": n_req * gen / best,
                      "dispatch": dict(eng.stats["kernel_dispatch"])}
    bit_identical = bool(np.array_equal(outs["pallas"], outs["xla"]))

    # autotune: cold search (compile + time every candidate), drop the
    # in-process memo, then reload from the private disk cache
    kw = dict(head_dim=cfg.head_dim, kv_heads=cfg.num_kv_heads,
              block_size=16, window=cfg.attention_window, dtype=cfg.dtype,
              platform=jax.default_backend())
    saved = {k: os.environ.get(k)
             for k in (autotune.CACHE_ENV, autotune.SEARCH_ENV)}
    with tempfile.TemporaryDirectory() as td:
        os.environ[autotune.CACHE_ENV] = str(pathlib.Path(td) / "tune.json")
        os.environ[autotune.SEARCH_ENV] = "search"
        try:
            autotune.clear_memory()
            t0 = time.perf_counter()
            cold = autotune.params_for("paged_span", **kw)
            dt_cold = time.perf_counter() - t0
            autotune.clear_memory()  # simulate a fresh process: disk only
            t0 = time.perf_counter()
            warm = autotune.params_for("paged_span", **kw)
            dt_warm = time.perf_counter() - t0
        finally:
            autotune.clear_memory()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    warm_hit = bool(warm == cold and dt_warm < dt_cold)

    results["kernels"] = {
        "tok_per_s_xla": runs["xla"]["tok_per_s"],
        "tok_per_s_pallas": runs["pallas"]["tok_per_s"],
        "pallas_to_xla_ratio":
            runs["pallas"]["tok_per_s"] / runs["xla"]["tok_per_s"],
        "bit_identical": bit_identical,
        "dispatch_pallas": runs["pallas"]["dispatch"],
        "autotune": {"cold_s": dt_cold, "warm_s": dt_warm,
                     "warm_hit": warm_hit, "params": cold},
    }
    yield (f"serve_kernel_xla,,{runs['xla']['tok_per_s']:.0f} tok/s "
           f"(gather path)")
    yield (f"serve_kernel_pallas,,{runs['pallas']['tok_per_s']:.0f} tok/s "
           f"(interpret mode off-TPU); dispatches "
           f"{runs['pallas']['dispatch']}; bit-identical={bit_identical}")
    yield (f"serve_kernel_autotune,,cold search {dt_cold * 1e3:.0f} ms -> "
           f"warm reload {dt_warm * 1e3:.1f} ms (hit={warm_hit}, "
           f"params={cold})")


def _bench_fork_sampling(cfg, model, params, results):
    """Section 9: n-way sampling via CoW forking vs the naive alternative.

    Both engines get the SAME pool budget — sized so the CoW fan fits
    whole (shared prompt blocks + per-fork write frontiers) while four
    independent 12-block requests cannot all be resident and must run as
    waves.  The fork path additionally pays ONE chunked prefill of the
    164-token prompt where the independent path pays four; together those
    are the claimed >=2x."""
    from repro.serve.step import UnifiedServeEngine

    n, prompt_len, gen, bs = 4, 164, 28, 16
    max_len = prompt_len + gen
    # 25 usable blocks: one request spans 12, so two independent requests
    # fit concurrently; the fan needs ~11 aliased + (n-1) CoW tails +
    # n decode-frontier blocks and fits whole
    num_blocks = 26
    prompt = np.random.default_rng(6).integers(
        0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
    REPS = 3

    def make():
        return UnifiedServeEngine(
            cfg, params, num_slots=n, max_len=max_len, block_size=bs,
            chunk_size=bs, num_blocks=num_blocks, prefix_cache=False)

    # single-request oracle: greedy tokens + solo block residency
    solo = make()
    r = solo.submit(prompt, gen)
    want = solo.run()[r.rid]  # warmup/compile
    solo.stats["peak_blocks"] = 0
    r = solo.submit(prompt, gen)
    assert np.array_equal(solo.run()[r.rid], want)
    single_peak = solo.stats["peak_blocks"]

    # 4 independent same-prompt requests (prefix cache off: no sharing)
    indep = make()
    [indep.submit(prompt, gen) for _ in range(n)]
    indep.run()  # warmup
    dt_ind = float("inf")
    for _ in range(REPS):
        rs = [indep.submit(prompt, gen) for _ in range(n)]
        t0 = time.perf_counter()
        out = indep.run()
        dt_ind = min(dt_ind, time.perf_counter() - t0)
    for req in rs:
        assert np.array_equal(out[req.rid], want)

    # one admission, n_samples=4: one prefill, CoW fan at prompt end
    fork = make()
    fork.submit(prompt, gen, n_samples=n)
    fork.run()  # warmup
    dt_fork, forks = float("inf"), 0
    for _ in range(REPS):
        f0 = fork.pool.stats["forks"]
        c0 = fork.pool.stats["cow_copies"]
        fork.stats["peak_blocks"] = fork.stats["peak_shared"] = 0
        rp = fork.submit(prompt, gen, n_samples=n)
        t0 = time.perf_counter()
        out = fork.run()
        dt_fork = min(dt_fork, time.perf_counter() - t0)
        forks = fork.pool.stats["forks"] - f0
        cow_copies = fork.pool.stats["cow_copies"] - c0
    fork0_match = bool(np.array_equal(out[rp.rid], want))
    all_match = fork0_match and all(
        np.array_equal(out[k.rid], want) for k in rp.forks)

    total = n * gen
    results["fork_sampling"] = {
        "n": n, "prompt_len": prompt_len, "gen": gen,
        "pool_blocks": num_blocks - 1,
        "tok_per_s_independent": total / dt_ind,
        "tok_per_s_forked": total / dt_fork,
        "speedup": dt_ind / dt_fork,
        "forks": forks, "cow_copies": cow_copies,
        "peak_blocks_forked": fork.stats["peak_blocks"],
        "peak_blocks_single": single_peak,
        "peak_ratio": fork.stats["peak_blocks"] / max(single_peak, 1),
        "peak_shared_blocks": fork.stats["peak_shared"],
        "fork0_greedy_match": fork0_match,
        "all_streams_match": all_match,
    }
    yield (f"serve_fork_independent,,{total / dt_ind:.0f} tok/s "
           f"({n} separate requests, {num_blocks - 1}-block pool)")
    yield (f"serve_fork_cow,,{total / dt_fork:.0f} tok/s (n_samples={n}: "
           f"{forks} forks, {cow_copies} CoW copies, peak "
           f"{fork.stats['peak_shared']} blocks shared)")
    yield (f"serve_fork_speedup,,{dt_ind / dt_fork:.2f}x tok/s at equal "
           f"pool budget; peak blocks {fork.stats['peak_blocks']} vs "
           f"{single_peak} solo = {fork.stats['peak_blocks'] / max(single_peak, 1):.2f}x; "
           f"fork-0 greedy match={fork0_match}")


def check_regression(results) -> int:
    """Compare against the committed baseline; nonzero = CI failure."""
    if results.get("sharded", {}).get("failed"):
        print("REGRESSION: sharded section failed "
              f"({results['sharded']['failed'][:200]})")
        return 1
    if results.get("replica_scaling", {}).get("failed"):
        print("REGRESSION: replica_scaling section failed "
              f"({results['replica_scaling']['failed'][:200]})")
        return 1
    if not BASELINE_PATH.exists():
        print(f"regression gate: no {BASELINE_PATH.name}, skipping")
        return 0
    base = json.loads(BASELINE_PATH.read_text())
    rc = 0
    gates = [("seed_vs_paged.speedup", "seed_vs_paged")]
    if "speculative" in base:
        gates.append(("speculative.speedup", "speculative"))
    for label, key in gates:
        floor = base[key]["speedup"] * (1 - REGRESSION_TOLERANCE)
        got = results[key]["speedup"]
        if got < floor:
            print(f"REGRESSION: {label} {got:.2f} < floor {floor:.2f} "
                  f"(baseline {base[key]['speedup']:.2f} "
                  f"- {REGRESSION_TOLERANCE:.0%})")
            rc = 1
        else:
            print(f"regression gate: {label} {got:.2f} >= floor "
                  f"{floor:.2f} OK")
    if "quantized_equal_budget" in base:
        q = results.get("quantized_equal_budget", {})
        # hard floor 1.8x (the quantization tentpole's claim) OR baseline
        # minus tolerance, whichever is stricter at this scale
        floor = max(1.8, base["quantized_equal_budget"]["concurrency_ratio"]
                    * (1 - REGRESSION_TOLERANCE))
        got = q.get("concurrency_ratio", 0.0)
        if got < floor:
            print(f"REGRESSION: quantized_equal_budget.concurrency_ratio "
                  f"{got:.2f} < floor {floor:.2f}")
            rc = 1
        else:
            print(f"regression gate: quantized_equal_budget."
                  f"concurrency_ratio {got:.2f} >= floor {floor:.2f} OK")
        if q.get("greedy_match", 0.0) < 0.75:
            print(f"REGRESSION: quantized_equal_budget.greedy_match "
                  f"{q.get('greedy_match', 0.0):.2f} < 0.75 — int8 decode "
                  f"diverged beyond the committed bound")
            rc = 1
    if "kernels" in base:
        k = results.get("kernels", {})
        if not k.get("bit_identical"):
            print("REGRESSION: kernels.bit_identical — pallas dispatch "
                  "changed served tokens")
            rc = 1
        if not k.get("autotune", {}).get("warm_hit"):
            print("REGRESSION: kernels.autotune.warm_hit — persisted "
                  "search result was not reloaded")
            rc = 1
        # loose ratio floor: interpret-mode emulation off-TPU, so only a
        # halving of the pallas/xla ratio (dispatch-overhead blowup) fails
        floor = base["kernels"]["pallas_to_xla_ratio"] * 0.5
        got = k.get("pallas_to_xla_ratio", 0.0)
        if got < floor:
            print(f"REGRESSION: kernels.pallas_to_xla_ratio {got:.3f} < "
                  f"floor {floor:.3f}")
            rc = 1
        else:
            print(f"regression gate: kernels.pallas_to_xla_ratio "
                  f"{got:.3f} >= floor {floor:.3f} OK")
    if "overlap_ratio" in base.get("sharded", {}):
        sh = results.get("sharded", {})
        # hard floor 0.70 (the overlap tentpole's claim vs the pre-overlap
        # 0.54) OR the committed baseline minus tolerance, whichever is
        # stricter on this machine
        floor = max(0.70, base["sharded"]["overlap_ratio"]
                    * (1 - REGRESSION_TOLERANCE))
        got = sh.get("overlap_ratio", 0.0)
        if got < floor:
            print(f"REGRESSION: sharded.overlap_ratio {got:.2f} < floor "
                  f"{floor:.2f}")
            rc = 1
        else:
            print(f"regression gate: sharded.overlap_ratio {got:.2f} >= "
                  f"floor {floor:.2f} OK")
        on = sh.get("mp2_overlap", {})
        off = sh.get("mp2_overlap_off", {})
        if on.get("comm_blocked_fraction", 1.0) \
                >= off.get("comm_blocked_fraction", 0.0):
            print("REGRESSION: comm-blocked fraction not reduced by the "
                  f"overlap pipeline ({on.get('comm_blocked_fraction')} vs "
                  f"{off.get('comm_blocked_fraction')} in the merged .prv)")
            rc = 1
        else:
            print(f"regression gate: comm blocked "
                  f"{on['comm_blocked_fraction']:.0%} (overlap on) < "
                  f"{off['comm_blocked_fraction']:.0%} (off) OK")
    if "fork_sampling" in base:
        fk = results.get("fork_sampling", {})
        # hard floor 2.0x (the CoW-fork tentpole's claim) OR the committed
        # baseline minus tolerance, whichever is stricter on this machine
        floor = max(2.0, base["fork_sampling"]["speedup"]
                    * (1 - REGRESSION_TOLERANCE))
        got = fk.get("speedup", 0.0)
        if got < floor:
            print(f"REGRESSION: fork_sampling.speedup {got:.2f} < floor "
                  f"{floor:.2f}")
            rc = 1
        else:
            print(f"regression gate: fork_sampling.speedup {got:.2f} >= "
                  f"floor {floor:.2f} OK")
        if not fk.get("fork0_greedy_match"):
            print("REGRESSION: fork_sampling.fork0_greedy_match — the "
                  "forked fan changed fork 0's greedy tokens")
            rc = 1
        if fk.get("peak_ratio", 99.0) >= 2.0:
            print(f"REGRESSION: fork_sampling.peak_ratio "
                  f"{fk.get('peak_ratio'):.2f} >= 2.0 — the fan is copying "
                  f"instead of aliasing prompt blocks")
            rc = 1
        else:
            print(f"regression gate: fork_sampling.peak_ratio "
                  f"{fk.get('peak_ratio', 0.0):.2f} < 2.0 OK "
                  f"({fk.get('peak_shared_blocks', 0)} blocks shared at peak)")
    if "replica_scaling" in base:
        rs = results.get("replica_scaling", {})
        # hard floor 1.5x (the router tentpole's claim) OR the committed
        # baseline minus tolerance, whichever is stricter on this machine
        floor = max(1.5, base["replica_scaling"]["scaling_ratio"]
                    * (1 - REGRESSION_TOLERANCE))
        got = rs.get("scaling_ratio", 0.0)
        if got < floor:
            print(f"REGRESSION: replica_scaling.scaling_ratio {got:.2f} < "
                  f"floor {floor:.2f}")
            rc = 1
        else:
            print(f"regression gate: replica_scaling.scaling_ratio "
                  f"{got:.2f} >= floor {floor:.2f} OK")
        pf = rs.get("replicas2_prefix", {}).get("hit_fraction", 0.0)
        rf = rs.get("replicas2_rr", {}).get("hit_fraction", 1.0)
        if pf <= rf:
            print(f"REGRESSION: prefix routing did not beat rr on hit "
                  f"tokens ({pf:.0%} vs {rf:.0%})")
            rc = 1
        else:
            print(f"regression gate: prefix-hit fraction {pf:.0%} "
                  f"(prefix route) > {rf:.0%} (rr) OK")
    return rc


def bench(results: dict | None = None):
    from repro.configs import get_config, reduced
    from repro.models.model import build_model

    cfg = reduced(get_config(ARCH), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if results is None:
        results = {}
    results["arch"] = f"{ARCH} (reduced)"
    here = results["device"] = _device()
    # a crashed child reports nothing; its env pinned it to the CPU
    cpu_child = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    for section in (_bench_seed_vs_paged, _bench_equal_budget,
                    _bench_quantized_budget, _bench_prefix_hits,
                    _bench_mixed_load, _bench_speculative):
        yield from _tagged(section(cfg, model, params, results), here)
    yield from _tagged(_bench_sharded(results),
                       lambda: results["sharded"].get("device", cpu_child))
    yield from _tagged(_bench_kernels(cfg, model, params, results), here)
    yield from _tagged(
        _bench_replicas(results),
        lambda: results["replica_scaling"].get("device", cpu_child))
    yield from _tagged(_bench_fork_sampling(cfg, model, params, results),
                       here)
    JSON_PATH.write_text(json.dumps(results, indent=2) + "\n")
    yield f"serve_bench_json,,{JSON_PATH.name} written"


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
        sys.exit(0)
    if "--replicas-child" in sys.argv:
        _replicas_child()
        sys.exit(0)
    print("name,us_per_call,derived")
    results: dict = {}
    for row in bench(results):
        print(row)
    sys.exit(check_regression(results))
