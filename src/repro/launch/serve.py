"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b \\
        --requests 16 --prompt-len 32 --gen 64 --trace --flush-every 16

    # tensor-parallel over a 1x2 device mesh (CPU: devices are forced)
    PYTHONPATH=src python -m repro.launch.serve --mp 2 --trace

Default mode is the unified token-budget engine (``--mode unified``):
each scheduler iteration assembles ONE mixed batch under
``--max-step-tokens`` — every decode slot gets a token and the in-flight
prompt streams ``--chunk-size`` prefill chunks from the remainder, so
long prompts never head-of-line-block decode (docs/chunked_prefill.md).
``--mode continuous`` keeps the legacy two-path engine (grouped
same-length prefill + decode bursts; the unified engine's equivalence
oracle) and ``--mode static`` the rectangular-batch path over contiguous
caches.  Both continuous modes pool attention K/V in a paged block pool
(``--block-size`` / ``--num-blocks`` size it; ``--no-prefix-cache``
disables prompt prefix reuse).  With ``--trace --flush-every N`` the
trace is streamed to disk mid-run and segment-merged into the final
``.prv``; traced runs print a TTFT/TPOT latency summary at exit
(:func:`repro.core.analysis.serve_latency_summary`).

``--mesh dp,mp`` (or the ``--mp N`` shorthand) runs the engine
tensor-parallel over a ``data x model`` mesh: parameters and the paged KV
pool are sharded per :func:`repro.sharding.partition.make_serve_rules`
(the full sharding summary is printed BEFORE the first compile — a
misconfigured mesh fails loudly here), and a traced run records one
stream per mesh_data TASK, merged mpi2prv-style into the final ``.prv``
(see docs/distributed_serving.md).  On CPU the requested device count is
forced via ``xla_force_host_platform_device_count``.

``--layers N`` serves the registry config at its published widths and
dtype, cut to its first N layers (N = the registry depth serves it whole);
without it the CLI serves ``reduced()``, the CPU test preset.  On a TPU
every result line names the device, and ``auto`` kernel dispatch refuses
to fall back to XLA (``repro.kernels.attention.dispatch``).

``--overlap on|off|auto`` controls communication/compute overlap for
sharded runs: the span batch is micro-batched inside the jitted step so
one micro-batch's TP all-reduces drain under the other's compute, and the
host keeps a two-deep dispatch queue (plan N+1 while N executes).  Greedy
output is bit-identical either way; traced runs report the overlapped
fraction of collective time in the exit latency summary (the
``EV_COMM_OVERLAP_US`` / ``EV_COMM_BLOCKED_US`` counters in the ``.prv``).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

import numpy as np


def _parse_mesh(args, parser) -> tuple[int, int] | None:
    """(dp, mp) from --mesh/--mp, or None for single-device serving."""
    if args.mesh and args.mp:
        parser.error("--mesh and --mp are mutually exclusive")
    if args.mp:
        return (1, args.mp)
    if args.mesh:
        try:
            dp, mp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            parser.error(f"--mesh expects 'dp,mp', got {args.mesh!r}")
        if dp < 1 or mp < 1:
            parser.error("--mesh extents must be >= 1")
        return (dp, mp)
    return None


def _ensure_devices(n: int):
    """Make n devices visible.  On CPU the device count locks on first
    backend init (the paper's LD_PRELOAD-ordering lesson transposed), so
    the flag must be set before anything touches jax devices — main()
    calls this before the first device-touching import executes a device
    query."""
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    import jax

    if len(jax.devices()) < n:
        raise SystemExit(
            f"mesh needs {n} devices but only {len(jax.devices())} are "
            f"visible (backend initialized before the flag took effect?)")


def _request_extras(cfg, rng, n):
    extras = {}
    if cfg.family == "vlm":
        extras["patch_embeds"] = rng.standard_normal(
            (n, cfg.num_patches, cfg.vision_dim)).astype(np.float32)
    if cfg.family == "encdec":
        extras["frames"] = rng.standard_normal(
            (n, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    return extras


def _serve_config(args, parser):
    """``reduced()`` by default; with ``--layers N`` the registry config at
    published widths cut to N layers, with the cut printed."""
    from repro.configs import get_config, reduced

    base = get_config(args.arch)
    if not args.layers:
        return reduced(base)
    n = args.layers
    if not 1 <= n <= base.num_layers:
        parser.error(f"--layers {n}: {args.arch} has {base.num_layers}")
    if base.block_pattern and n % len(base.block_pattern):
        parser.error(f"--layers {n}: {args.arch} stacks blocks of "
                     f"{len(base.block_pattern)} layers")
    cut = (f"depth cut by {base.num_layers - n}" if n < base.num_layers
           else "full depth")
    print(f"[serve] {args.arch} at published widths (d_model "
          f"{base.d_model}, {base.num_heads}/{base.num_kv_heads} heads x "
          f"{base.head_dim}, d_ff {base.d_ff}, vocab {base.vocab_size}, "
          f"{base.dtype}): {n} of {base.num_layers} layers ({cut})")
    return base.replace(num_layers=n)


def _device_label() -> str:
    import jax

    d = jax.devices()
    return f"{d[0].platform} {d[0].device_kind} x{len(d)}"


def _main_replicas(args) -> int:
    """Serve through the multi-replica router (docs/router.md).

    The router process itself never touches jax — the engines live in the
    worker subprocesses, so N replicas really do compute concurrently.
    Requests are generated in shared-prefix PAIRS (pair g shares a
    block-aligned prefix, unique tails) so ``--route prefix`` has real
    affinity structure to exploit; the pair index doubles as a sticky
    session key."""
    import time

    from repro.configs import all_arch_names, get_config, reduced
    from repro.core.analysis import serve_latency_summary
    from repro.core.paraver import parse_prv
    from repro.serve.router import Router

    if args.arch not in all_arch_names():
        raise SystemExit(f"unknown --arch {args.arch!r}")
    cfg = reduced(get_config(args.arch))
    if cfg.family not in ("dense", "moe"):
        raise SystemExit("--replicas serves token-only prompts (dense/moe "
                         f"archs); {args.arch} is family {cfg.family!r}")
    cfg_over = {}
    if args.kernel_mode:
        cfg_over["kernel_mode"] = args.kernel_mode
    if args.kv_dtype:
        cfg_over["kv_dtype"] = args.kv_dtype
    engine = dict(
        num_slots=min(args.slots, args.requests), max_len=args.prompt_len + args.gen,
        block_size=args.block_size, num_blocks=args.num_blocks or None,
        prefix_cache=not args.no_prefix_cache,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed, max_step_tokens=args.max_step_tokens or None,
        chunk_size=args.chunk_size or None, chunk_rows=args.chunk_rows,
        mixed_burst=args.mixed_burst, spec=args.spec, spec_k=args.spec_k,
        spec_adaptive=args.spec_adaptive)

    rng = np.random.default_rng(0)
    shared = args.prompt_len // 2 // args.block_size * args.block_size
    prompts = []
    for i in range(args.requests):
        g = i // 2
        head_rng = np.random.default_rng(1000 + g)
        plen = max(1, args.prompt_len - (i % 4))
        head = head_rng.integers(0, cfg.vocab_size, (min(shared, plen),))
        tail = rng.integers(0, cfg.vocab_size, (plen - len(head),))
        prompts.append(np.concatenate([head, tail]).astype(np.int32))

    out = pathlib.Path(args.out)
    t0 = time.perf_counter()
    with Router(args.arch, num_replicas=args.replicas, route=args.route,
                disaggregate=args.disaggregate, cfg=cfg_over, engine=engine,
                trace=args.trace, app_name=f"serve-{args.arch}") as router:
        reqs = [router.submit(p, args.gen, session=i // 2, n_samples=args.n)
                for i, p in enumerate(prompts)]
        results = router.run()
        seconds = time.perf_counter() - t0
        tokens = sum(len(results[r.rid]) for r in reqs)
        mode = "disaggregated" if args.disaggregate else args.route
        print(f"[serve] {args.arch} replicas={args.replicas} route={mode}: "
              f"{tokens} tokens in {seconds:.2f}s = "
              f"{tokens / seconds:.1f} tok/s aggregate (host wall clock)")
        st = router.stats
        print(f"[serve] router: {st['route_decisions']} decisions, "
              f"{st['bounces']} bounces, "
              f"{st['prefix_hit_tokens']}/{st['prompt_tokens']} prompt "
              f"tokens prefix-hit (expected {st['expected_hit_tokens']})")
        if args.disaggregate:
            print(f"[serve] kv handoff: {st['kv_xfers']} transfers, "
                  f"{st['kv_xfer_bytes']} wire bytes "
                  f"({router.wire_dtype}), {st['kv_xfer_us']}us wall")
        paths = router.close(out / "serve" if args.trace else None)
        for h in router.handles:
            pool = h.stats.get("pool", {})
            eng = h.stats.get("stats", {})
            print(f"[serve] replica {h.idx} ({h.role}): "
                  f"{eng.get('tokens_decoded', 0)} tokens decoded, "
                  f"pool free/cached/active "
                  f"{pool.get('free', '?')}/{pool.get('cached', '?')}/"
                  f"{pool.get('active', '?')}, "
                  f"{pool.get('evictions', 0)} evictions")
    if args.trace and paths is not None:
        trace = parse_prv(paths["prv"])
        print(f"[serve] trace: {paths['prv']}  ({trace.summary()}; "
              f"{trace.num_tasks} tasks: router + {args.replicas} replicas)")
        lat = serve_latency_summary(trace)
        if lat["per_task"]:
            print("[serve] per-replica latency (from the merged .prv):")
            print(f"  {'task':>4} {'role':>8} {'n':>4} "
                  f"{'TTFT p50':>10} {'TTFT p95':>10} "
                  f"{'TPOT p50':>10} {'TPOT p95':>10}")
            for t, d in sorted(lat["per_task"].items()):
                role = (router.handles[t - 1].role if 0 < t <= args.replicas
                        else "router")
                print(f"  {t:>4} {role:>8} {d['ttft_us']['count']:>4} "
                      f"{d['ttft_us']['p50']:>9.0f}u {d['ttft_us']['p95']:>9.0f}u "
                      f"{d['tpot_us']['p50']:>9.0f}u {d['tpot_us']['p95']:>9.0f}u")
        if lat["ttft_us"]["count"]:
            t, o = lat["ttft_us"], lat["tpot_us"]
            print(f"[serve] aggregate over {t['count']} requests: "
                  f"TTFT p50 {t['p50']:.0f}us / p95 {t['p95']:.0f}us; "
                  f"TPOT p50 {o['p50']:.0f}us / p95 {o['p95']:.0f}us")
    return 0


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> dict:
    """The CLI's body.  Returns a report of the single-engine run: the
    config, the kernel plan (variant -> backend), the engine stats and each
    request's generated tokens in submission order (empty for --replicas,
    which reports on stdout only)."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-8b")
    p.add_argument("--layers", type=int, default=0,
                   help="serve the registry config at published widths, "
                        "cut to its first N layers (0 = the reduced() CPU "
                        "test preset)")
    p.add_argument("--mode", default="unified",
                   choices=["unified", "continuous", "static"])
    p.add_argument("--max-step-tokens", type=int, default=0,
                   help="unified-step token budget per scheduler iteration "
                        "(0 = slots + chunk-size)")
    p.add_argument("--chunk-size", type=int, default=0,
                   help="prefill chunk length for the unified step "
                        "(0 = max(2*block-size, 16))")
    p.add_argument("--chunk-rows", type=int, default=2,
                   help="concurrent prefill streams per unified step")
    p.add_argument("--mixed-burst", type=int, default=4,
                   help="decode steps scanned per chunk-carrying dispatch "
                        "(1 = strict per-iteration budget)")
    p.add_argument("--mesh", default="",
                   help="dp,mp — serve tensor-parallel over a data x model "
                        "device mesh (CPU devices are forced as needed)")
    p.add_argument("--mp", type=int, default=0,
                   help="shorthand for --mesh 1,N (model parallelism only)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--n", type=int, default=1,
                   help="samples per prompt: each request prefills ONCE and "
                        "CoW-forks into n decode streams whose block tables "
                        "alias the prompt blocks (docs/paged_cache.md); "
                        "per-fork PRNG keys fold --seed + fork index, so "
                        "sampled fans are reproducible (unified mode)")
    p.add_argument("--best-of", type=int, default=0,
                   help="candidate count: sugar for --n N.  The serve path "
                        "tracks no EOS/logprob state, so ranking the n "
                        "candidates is the caller's job — the flag "
                        "demonstrates the one-prefill fan-out cost model "
                        "(use --beam for model-scored search)")
    p.add_argument("--beam", type=int, default=0,
                   help="beam search width: fork-based beams on the CoW "
                        "pool, per-step score/prune, summed log-prob "
                        "ranking (unified mode, single engine, serves "
                        "prompts one at a time)")
    p.add_argument("--session", action="store_true",
                   help="serve each prompt as a 2-turn conversation under a "
                        "persistent session id: turn 2 re-submits the full "
                        "turn-1 context + fresh tokens and must prefix-hit "
                        "the pinned blocks (unified mode, single engine)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k sampling filter (0 = off; ignored when greedy)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling filter (1.0 = off)")
    p.add_argument("--seed", type=int, default=0,
                   help="engine RNG seed: temperature>0 runs (and spec "
                        "rejection sampling) are reproducible per seed")
    p.add_argument("--spec", default="",
                   help="speculative decoding proposer for the unified "
                        "engine: 'ngram' (prompt-lookup, zero weights) or "
                        "'draft:<arch>' (cut-down model sharing the vocab)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="max draft tokens verified per slot per dispatch")
    p.add_argument("--spec-adaptive", action="store_true",
                   help="walk K down/up with the measured acceptance rate")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV-cache block size (tokens) for the paged pool")
    p.add_argument("--num-blocks", type=int, default=0,
                   help="KV pool size in blocks (0 = contiguous-equivalent "
                        "budget: slots * ceil(max_len/block_size) + 1)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable hash-based prompt prefix reuse")
    p.add_argument("--kernel-mode", default="",
                   choices=["auto", "pallas", "xla"],
                   help="attention-kernel dispatch (docs/kernels.md): auto "
                        "= Pallas where shape/platform allow, pallas = "
                        "force the kernels (interpret mode off-TPU), xla "
                        "= always the gather/SDPA path")
    p.add_argument("--kv-dtype", default="",
                   choices=["fp16", "int8", "fp8"],
                   help="KV block-pool storage dtype (docs/paged_cache.md): "
                        "fp16 = native model dtype, int8/fp8 = quantized "
                        "blocks with per-(position, kv-head) scales, dequant "
                        "fused into the paged/span attention paths")
    p.add_argument("--overlap", default="",
                   choices=["on", "off", "auto"],
                   help="communication/compute overlap for sharded serving "
                        "(docs/distributed_serving.md): micro-batched span "
                        "pipeline + two-deep dispatch queue.  auto (default "
                        "via cfg.comm_overlap) = on when --mp/--mesh shards "
                        "the model axis, off single-device")
    p.add_argument("--replicas", type=int, default=0,
                   help="serve through N engine-replica subprocesses behind "
                        "the prefix-affinity router (docs/router.md); 0 = "
                        "single in-process engine")
    p.add_argument("--route", default="prefix",
                   choices=["prefix", "rr", "least-loaded"],
                   help="replica routing policy: prefix = expected "
                        "resident-prefix-hit tokens (least-loaded "
                        "fallback), rr = round-robin")
    p.add_argument("--disaggregate", action="store_true",
                   help="prefill/decode disaggregation: the first replica "
                        "serves only prompts and streams finished KV "
                        "blocks (quantized wire format) to the decode "
                        "replicas; needs --replicas >= 2")
    p.add_argument("--trace", action="store_true")
    p.add_argument("--flush-every", type=int, default=0,
                   help="stream the trace to disk every N decode iterations")
    p.add_argument("--out", default="runs/serve")
    args = p.parse_args(argv)
    if args.flush_every and not args.trace:
        p.error("--flush-every streams the trace and requires --trace")
    if args.spec and args.mode != "unified":
        p.error("--spec is a unified-engine lane (--mode unified)")
    if args.best_of:
        if args.n > 1 and args.n != args.best_of:
            p.error("--best-of implies --n; pick one")
        args.n = args.best_of
    if (args.n > 1 or args.beam or args.session) and args.mode != "unified":
        p.error("--n/--best-of/--beam/--session ride the unified engine's "
                "CoW fork path (--mode unified)")
    if args.beam and (args.n > 1 or args.session):
        p.error("--beam is a standalone search (no --n/--session)")
    if args.session and args.n > 1:
        p.error("--session persists ONE stream; fan-out is per-request "
                "(--n) — they are mutually exclusive")
    if args.replicas and (args.beam or args.session):
        p.error("--beam/--session need the single in-process engine "
                "(--replicas routes sticky sessions on its own)")
    if args.replicas:
        if args.layers:
            p.error("--replicas serves the reduced() preset (replica "
                    "workers build their own config)")
        if args.mode != "unified":
            p.error("--replicas serves through UnifiedServeEngine workers "
                    "(--mode unified)")
        if args.mesh or args.mp:
            p.error("--replicas and --mesh/--mp are separate scale-out axes "
                    "(replicate OR shard, not both yet)")
        if args.disaggregate and args.replicas < 2:
            p.error("--disaggregate needs --replicas >= 2")
        if args.flush_every:
            p.error("--flush-every is per-engine; replica workers stream "
                    "their own per-task segments at shutdown")
        _main_replicas(args)
        return {}
    if args.disaggregate:
        p.error("--disaggregate needs --replicas >= 2")
    mesh_shape = _parse_mesh(args, p)
    if mesh_shape is not None:
        _ensure_devices(mesh_shape[0] * mesh_shape[1])

    # device-touching imports happen AFTER the device count is forced
    import jax

    from repro import core as xtrace
    from repro.compat import make_mesh
    from repro.configs import all_arch_names
    from repro.core.analysis import serve_latency_summary
    from repro.launch.cache import use_compile_cache
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousServeEngine, ServeEngine
    from repro.serve.step import UnifiedServeEngine
    from repro.sharding.partition import make_serve_rules

    if args.arch not in all_arch_names():
        p.error(f"unknown --arch {args.arch!r} (choose from "
                f"{', '.join(all_arch_names())})")

    use_compile_cache()
    cfg = _serve_config(args, p)
    if args.kernel_mode:
        cfg = cfg.replace(kernel_mode=args.kernel_mode)
    if args.kv_dtype:
        cfg = cfg.replace(kv_dtype=args.kv_dtype)
    mesh = (make_mesh(mesh_shape, ("data", "model"))
            if mesh_shape is not None else None)
    model = build_model(cfg)
    # under a mesh every parameter is created already sharded
    shardings = (make_serve_rules(cfg, mesh).tree_shardings(model.param_axes())
                 if mesh is not None else None)
    params = model.init(jax.random.PRNGKey(0), shardings)
    out = pathlib.Path(args.out)
    outputs: list[np.ndarray] = []

    slots = min(args.slots, args.requests)
    if args.beam:
        slots = max(slots, args.beam)  # beams borrow the slot rows
    tracer = xtrace.init(f"serve-{args.arch}") if args.trace else None
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, (args.requests, args.prompt_len)).astype(np.int32)
    extras = _request_extras(cfg, np.random.default_rng(1), args.requests)
    max_len = args.prompt_len + cfg.num_patches + args.gen
    if args.session:  # turn 2 = turn-1 context + 8 follow-up + gen more
        max_len += args.gen + 8

    if args.mode == "static":
        engine = ServeEngine(cfg, params, max_len=max_len, tracer=tracer,
                             mesh=mesh)
        stats = engine.throughput_stats(prompts, num_tokens=args.gen,
                                        extras=extras,
                                        temperature=args.temperature,
                                        top_k=args.top_k, top_p=args.top_p,
                                        seed=args.seed)
    else:
        if args.flush_every:
            out.mkdir(parents=True, exist_ok=True)
        cls = (UnifiedServeEngine if args.mode == "unified"
               else ContinuousServeEngine)
        unified_kw = {}
        if args.mode == "unified":
            unified_kw = dict(
                max_step_tokens=args.max_step_tokens or None,
                chunk_size=args.chunk_size or None,
                chunk_rows=args.chunk_rows, mixed_burst=args.mixed_burst)
            if args.spec:
                from repro.serve.spec import make_proposer

                unified_kw.update(
                    spec=make_proposer(
                        args.spec, cfg,
                        num_slots=slots,
                        max_len=max_len, temperature=args.temperature,
                        top_k=args.top_k, top_p=args.top_p, seed=args.seed),
                    spec_k=args.spec_k, spec_adaptive=args.spec_adaptive)
        engine = cls(
            cfg, params, num_slots=slots, max_len=max_len,
            block_size=args.block_size,
            num_blocks=args.num_blocks or None,
            prefix_cache=not args.no_prefix_cache,
            tracer=tracer, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=args.seed,
            flush_every=args.flush_every,
            flush_base=out / "serve" if args.flush_every else None,
            mesh=mesh, overlap=args.overlap or None, **unified_kw,
        )
        print(f"[serve] {engine.overlap.describe()}")
        if mesh is not None:
            # fail loudly before compile: every param pspec + the KV-pool
            # placement, diffable against what the operator expected
            print("[serve] sharding summary:")
            for line in engine.sharding_summary():
                print(f"  {line}")
        if args.beam:
            # standalone model-scored search: one prompt at a time on the
            # idle engine (beams borrow the slot rows)
            for i in range(args.requests):
                plen = max(1, args.prompt_len - (i % 4))
                beams = engine.beam_search(prompts[i, :plen], args.gen,
                                           width=args.beam)
                print(f"[serve] beam prompt {i}: width {args.beam}, best "
                      f"sum-log-prob {beams[0][1]:.3f} "
                      f"(worst kept {beams[-1][1]:.3f})")
        elif args.session:
            # 2-turn conversations: turn 2 extends turn 1's full context
            # and must serve it from the session's pinned blocks
            t1 = []
            for i in range(args.requests):
                plen = max(1, args.prompt_len - (i % 4))
                t1.append(engine.submit(prompts[i, :plen], args.gen,
                                        session=f"s{i}"))
            out1 = engine.run()
            t2 = []
            for i, r in enumerate(t1):
                follow = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
                ctx = np.concatenate([r.prompt, out1[r.rid], follow])
                t2.append(engine.submit(ctx, args.gen, session=f"s{i}"))
            engine.run()
            hit = sum(r.prefix_hit_tokens for r in t2)
            need = sum(r.prompt_len for r in t2)
            print(f"[serve] sessions: {len(t2)} turn-2 requests, "
                  f"{hit}/{need} prompt tokens served from pinned context")
            for i in range(args.requests):
                engine.close_session(f"s{i}")
        else:
            # staggered prompt lengths exercise variable-length admission
            reqs = []
            for i in range(args.requests):
                plen = max(1, args.prompt_len - (i % 4))
                ex = {k: v[i] for k, v in extras.items()}
                reqs.append(engine.submit(prompts[i, :plen], args.gen,
                                          extras=ex, n_samples=args.n))
            done = engine.run()
            outputs = [done[r.rid] for r in reqs]
        stats = engine.throughput_stats()

    mesh_note = (f" mesh={mesh_shape[0]}dx{mesh_shape[1]}m"
                 if mesh_shape is not None else "")
    print(f"[serve] {args.arch} mode={args.mode}{mesh_note}: "
          f"{stats['tokens']} tokens in "
          f"{stats['seconds']:.2f}s = {stats['tok_per_s']:.1f} tok/s "
          f"(host syncs: {stats.get('host_syncs', '?')}; {_device_label()})")
    if args.mode != "static" and engine.pool is not None:
        print(f"[serve] paged pool: {engine.num_blocks - 1} blocks x "
              f"{engine.block_size} tokens ({engine.pool.kv_dtype} storage, "
              f"{engine.kv_bytes_per_token} B/token); "
              f"peak {stats['peak_blocks']} in use, "
              f"{stats['prefix_hit_tokens']} prefix-hit tokens, "
              f"{stats['preemptions']} preemptions, "
              f"{stats.get('evictions', 0)} cache evictions")
        kd = engine.stats.get("kernel_dispatch", {})
        counts = (" ".join(f"{k}={v}" for k, v in sorted(kd.items()))
                  or "none recorded")
        print(f"[serve] attention kernels (mode={cfg.kernel_mode}): {counts}")
        if stats.get("forks", 0):
            print(f"[serve] CoW forking: {stats['forks']} forks, "
                  f"{stats['cow_copies']} block copies, peak "
                  f"{stats.get('peak_shared', 0)} blocks shared "
                  f"(n={args.beam or args.n} per prompt)")
    if args.mode == "unified":
        note = ("on" if engine.chunkable
                else "off — state-carrying family, whole-prompt admission")
        print(f"[serve] unified step: budget {engine.max_step_tokens} "
              f"tokens/iteration, chunk {engine.chunk_size} "
              f"(chunked prefill {note})")
        if args.spec:
            drafted = max(engine.stats["spec_drafted"], 1)
            print(f"[serve] speculative ({args.spec}): "
                  f"{engine.stats['spec_dispatches']} verify dispatches, "
                  f"{engine.stats['spec_accepted']}/"
                  f"{engine.stats['spec_drafted']} drafts accepted "
                  f"({engine.stats['spec_accepted'] / drafted:.0%}), "
                  f"{engine.stats['spec_rollback_blocks']} blocks rolled "
                  f"back, K={engine._spec_k}")
    if tracer:
        segments = list(tracer.segments)
        trace = xtrace.finish()
        out.mkdir(parents=True, exist_ok=True)
        paths = xtrace.write_prv(trace, out / "serve", segments=segments)
        seg_note = f", merged {len(segments)} flushed segments" if segments else ""
        print(f"[serve] trace: {paths['prv']}  ({trace.summary()}{seg_note})")
        # flushed events live in the segment files, not the in-memory trace:
        # summarize the MERGED .prv so every retired request counts
        lat = serve_latency_summary(xtrace.parse_prv(paths["prv"])
                                    if segments else trace)
        if lat["ttft_us"]["count"]:
            t, o = lat["ttft_us"], lat["tpot_us"]
            comm = lat.get("comm", {})
            ov_note = (f"; comm overlap {comm['overlap_fraction']:.0%} of "
                       f"{comm['overlap_us'] + comm['blocked_us']:.0f}us "
                       f"collective time"
                       if comm.get("overlap_us", 0) + comm.get("blocked_us", 0)
                       else "")
            print(f"[serve] latency over {t['count']} requests: "
                  f"TTFT p50 {t['p50']:.0f}us / p95 {t['p95']:.0f}us / "
                  f"max {t['max']:.0f}us; TPOT p50 {o['p50']:.0f}us / "
                  f"p95 {o['p95']:.0f}us{ov_note}")
        if lat["spec"]["dispatches"]:
            sp = lat["spec"]
            print(f"[serve] spec (from trace): {sp['accepted']}/"
                  f"{sp['drafted']} drafts accepted "
                  f"({sp['acceptance']:.0%}) over {sp['dispatches']} "
                  f"verify dispatches")
        if lat["forks"]["count"]:
            fk = lat["forks"]
            print(f"[serve] forks (from trace): {fk['count']} children off "
                  f"{fk['parents']} parents, peak "
                  f"{fk['peak_shared_blocks']} blocks shared")
    plan = getattr(engine, "kernel_plan", {})
    return {"cfg": cfg, "stats": stats, "outputs": outputs,
            "plan": {v: d.backend for v, d in plan.items()}}


if __name__ == "__main__":
    sys.exit(main())
