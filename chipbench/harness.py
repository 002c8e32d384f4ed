"""One run of one benchmark cell (``run.py`` is its command line).

Steps: refuse to run without a TPU (and a cell on more than one chip:
the engine is built on one device); draw the weights from the seed on the device in one jitted
call; build the program's ``UnifiedServeEngine`` at the cell's deployment
settings (``kernel_mode=auto``, compile cache in ``<checkout>/.jax_cache``);
warm every step shape the traffic uses; drive the cell's traffic through
``submit``/``run`` and measure for ``--seconds``; stop the engine, free it,
and compare a sample of the served tokens with the float32 reference of
the configuration's architecture (``chipbench/arch/<reference>.py``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, with ``--control 1`` ``control_correct``, and last
``checks``: each compared number beside its limit).  The compared numbers are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))  # the program under test


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="also read the control (the reference in float8) on "
                        "the same sample; for setting the check's limit")
    p.add_argument("--keep-trace", default="",
                   help="copy the profiler's trace file here (a directory)")
    return p.parse_args(argv)


def mem_note(devs, what: str):
    m = devs[0].memory_stats() or {}
    log(f"memory {what}: {m.get('bytes_in_use', 0) / 1e9:.3f} GB in use, "
        f"peak {m.get('peak_bytes_in_use', 0) / 1e9:.3f} GB, limit "
        f"{m.get('bytes_limit', 0) / 1e9:.3f} GB")


def setup_jax():
    """The persistent compile cache lives at one fixed path inside the
    checkout, whatever the environment says, so only a cell's first run
    in a checkout compiles.  Must run before jax is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def devices(jax, chips: int, require_tpu: bool):
    """The devices the run uses.  The engine is built on one device, so a
    cell that asks for more chips is refused until a mesh path exists."""
    if chips != 1:
        raise SystemExit(f"the cell asks for {chips} chips; this harness "
                         f"builds the engine on one device")
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU chip; JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:1]


class Span:
    """Host spans in the profiler's trace (harness boundaries only)."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name):
        import contextlib

        import jax

        return (jax.profiler.TraceAnnotation(name) if self.on
                else contextlib.nullcontext())


SPANS = ("engine.run", "setup.sessions")


def build_engine(c: dict, mx: dict, seed: int):
    """The program at the configuration's depth, with any other fields of
    its config from the configuration file's ``program`` block, checked
    against the widths its architecture reads from the file; and
    ``UnifiedServeEngine`` given the mix's ``engine`` block as keyword
    arguments (the prefix cache on unless the block says otherwise)."""
    import jax

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve.step import UnifiedServeEngine

    import cellspec
    import seedweights as sw

    want = cellspec.arch(c.get("reference")).program(c)
    base = get_config(c["arch"])
    cfg = base.replace(num_layers=c["num_hidden_layers"], kernel_mode="auto")
    cfg = cfg.replace(**c.get("program", {}))
    have = {k: getattr(cfg, k) for k in want}
    if have != want:
        raise SystemExit(f"program config {have} differs from {want}")
    model = build_model(cfg)
    params = sw.program_params(seed, c, model.abstract_params())
    jax.block_until_ready(params)
    kw = {"prefix_cache": True, **mx["engine"], "seed": seed % (2 ** 31)}
    engine = UnifiedServeEngine(cfg, params, **kw)
    return cfg, params, engine


def traffic(c: dict, mx: dict, seed: int, seconds: float):
    import loadgen

    v = c["vocab_size"]
    if mx["kind"] == "closed":
        return loadgen.closed_streams(mx, seed, v), None
    segments = (mx["lead_s"], seconds, mx["tail_s"])
    if mx["kind"] == "open":
        return loadgen.open_arrivals(mx, seed, v, segments), None
    if mx["kind"] == "sessions":
        s = loadgen.sessions(mx, seed, v, segments)
        return s.turns, s.histories
    raise SystemExit(f"unknown traffic kind {mx['kind']!r}")


def prefill_histories(engine, histories, span):
    """Sessions: every conversation's history enters the prefix cache."""
    with span("setup.sessions"):
        for h in histories:
            engine.submit(h, 1)
        engine.run()


def request_rows(ctl, marks) -> list[dict]:
    watch = set(marks.get("watch", []))
    cut = marks["cutoff"]
    tok0, tok1 = marks.get("tok0", {}), marks.get("tok1", {})
    rows = []
    for h in ctl.handles:
        r = h.req
        first = r.t_first_ns if 0 <= r.t_first_ns <= cut else -1
        done = r.t_done_ns if 0 <= r.t_done_ns <= cut else -1
        n = h.n_seen if id(h) in watch else tok1.get(id(h), len(r.tokens))
        rows.append(dict(
            due_ns=h.due_ns, submit_ns=h.submit_ns,
            admit_ns=r.t_admit_ns if 0 <= r.t_admit_ns <= cut else -1,
            first_ns=first, done_ns=done,
            last_ns=done if done >= 0 else h.t_last_ns,
            n_tokens=n, n0=tok0.get(id(h), 0), n1=tok1.get(id(h), n),
            prompt_len=int(r.prompt_len),
            prefix_hit=int(r.prefix_hit_tokens),
            in_window=(id(h) in watch) if watch else (id(h) in tok1)))
    return rows


def decode_work(c: dict, rows, key0: str, key1: str) -> dict:
    """Decoded tokens (each request's second token on) between two token
    snapshots, with the sum of their attended lengths and each row's
    (context before its first decoded token, tokens decoded)."""
    import workcount as wc

    toks = ctx = 0
    spans = []
    for r in rows:
        a, b = max(r[key0], 1), max(r[key1], 1)
        if b > a:
            toks += b - a
            # token j (1-based) attends prompt + j - 1 positions
            spans.append((r["prompt_len"] + a - 1, b - a))
            ctx += wc.sum_contexts(*spans[-1])
    return {"tokens": toks, "ctx_sum": ctx, "spans": spans}


def pick_sample(handles, seed: int, want_tokens: int, max_total: int,
                max_seqs: int):
    """The requests to check: the one with the most served tokens and the
    longest one, then others drawn from the seed until ``want_tokens``
    served tokens, within ``max_total`` tokens and ``max_seqs`` requests."""
    import numpy as np

    cand = [h for h in handles if len(h.req.tokens) >= 1]
    if not cand:
        return []
    served = lambda h: len(h.req.tokens)
    total = lambda h: h.req.prompt_len + len(h.req.tokens)
    out = [max(cand, key=served)]
    longest = max(cand, key=total)
    if longest is not out[0] and total(out[0]) + total(longest) <= max_total:
        out.append(longest)
    rng = np.random.default_rng([int(seed) % (2 ** 64), 11])
    for i in rng.permutation(len(cand)):
        h = cand[i]
        got, size = sum(map(served, out)), sum(map(total, out))
        if got >= want_tokens or len(out) >= max_seqs:
            break
        if not any(h is o for o in out) and size + total(h) <= max_total:
            out.append(h)
    return out


def verdict(check: dict | None, engine_error) -> bool:
    """Correct: the engine ran without a fault and the widest gap of the
    served tokens lies within the configuration's limit."""
    return (check is not None and engine_error is None
            and check["limit"] is not None
            and check["value"] <= check["limit"])


def main(argv=None, *, require_tpu: bool = True, fault=None,
         files: dict | None = None, t_process: int | None = None) -> dict:
    """One run.  ``require_tpu=False`` and ``files`` (BENCHMARK.json,
    configuration, mix and peaks given as dicts) let a CPU test drive the
    whole run at a small size; ``fault(engine)`` breaks the engine first."""
    args = parse(argv)
    T_PROCESS = t_process or time.perf_counter_ns()
    import cellspec

    files = files or {}
    spec = files.get("bench") or cellspec.bench()
    cell = cellspec.cell(args.workload, spec)
    c = files.get("config") or cellspec.config(cell["config"])
    mx = files.get("mix") or cellspec.mix(cell["traffic"])
    jax = setup_jax() if require_tpu else __import__("jax")
    devs = devices(jax, cell["chips"], require_tpu)
    kind = devs[0].device_kind
    peak = files.get("peaks") or cellspec.peaks(kind)
    sys.setswitchinterval(0.001)  # the monitor thread wakes on time

    import benchstats
    import devtrace
    import drive

    clock = benchstats.CompileClock()
    span = Span(bool(args.trace))
    cfg, params, engine = build_engine(c, mx, args.seed)
    mem_note(devs, "with weights and the KV pool")
    if fault is not None:
        fault(engine)
    drive.warm(engine, mx["engine"]["chunk_size"], c["vocab_size"], args.seed)
    mem_note(devs, "after the warm-up")
    log(f"compiled so far: {clock.compiles} compiles ({clock.compile_s:.1f} s), "
        f"{clock.cache_hits} cache loads")
    specs, histories = traffic(c, mx, args.seed, args.seconds)
    if histories is not None:
        prefill_histories(engine, histories, span)
    log(f"set-up served; {len(specs)} requests in the schedule")

    ctl = drive.Ctl()
    tracer = devtrace.Profiler() if args.trace else None
    trace_s = float(mx.get("trace_s", 5.0))
    marks_clock = {}

    def note(edge):
        marks_clock[edge] = dict(clock.since())

    if mx["kind"] == "closed":
        def monitor():
            clock.mark()
            drive.closed_loop(engine, ctl, specs, mx["engine"]["num_slots"],
                              args.seconds, tracer, trace_s, on_edge=note)
    else:
        def monitor():
            clock.mark()
            drive.open_loop(engine, ctl, specs, mx["lead_s"], args.seconds,
                            tracer, trace_s, tail_s=mx["tail_s"],
                            on_edge=note)
    gc_marks = []  # full collections of the host's heap, for the log

    def on_gc(phase, info):
        if info["generation"] == 2:
            gc_marks.append(time.perf_counter_ns())

    gc.callbacks.append(on_gc)
    drive.run_threads(engine, ctl, monitor, span=span if args.trace else None)
    gc.callbacks.remove(on_gc)
    if ctl.error is not None:
        log(f"run failed: {ctl.error!r}")
    m = ctl.marks
    mem = devs[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    window_compiles = {k: marks_clock.get("t1", {}).get(k, 0)
                       - marks_clock.get("t0", {}).get(k, 0)
                       for k in ("compiles", "cache_loads")}

    rows = request_rows(ctl, m) if "cutoff" in m else []
    rec = dict(cell=args.workload, config=c, mix=mx, chips=len(devs),
               peaks=peak, rows=rows,
               setup_s=(m.get("t0", T_PROCESS) - T_PROCESS) / 1e9,
               window_s=((m["f1"] - m["f0"]) if "f1" in m else
                         (m["t1"] - m["t0"])) / 1e9 if "t1" in m else None,
               tokens_in_window=(m["n1"] - m["n0"]) if "n1" in m else None,
               refused=len(ctl.refused), trace=None,
               cutoff_ns=m.get("cutoff"))
    if mx["kind"] == "closed" and rows:
        rec["decode"] = decode_work(c, rows, "n0", "n1")
        rec["decode"]["streams"] = mx["engine"]["num_slots"]
    trace_info, breakdown = {}, None
    if tracer is not None and tracer.path:
        if args.keep_trace:
            import shutil

            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(tracer.path, args.keep_trace)
        tr = devtrace.load(tracer.path, SPANS)
        window = devtrace.window_s(tr)
        rec["trace"] = tr
        rec["trace_window_s"] = window
        if mx["kind"] == "closed" and rec["window_s"]:
            # the traced stretch ends at the window's close and serves at
            # the window's rate: each stream's last share of its window
            # tokens, so no whole dispatch is counted in or out at its ends
            share = min(window / rec["window_s"], 1.0)
            for r in rows:
                r["nt0"] = r["n1"] - share * (r["n1"] - r["n0"])
            rec["trace_decode"] = decode_work(c, rows, "nt0", "n1")
        trace_info = {"busy_s": devtrace.busy_s(tr), "window_s": window}
        breakdown = {"device_ops": devtrace.top_ops(tr),
                     "idle_gaps": devtrace.idle_gaps(tr)}
        tracer.cleanup()

    metrics = {}
    for mdef in cellspec.metrics_for(args.workload, bool(args.trace), spec):
        try:
            val = cellspec.reader(mdef["name"])(rec)
        except Exception as e:  # a reader that cannot read reports nothing
            log(f"metric {mdef['name']}: {e!r}")
            val = None
        if val is not None:
            metrics[mdef["name"]] = {"value": val, "unit": mdef["unit"]}

    window_due = [r for r in rows if r["in_window"]]
    no_first = sum(1 for r in window_due if r["first_ns"] < 0)
    attempted = len(window_due) + rec["refused"]
    failed = rec["refused"] + (1 if ctl.error is not None else 0)
    log(f"window: {len(window_due)} requests due or streaming, "
        f"{rec['tokens_in_window']} tokens received, "
        f"{no_first} without a first token at the cut-off, "
        f"{rec['refused']} refused")
    if m.get("fetches") and "t0" in m:
        gaps = [round((b - a) / 1e6, 1)
                for a, b in zip([m["t0"]] + m["fetches"], m["fetches"])]
        log(f"fetches in the window: {len(gaps)}, ms apart: {gaps}")
    if "t1" in m:
        full = [(b - a) / 1e6 for a, b in zip(gc_marks[::2], gc_marks[1::2])
                if m["t0"] <= b and a <= m["t1"]]
        log(f"full collections of the host heap in the window: {len(full)}, "
            f"ms: {[round(x, 1) for x in full]}")
    log(f"compiles inside the window: {window_compiles['compiles']} backend "
        f"compiles, {window_compiles['cache_loads']} cache loads; "
        f"in all {clock.compiles} compiles ({clock.compile_s:.1f} s), "
        f"{clock.cache_hits} cache loads")
    log(f"setup_s {rec['setup_s']:.3f}; peak {peak_bytes / 1e9:.3f} GB of "
        f"{mem.get('bytes_limit', 0) / 1e9:.3f} GB")

    # ---- correctness: free the program, then the reference --------------
    ck = mx.get("check", {})
    sample = pick_sample(ctl.handles, args.seed, ck.get("tokens", 256),
                         ck.get("max_total", 12288), ck.get("max_seqs", 6))
    seqs = [list(h.req.prompt) + list(h.req.tokens) for h in sample]
    starts = [int(h.req.prompt_len) for h in sample]
    served_n = sum(len(s) - p for s, p in zip(seqs, starts))
    engine_error = ctl.error
    del engine, params, ctl, rec
    gc.collect()
    mem_note(devs, "with the engine freed, before the reference")
    import numpy as np

    import refmodel

    checks = {}
    limit = c["check"]["max_logit_gap"]
    t_ref = time.perf_counter()
    if seqs:
        # one padded shape for every run of the cell, so the reference
        # compiles once in a checkout
        out = refmodel.served_gaps(args.seed, c,
                                   [np.asarray(s, np.int32) for s in seqs],
                                   starts, control=bool(args.control),
                                   rows=ck.get("max_seqs", 6),
                                   length=mx["engine"]["max_len"])
        checks["max_logit_gap"] = {"value": max(out["gap"]), "limit": limit}
        if args.control:
            checks["control_max_logit_gap"] = {
                "value": max(out["control_gap"]), "limit": limit}
    log(f"check: {len(seqs)} requests, {served_n} served tokens against "
        f"the reference in {time.perf_counter() - t_ref:.1f} s")
    correct = verdict(checks.get("max_logit_gap"), engine_error)
    if not seqs:
        log("check: no served token to compare")
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    device.update(trace_info)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.control:
        # the control in the program's place, judged by the same rule
        result["control_correct"] = verdict(
            checks.get("control_max_logit_gap"), engine_error)
    result["checks"] = checks
    for k, v in checks.items():
        log(f"{k} {v['value']!r} limit {v['limit']!r}")
    return result
