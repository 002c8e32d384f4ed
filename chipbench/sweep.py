"""Find the knee of an open-loop cell: the highest offered rate at which the
backlog does not grow through the window.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds 40 \\
        --rates 0.2,0.3,0.4,0.5

One process builds the cell's engine once (weights from the seed, warm-up,
and for sessions the histories), then offers each rate in turn: ``lead``
seconds of load, then ``--seconds`` measured.  Every half second it counts
the requests waiting for admission; the backlog grows when the least-squares
slope of that count over the window exceeds ``--slope`` requests per second,
or when requests due in the window are still waiting at its end.  Each rate
prints one JSON line; the last line names the knee.  The engine is drained
between rates.  Needs a TPU, as the benchmark does.
"""
from __future__ import annotations

import json
import sys
import threading
import time

import harness


def main(argv=None, *, require_tpu: bool = True, files: dict | None = None):
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--slope", type=float, default=0.05)
    a = p.parse_args(argv)

    import cellspec

    files = files or {}
    spec = files.get("bench") or cellspec.bench()
    cell = cellspec.cell(a.workload, spec)
    c = files.get("config") or cellspec.config(cell["config"])
    mx = files.get("mix") or cellspec.mix(cell["traffic"])
    if mx["kind"] == "closed":
        raise SystemExit("a closed loop has no offered rate to sweep")
    jax = harness.setup_jax() if require_tpu else __import__("jax")
    harness.devices(jax, cell["chips"], require_tpu)
    sys.setswitchinterval(0.001)

    import benchstats
    import drive
    import loadgen

    _, _, engine = harness.build_engine(c, mx, a.seed)
    drive.warm(engine, mx["engine"]["chunk_size"], c["vocab_size"], a.seed)
    span = lambda name: __import__("contextlib").nullcontext()
    segments = (mx["lead_s"], a.seconds)
    if mx["kind"] == "sessions":
        s = loadgen.sessions(mx, a.seed, c["vocab_size"], segments)
        harness.prefill_histories(engine, s.histories, span)
    out = []
    for rate in [float(r) for r in a.rates.split(",")]:
        if mx["kind"] == "sessions":
            specs = loadgen.sessions(mx, a.seed, c["vocab_size"], segments,
                                     rate_rps=rate).turns
        else:
            specs = loadgen.open_arrivals(mx, a.seed, c["vocab_size"],
                                          segments, rate_rps=rate)
        ctl = drive.Ctl()
        samples: list[tuple[float, int]] = []
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                t = drive.now_ns()
                t0, t1 = ctl.marks.get("t0"), ctl.marks.get("t1")
                if t0 is not None and t0 <= t <= t1:
                    waiting = sum(1 for h in list(ctl.handles)
                                  if h.req.t_admit_ns < 0 and not h.req.done)
                    samples.append(((t - t0) / 1e9, waiting))
                time.sleep(0.5)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        drive.run_threads(engine, ctl, lambda: drive.open_loop(
            engine, ctl, specs, mx["lead_s"], a.seconds, tail_s=0.0))
        stop.set()
        th.join()
        rows = harness.request_rows(ctl, ctl.marks)
        win = [r for r in rows if r["in_window"]]
        cut = ctl.marks["cutoff"]
        ttft = [((r["first_ns"] if r["first_ns"] >= 0 else cut) - r["due_ns"])
                / 1e6 for r in win]
        tpot = [(r["last_ns"] - r["first_ns"]) / (r["n_tokens"] - 1) / 1e6
                for r in win if r["n_tokens"] >= 2 and r["first_ns"] >= 0
                and r["last_ns"] > r["first_ns"]]
        xs = [s[0] for s in samples]
        ys = [s[1] for s in samples]
        slope = 0.0
        if len(xs) >= 2:
            mx_, my = sum(xs) / len(xs), sum(ys) / len(ys)
            den = sum((x - mx_) ** 2 for x in xs) or 1.0
            slope = sum((x - mx_) * (y - my) for x, y in zip(xs, ys)) / den
        waiting_end = sum(1 for r in win if r["admit_ns"] < 0)
        grows = slope > a.slope or waiting_end > 0
        row = dict(rate_rps=rate, due_in_window=len(win),
                   backlog_slope_per_s=slope, waiting_at_end=waiting_end,
                   backlog_mean=(sum(ys) / len(ys)) if ys else None,
                   grows=grows,
                   ttft_p50_ms=benchstats.percentile(ttft, 50) if ttft else None,
                   ttft_p95_ms=benchstats.percentile(ttft, 95) if ttft else None,
                   tpot_p95_ms=benchstats.percentile(tpot, 95) if tpot else None,
                   error=repr(ctl.error) if ctl.error else None)
        print(json.dumps(row), flush=True)
        out.append(row)
        if ctl.error is not None:
            break
    ok = [r["rate_rps"] for r in out if not r["grows"]]
    print(json.dumps({"knee_rps": max(ok) if ok else None,
                      "rates": [r["rate_rps"] for r in out]}), flush=True)
    return out


if __name__ == "__main__":
    main()
