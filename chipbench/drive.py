"""Drive the serve engine through its public API: warm-up, open and closed
loops, and the stop at the end of a run.

The engine's ``run()`` serves until its queue and slots drain.  The main
thread re-enters it whenever work is queued; a monitor thread submits
requests at their due times (open loop) or as streams finish (closed loop),
stamps how late each submit was, follows token counts, opens and closes the
measured window, and starts and stops the profiler.

Each request's ``arrival_ns`` is its due time, so the engine's own TTFT
stamps count from when the request was due: the generator's lateness is
charged to the system, and reported apart.  When the window's numbers are
taken, every unfinished request is given one more token to go
(``max_new_tokens``), so the engine drains in a few steps and ``run()``
returns; the tokens served until then are kept for the correctness check.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

import numpy as np

from loadgen import ReqSpec


def now_ns() -> int:
    # the clock the engine stamps requests with (serve/queue.py)
    return time.perf_counter_ns()


@dataclasses.dataclass
class Handle:
    spec: ReqSpec
    req: object  # the engine's Request
    due_ns: int
    submit_ns: int
    t_last_ns: int = -1  # when its token count last grew (polled)
    n_seen: int = 0


@dataclasses.dataclass
class Ctl:
    handles: list = dataclasses.field(default_factory=list)
    refused: list = dataclasses.field(default_factory=list)
    work: threading.Event = dataclasses.field(default_factory=threading.Event)
    stop: threading.Event = dataclasses.field(default_factory=threading.Event)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    error: Exception | None = None
    marks: dict = dataclasses.field(default_factory=dict)


def submit(engine, ctl: Ctl, spec: ReqSpec, due_ns: int) -> Handle | None:
    try:
        req = engine.submit(spec.prompt, spec.max_new, arrival_ns=due_ns)
    except ValueError as e:  # refused by the engine: counts as failed
        ctl.refused.append((spec, repr(e)))
        return None
    h = Handle(spec, req, due_ns, now_ns())
    ctl.handles.append(h)
    ctl.work.set()
    return h


def tokens_now(handles) -> int:
    return sum(len(h.req.tokens) for h in handles)


def serve(engine, ctl: Ctl, span=None):
    """Main thread: re-enter ``engine.run()`` while work is queued, until
    the monitor has stopped the run and every request has drained."""
    try:
        while not (ctl.stop.is_set()
                   and all(h.req.done for h in list(ctl.handles))):
            ctl.work.wait(0.01)
            ctl.work.clear()
            if span is None:
                engine.run()
            else:
                with span("engine.run"):
                    engine.run()
    except Exception as e:  # a fault of the engine ends the run
        ctl.error = e
        ctl.stop.set()
    finally:
        ctl.done.set()


def finish_all(handles):
    """Give every unfinished request one more token to go: the engine then
    retires each within a step or two and ``run()`` returns.  The budget
    only ever shrinks: a request whose last token is already in flight
    keeps its own, or it would wait for a token never scheduled."""
    for h in handles:
        r = h.req
        if not r.done:
            r.max_new_tokens = min(r.max_new_tokens,
                                   max(int(r.scheduled), len(r.tokens)) + 1)


def watchdog(ctl: Ctl, seconds: float):
    """End the process if the engine has not drained ``seconds`` after the
    stop: a run that cannot stop must not outlive its allowance."""
    ctl.stop.wait()
    if not ctl.done.wait(seconds):
        print(f"[chipbench] the engine did not drain within {seconds:.0f} s "
              f"of the stop", file=sys.stderr, flush=True)
        os._exit(3)


# ----------------------------------------------------------------------
# warm-up: every step shape the traffic can use, through submit/run
# ----------------------------------------------------------------------
def warm(engine, chunk: int, vocab: int, seed: int):
    """Serve requests whose shapes cover the unified step's executables:
    decode bursts of 1, 2, 4 and 8 steps alone, and of 1, 2 and 4 steps
    beside a prefill chunk (a one-chunk prompt finishing while a
    three-chunk prompt streams), plus chunk-only steps."""
    rng = np.random.default_rng([int(seed) % (2 ** 64), 7])
    tok = lambda n: rng.integers(0, vocab, (n,)).astype(np.int32)
    for s in (1, 2, 4, 8):
        engine.submit(tok(chunk), s + 1)
        engine.run()
    for s in (1, 2, 4):
        engine.submit(tok(chunk), s + 1)
        engine.submit(tok(3 * chunk), 1)
        engine.run()


# ----------------------------------------------------------------------
# monitors
# ----------------------------------------------------------------------
def _wait_fetch(handles, after_ns: int, poll_s: float = 0.001,
                limit_s: float = 60.0) -> tuple[int, int]:
    """First time at or after ``after_ns`` at which the host received
    tokens (a fetch), once the count has settled: (time, count).  Gives up
    (returning the time it gave up) when every request is done or after
    ``limit_s``: nothing more can arrive then."""
    while now_ns() < after_ns:
        time.sleep(min(poll_s, max((after_ns - now_ns()) / 1e9, 0)))
    n0 = tokens_now(handles)
    give_up = now_ns() + int(limit_s * 1e9)
    while True:
        time.sleep(poll_s)
        n = tokens_now(handles)
        if n != n0:
            t = now_ns()
            while True:  # a fetch appends tokens one by one
                time.sleep(0.002)
                m = tokens_now(handles)
                if m == n:
                    return t, n
                n = m
        if now_ns() > give_up or all(h.req.done for h in list(handles)):
            return now_ns(), n


def closed_loop(engine, ctl: Ctl, specs: list[ReqSpec], streams: int,
                seconds: float, tracer=None, trace_s: float = 0.0,
                on_edge=lambda edge: None):
    """Monitor thread of a closed loop: ``streams`` requests at once, each
    replaced when it finishes; the window opens at the first fetch after
    every stream has its first token and closes at the first fetch
    ``seconds`` later."""
    try:
        it = iter(specs)
        t = now_ns()
        live = [submit(engine, ctl, next(it), t) for _ in range(streams)]
        while not all(h is None or len(h.req.tokens) >= 1 for h in live):
            if ctl.stop.is_set():
                return
            time.sleep(0.01)
        t0, n0 = _wait_fetch(ctl.handles, now_ns())
        on_edge("t0")
        ctl.marks.update(t0=t0, n0=n0, tok0={id(h): len(h.req.tokens)
                                             for h in ctl.handles})
        end = t0 + int(seconds * 1e9)
        trace_at = end - int(trace_s * 1e9) if tracer else None
        fetches, seen = ctl.marks.setdefault("fetches", []), n0
        while now_ns() < end and not ctl.stop.is_set():
            n = tokens_now(ctl.handles)
            if n != seen:  # when the host received tokens, for the log
                t = now_ns()
                if not fetches or t - fetches[-1] > 20_000_000:
                    fetches.append(t)
                seen = n
            for i, h in enumerate(live):
                if h is not None and h.req.done:
                    nxt = next(it, None)
                    live[i] = (submit(engine, ctl, nxt, h.req.t_done_ns)
                               if nxt is not None else None)
            if trace_at is not None and now_ns() >= trace_at:
                tracer.start()
                trace_at = None
            time.sleep(0.002)
        t1, n1 = _wait_fetch(ctl.handles, end)
        # each stream's count at the close, before the profiler's stop,
        # which takes seconds while the engine goes on serving
        tok1 = {id(h): len(h.req.tokens) for h in ctl.handles}
        on_edge("t1")
        if tracer and tracer.active:
            tracer.stop()
        ctl.marks.update(t1=t1, n1=n1, tok1=tok1, cutoff=t1)
    except Exception as e:
        ctl.error = e
    finally:
        finish_all(ctl.handles)
        ctl.stop.set()
        ctl.work.set()


def open_loop(engine, ctl: Ctl, specs: list[ReqSpec], lead_s: float,
              seconds: float, tracer=None, trace_s: float = 0.0,
              tail_s: float = 60.0, start_ns: int | None = None,
              on_edge=lambda edge: None):
    """Monitor thread of an open loop: each request is submitted at its due
    time.  The window holds the requests due in [t0, t0 + seconds); after
    it closes, arrivals go on until every one of them has its first token
    (at most ``tail_s``), so their waits are measured under the same load."""
    try:
        base = start_ns or now_ns()
        t0 = base + int(lead_s * 1e9)
        t1 = t0 + int(seconds * 1e9)
        ctl.marks.update(t0=t0, t1=t1)
        mt = threading.Thread(target=meter, args=(ctl, t0, t1), daemon=True)
        mt.start()
        trace_at = t1 - int(trace_s * 1e9) if tracer else None
        edges = ["t0", "t1"]
        pending = sorted(specs, key=lambda s: s.due_s)
        k = 0
        watch: list[Handle] = []
        while not ctl.stop.is_set():
            t = now_ns()
            while k < len(pending) and base + int(pending[k].due_s * 1e9) <= t:
                due = base + int(pending[k].due_s * 1e9)
                h = submit(engine, ctl, pending[k], due)
                if h is not None and t0 <= due < t1:
                    watch.append(h)
                k += 1
            for h in watch:
                n = len(h.req.tokens)
                if n != h.n_seen:
                    h.n_seen, h.t_last_ns = n, t
            while edges and t >= ctl.marks[edges[0]]:
                on_edge(edges.pop(0))
            if trace_at is not None and t >= trace_at:
                tracer.start()
                trace_at = None
            if tracer and tracer.active and t >= t1:
                tracer.stop()
            if t >= t1 and (all(len(h.req.tokens) >= 1 for h in watch)
                            or t >= t1 + int(tail_s * 1e9)
                            or k >= len(pending)):
                break
            nxt = (base + int(pending[k].due_s * 1e9) if k < len(pending)
                   else t + 5_000_000)
            time.sleep(max(min(nxt - now_ns(), 5_000_000), 0) / 1e9)
        mt.join()
        cut = now_ns()
        for h in watch:
            n = len(h.req.tokens)
            if n != h.n_seen:
                h.n_seen, h.t_last_ns = n, cut
        ctl.marks.update(cutoff=cut, watch=[id(h) for h in watch])
        if tracer and tracer.active:
            tracer.stop()
    except Exception as e:
        ctl.error = e
    finally:
        finish_all(ctl.handles)
        ctl.stop.set()
        ctl.work.set()


def meter(ctl: Ctl, t0: int, t1: int):
    """Token counts at the first fetch at or after t0 and after t1, so that
    an open loop's rate, too, is taken over whole steps."""
    try:
        a, na = _wait_fetch(ctl.handles, t0)
        ctl.marks.update(f0=a, n0=na)
        b, nb = _wait_fetch(ctl.handles, t1)
        ctl.marks.update(f1=b, n1=nb)
    except Exception as e:
        ctl.error = e


def run_threads(engine, ctl: Ctl, monitor, span=None, drain_s: float = 150.0):
    """Run ``monitor`` in a thread and serve in this one until both end."""
    th = threading.Thread(target=monitor, daemon=True)
    threading.Thread(target=watchdog, args=(ctl, drain_s), daemon=True).start()
    th.start()
    serve(engine, ctl, span)
    th.join()
