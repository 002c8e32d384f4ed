"""Serve-loop spans in the JAX profiler's trace and in the ``.prv``, on one
clock.

A tiny engine serves a few requests under a profiler session with a
Paraver tracer bound.  Each dispatch then shows ``serve.plan`` ->
``serve.blocks`` -> the dispatch span -> ``serve.fetch`` -> ``serve.retire``
on the serving thread, the dispatch span carries the Paraver budget
triple's values as stats, the ``.prv`` holds ``EV_USER_FUNC`` regions of
the same names, and ``tracer.anchor`` maps each region onto its profiler
span.
"""
from __future__ import annotations

import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import events as ev
from repro.core.tracer import Tracer, span
from repro.models.model import build_model
from repro.serve.spec import NGramProposer
from repro.serve.step import UnifiedServeEngine

DISPATCH = {"unified": "serve.unified_step", "spec": "serve.spec_step"}


def _host_spans(path):
    """(thread line, name, start_ns, end_ns, stats) of the program's spans."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve.") or e.name == "tracer.anchor":
                    out.append((line.name, e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[2])


@pytest.fixture(scope="module", params=["unified", "spec"])
def profiled(request, tmp_path_factory):
    cfg = reduced(get_config("granite-8b"), num_layers=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (11, 19, 6)]
    tmp = tmp_path_factory.mktemp(f"prof-{request.param}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        tracer = Tracer("spans").init()
        extra = ({"spec": NGramProposer(), "spec_k": 3}
                 if request.param == "spec" else {})
        eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                                 block_size=8, chunk_size=8, tracer=tracer,
                                 **extra)
        # what each decode dispatch's kernel will read: its rows and the
        # slots' position registers on the device as it is dispatched
        seen = []
        dispatch = eng._dispatch

        def recording(pairs, steps, chunks):
            if pairs or chunks:  # else nothing is dispatched
                seen.append(([s for s, _ in pairs], steps,
                             np.asarray(eng._idx)))
            return dispatch(pairs, steps, chunks)
        eng._dispatch = recording
        for p in prompts:
            eng.submit(p, 6)
        eng.run()
        trace = tracer.finish()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    eng.dispatched = seen
    return request.param, _host_spans(path), tracer, trace, eng


def test_each_dispatch_runs_plan_blocks_dispatch_fetch_retire(profiled):
    kind, spans, _, trace, eng = profiled
    serve = [s for s in spans if s[1] != "tracer.anchor"]
    assert len({s[0] for s in serve}) == 1  # one serving thread
    names = [s[1] for s in serve]
    d = DISPATCH[kind]
    at = [i for i, n in enumerate(names) if n == d]
    fetches = [i for i, n in enumerate(names) if n == "serve.fetch"]
    assert at and len(at) == len(fetches) == names.count("serve.retire")
    for k, i in enumerate(at):
        assert names[i - 2:i] == ["serve.plan", "serve.blocks"]
        j = fetches[k]  # the fetch of this dispatch, at once or pipelined
        assert serve[j][2] >= serve[i][3]
        assert names[j + 1] == "serve.retire"
    # the dispatch span's stats are the Paraver triple's values
    evs = trace.events
    decode = evs[evs["type"] == ev.EV_DECODE_TOKENS]["value"]
    chunk = evs[evs["type"] == ev.EV_CHUNK_TOKENS]["value"]
    stats = [serve[i][4] for i in at]
    assert [s["chunk_tokens"] for s in stats] == list(chunk)
    assert sum(s["chunk_tokens"] for s in stats) == eng.stats["prefill_tokens"]
    if kind == "unified":
        assert [s["decode_rows"] for s in stats] == list(decode)
        assert sum(s["steps"] for s in stats) == eng.stats["iterations"]
    else:
        assert all(s["steps"] == 1 for s in stats)


def test_unified_step_counts_the_decode_kernels_live_pages(profiled):
    """``kv_pages``: the table entries the decode kernel reads per layer in
    the dispatch, from the rows' device positions: a row at position p
    reads entries 0 .. min(p // bs, W - 1) at each of the ``steps``."""
    kind, spans, _, _, eng = profiled
    if kind == "spec":  # verify spans run the span kernel: no such count
        assert all("kv_pages" not in s[4] for s in spans
                   if s[1] == "serve.spec_step")
        return
    stats = [s[4] for s in spans if s[1] == "serve.unified_step"]
    assert len(stats) == len(eng.dispatched)
    bs, w = eng.block_size, eng._tables.shape[1]
    want = [sum(min((int(idx[s]) + t) // bs, w - 1) + 1
                for s in rows for t in range(steps))
            for rows, steps, idx in eng.dispatched]
    assert [s["kv_pages"] for s in stats] == want
    assert any(want)


def test_prv_regions_align_with_profiler_spans(profiled):
    _, spans, tracer, trace, _ = profiled
    anchor = [s for s in spans if s[1] == "tracer.anchor"]
    assert len(anchor) == 1
    _, _, a_start, a_end, a_stats = anchor[0]
    assert a_stats["mono_ns"] == tracer.t0 and a_end - a_start < 1_000_000
    evs = trace.events[trace.events["type"] == ev.EV_USER_FUNC]
    labels = trace.event_types[ev.EV_USER_FUNC].values
    regions = [(labels[int(v)], int(t)) for t, v in zip(evs["time"],
                                                         evs["value"]) if v]
    serve = [s for s in spans if s[1] != "tracer.anchor"]
    assert [r[0] for r in regions] == [s[1] for s in serve]
    # profiler time t lies at .prv time t - anchor.start (t0 unpinned)
    off = np.array([t - (s[2] - a_start) for (_, t), s in zip(regions, serve)])
    assert np.abs(off).max() < 1_000_000  # within 1 ms


def test_span_is_cheap_without_a_profiler_session():
    """With no session collecting, a span with three counts costs
    microseconds: well under a thousandth of a millisecond-long dispatch."""
    n = 20_000
    t = time.perf_counter()
    for _ in range(n):
        with span("serve.unified_step", steps=8, decode_rows=15,
                  chunk_tokens=0):
            pass
    assert (time.perf_counter() - t) / n < 50e-6
