"""Each metric reader on known inputs: request rows with hand-computed
latencies, and a small profiler trace recorded on the chip (trimmed to a
slice and kept as JSON) whose kernel times and busy share are summed here
by hand."""
import json
import pathlib

import pytest

import cellspec
import devtrace
import workcount

HERE = pathlib.Path(__file__).resolve().parent
MS = 1_000_000


def row(due, submit, admit, first, last, n, prompt=100, hit=0, win=True):
    return dict(due_ns=due, submit_ns=submit, admit_ns=admit, first_ns=first,
                done_ns=-1, last_ns=last, n_tokens=n, n0=0, n1=n,
                prompt_len=prompt, prefix_hit=hit, in_window=win)


def rec_rows():
    rows = [row(i * 100 * MS, i * 100 * MS + i * MS, i * 100 * MS + 10 * MS,
                i * 100 * MS + (i + 1) * 20 * MS,
                i * 100 * MS + (i + 1) * 20 * MS + 9 * (i + 1) * MS, 10,
                hit=50 if i % 2 else 0)
            for i in range(20)]
    # one request still waiting at the cut-off, one outside the window
    rows.append(row(2000 * MS, 2000 * MS, -1, -1, -1, 0))
    rows.append(row(0, 0, 0, 1, 2, 5, win=False))
    return {"rows": rows, "cutoff_ns": 2500 * MS, "setup_s": 42.0}


def read(name, rec):
    return cellspec.reader(name)(rec)


def pct(xs, q):
    xs = sorted(xs)
    pos = (len(xs) - 1) * q / 100
    lo = int(pos)
    return xs[lo] + (xs[min(lo + 1, len(xs) - 1)] - xs[lo]) * (pos - lo)


def test_latency_readers():
    rec = rec_rows()
    ttft = [(i + 1) * 20.0 for i in range(20)] + [500.0]  # censored at cut-off
    assert read("ttft_p50_ms", rec) == pytest.approx(pct(ttft, 50))
    assert read("ttft_p95_ms", rec) == pytest.approx(pct(ttft, 95))
    tpot = [(i + 1) * 1.0 for i in range(20)]  # 9(i+1) ms over 9 gaps
    assert read("tpot_p95_ms", rec) == pytest.approx(pct(tpot, 95))
    late = [float(i) for i in range(20)] + [0.0]
    assert read("gen_late_p95_ms", rec) == pytest.approx(pct(late, 95))
    wait = [10.0] * 20 + [500.0]
    assert read("queue_wait_p95_ms", rec) == pytest.approx(pct(wait, 95))
    assert read("prefix_hit_share", rec) == pytest.approx(100 * 500 / 2000)
    assert read("setup_s", rec) == 42.0


def test_decode_readers():
    c = cellspec.config("yi-9b-l24")
    pk = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    rec = {"config": c, "peaks": pk, "chips": 1, "window_s": 10.0,
           "tokens_in_window": 1234,
           "decode": {"tokens": 1000, "ctx_sum": 2_000_000, "streams": 25}}
    assert read("decode_tok_s", rec) == pytest.approx(123.4)
    flops = 2 * workcount.matmul_params(c) * 1000 + 4 * 32 * 128 * 24 * 2_000_000
    assert read("decode_mfu", rec) == pytest.approx(100 * flops / 10 / 197e12)
    b = workcount.weight_bytes(c) * 40 + 2048 * 24 * 2_000_000
    assert read("decode_hbm_share", rec) == pytest.approx(100 * b / 10 / 819e9)
    assert read("paged_decode_roofline", rec) is None  # no trace: nothing


SYNTH = devtrace.Trace(
    ops=[(0, "while.3", 0, 500),  # a loop holding the next three ops
         (0, "fusion.1", 0, 100),
         (0, "paged_attention.9", 150, 450),
         (0, "fusion.2", 450, 500),
         (0, "paged_span_attention.2", 900, 1000),
         (0, "fusion.7", 1900, 2100)],  # runs past the window's end
    spans=[("engine.run", 480, 950), ("trace.window", 0, 2000)], devices=1)


def test_trace_reductions_by_hand():
    # busy: [0, 500] and [900, 1000] and [1900, 2000] inside the window
    assert devtrace.busy_s(SYNTH) == pytest.approx(700 / 1e9)
    assert devtrace.window_s(SYNTH) == pytest.approx(2000 / 1e9)
    assert devtrace.op_time_s(SYNTH, ("paged_attention",)) == pytest.approx(300e-9)
    assert devtrace.op_time_s(
        SYNTH, ("paged_attention", "paged_span_attention")) == pytest.approx(400e-9)
    gaps = devtrace.idle_gaps(SYNTH)
    assert gaps[0] == ["outside harness spans", pytest.approx(900e-9)]
    assert gaps[1] == ["engine.run", pytest.approx(400e-9)]
    # self time: the loop keeps 500 - 100 - 300 - 50 = 50 of its own
    assert devtrace.self_times(SYNTH) == {"while": 50, "fusion": 350,
                                          "paged_attention": 300,
                                          "paged_span_attention": 100}
    assert devtrace.top_ops(SYNTH)[0] == ["fusion", pytest.approx(350e-9)]
    rec = {"trace": SYNTH, "trace_window_s": devtrace.window_s(SYNTH)}
    assert read("idle_share.chat", rec) == pytest.approx(100 * (1 - 700 / 2000))
    assert read("attn_kernel_share.chat", rec) == pytest.approx(100 * 400 / 700)


FIXTURE = HERE / "fixtures" / "decode_trace_slice.json"


def test_recorded_trace_slice():
    d = json.loads(FIXTURE.read_text())
    tr = devtrace.from_json(d["trace"])
    want = d["expect"]
    # recomputed here from the raw events, not through devtrace
    evs = sorted((o[2], o[3]) for o in tr.ops)
    busy, end = 0, None
    for s, e in evs:
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert devtrace.busy_s(tr) == pytest.approx(busy / 1e9)
    kern = sum(o[3] - o[2] for o in tr.ops
               if o[1].startswith("paged_attention."))
    assert devtrace.op_time_s(tr, ("paged_attention",)) == pytest.approx(kern / 1e9)
    assert busy / 1e9 == pytest.approx(want["busy_s"])
    assert kern / 1e9 == pytest.approx(want["paged_decode_s"])
