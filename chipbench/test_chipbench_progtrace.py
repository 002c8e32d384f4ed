"""The program's spans and scopes in a profiler trace (progtrace): the two
readers built on them, by hand on a small made-up trace and on a slice
recorded on the chip; and the accepted readers, which read the older
recorded slice exactly as before whichever loader kept it."""
import json
import pathlib

import pytest

import cellspec
import devtrace
import progtrace

HERE = pathlib.Path(__file__).resolve().parent
OLD = HERE / "fixtures" / "decode_trace_slice.json"


def read(name, rec):
    return cellspec.reader(name)(rec)


DEC = "jit(_unified_impl)/decode/while/body"
SYNTH = progtrace.Trace(
    ops=[(0, "while.1", 0, 1000),  # holds the next two
         (0, "fusion.1", 0, 200),
         (0, "paged_attention.3", 200, 800),
         (0, "copy.5", 1200, 1500),
         (0, "fusion.9", 2000, 2100),
         (0, "dynamic-update-slice.2", 9900, 10300)],  # past the window
    scopes=["jit(_unified_impl)/decode/while",
            f"{DEC}/kv_write/scatter",
            f"{DEC}/paged_attention/pallas_call",
            "",
            "jit(_unified_impl)/kv_writes/add",  # not the kv_write scope
            f"{DEC}/kv_write/dynamic_update_slice"],
    spans=[("trace.window", 0, 10_000), ("engine.run", 0, 10_000),
           ("serve.unified_step", -500, 50),  # starts before the window
           ("serve.plan", 100, 300), ("serve.blocks", 300, 400),
           ("serve.unified_step", 400, 1000), ("serve.fetch", 1000, 5000),
           ("serve.retire", 5000, 5600),
           ("serve.fetch", 5100, 5300),  # a wait nested in host work
           ("serve.plan", 5600, 5800), ("serve.blocks", 5800, 5900),
           ("serve.unified_step", 5900, 6500),
           ("serve.fetch", 6500, 10_500)],
    devices=1)


def test_readers_by_hand():
    # host work: 50 (clipped) + 200 + 100 + 600 + (600 - 200) + 200 + 100
    # + 600, over the two dispatches that start in the window
    assert progtrace.host_ms_per_dispatch(SYNTH) == pytest.approx(
        2250 / 2 / 1e6)
    # busy 1000 + 300 + 100 + 100; kv_write self time 200 + 100 (clipped)
    assert progtrace.scope_share(SYNTH, "kv_write") == pytest.approx(
        100 * 300 / 1500)
    # decode: the loop's own 200, its 200 and 600, and the clipped 100
    assert progtrace.scope_share(SYNTH, "decode") == pytest.approx(
        100 * 1100 / 1500)
    rec = {"trace": SYNTH}
    assert read("host_ms_per_dispatch.decode", rec) == pytest.approx(
        2250 / 2 / 1e6)
    assert read("kv_write_share.decode", rec) == pytest.approx(20.0)
    # each idle gap is named by the innermost span over its middle
    assert devtrace.idle_gaps(SYNTH)[:3] == [
        ["serve.unified_step", pytest.approx(7800e-9)],
        ["serve.fetch", pytest.approx(500e-9)],
        ["serve.fetch", pytest.approx(200e-9)]]


def test_json_round_trip_keeps_scopes():
    d = progtrace.to_json(SYNTH, 0, 10_000)
    back = progtrace.from_json(d)
    assert back.scopes == SYNTH.scopes
    assert progtrace.scope_share(back, "kv_write") == pytest.approx(20.0)


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _msg(*fields):
    """Protobuf wire bytes of (field number, int | bytes | str) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_reads_the_tf_op_stat_of_device_ops(tmp_path):
    def stat_meta(i, name):
        return (5, _msg((1, i), (2, _msg((1, i), (2, name)))))

    def op(i, name, *stats):
        return (4, _msg((1, i), (2, _msg((1, i), (2, name),
                                         *[(5, st) for st in stats]))))

    tpu = _msg((2, "/device:TPU:0 TensorCore"), (3, _msg((2, "XLA Ops"))),
               stat_meta(3, "tf_op"), stat_meta(4, "hlo_category"),
               stat_meta(9, "jit(f)/decode/dot_general:"),
               op(1, "%fusion.1 = f32[8] fusion()",
                  _msg((1, 4), (5, "loop fusion")),
                  _msg((1, 3), (5, "jit(f)/decode/kv_write/scatter:"))),
               op(2, "%fusion.2 = f32[8] fusion()", _msg((1, 3), (7, 9))),
               op(3, "%copy-done = f32[8] copy-done()"))
    host = _msg((2, "/host:CPU"), stat_meta(3, "tf_op"),
                op(1, "serve.plan", _msg((1, 3), (5, "not a device op"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, tpu), (3, "a warning")))
    assert progtrace.op_scopes(str(path)) == {
        "%fusion.1 = f32[8] fusion()": "jit(f)/decode/kv_write/scatter:",
        "%fusion.2 = f32[8] fusion()": "jit(f)/decode/dot_general:"}


SCOPED = HERE / "fixtures" / "decode_trace_scoped.json"


def test_recorded_scoped_slice():
    d = json.loads(SCOPED.read_text())
    tr = progtrace.from_json(d["trace"])
    want = d["expect"]
    # recomputed here from the raw records: the serving thread's spans do
    # not nest, and the ops under kv_write hold no other op
    host = sum(e - s for n, s, e in tr.spans if n in progtrace.HOST_WORK)
    starts = [s for n, s, _ in tr.spans if n in progtrace.DISPATCH]
    kv = [o for o, sc in zip(tr.ops, tr.scopes) if "kv_write" in sc.split("/")]
    assert kv and not any(o[0] == k[0] and k[2] <= o[2] and o[3] <= k[3]
                          and o is not k for k in kv for o in tr.ops)
    kv_s = sum(o[3] - o[2] for o in kv) / 1e9
    assert len(starts) == want["dispatches"]
    assert host / len(starts) / 1e6 == pytest.approx(want["host_ms_per_dispatch"])
    assert kv_s == pytest.approx(want["kv_write_s"])
    assert devtrace.busy_s(tr) == pytest.approx(want["busy_s"])
    rec = {"trace": tr}
    assert read("host_ms_per_dispatch.decode", rec) == pytest.approx(
        want["host_ms_per_dispatch"])
    assert read("kv_write_share.decode", rec) == pytest.approx(
        100 * want["kv_write_s"] / want["busy_s"])


def _old_rec(tr):
    return {"config": cellspec.config("yi-9b-l24"),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "chips": 1, "window_s": 30.0, "trace": tr,
            "trace_window_s": devtrace.window_s(tr),
            "decode": {"tokens": 3375, "ctx_sum": 7_500_000, "streams": 15},
            "trace_decode": {"tokens": 30, "ctx_sum": 66_000}}


# read on the accepted benchmark's code before the program's spans existed
OLD_READINGS = {"decode_mfu": 0.5540952365482233,
                "decode_hbm_share": 9.5862753992674,
                "paged_decode_roofline": 17.862216246166607,
                "idle_share.decode": 0.0}


@pytest.mark.parametrize("loader", [devtrace.from_json, progtrace.from_json])
@pytest.mark.parametrize("metric", sorted(OLD_READINGS))
def test_accepted_readers_read_the_old_slice_unchanged(loader, metric):
    tr = loader(json.loads(OLD.read_text())["trace"])
    assert read(metric, _old_rec(tr)) == OLD_READINGS[metric]


@pytest.mark.parametrize("metric", ["host_ms_per_dispatch.decode",
                                    "kv_write_share.decode"])
def test_new_readers_find_nothing_in_an_older_program_s_trace(metric):
    d = json.loads(OLD.read_text())["trace"]
    for tr in (devtrace.from_json(d), progtrace.from_json(d)):
        assert read(metric, _old_rec(tr)) is None
