"""What the harness takes from the data files, on the CPU at a small size:
the configuration's ``program`` block and the mix's ``engine`` block reach
the program, and a cell on more chips than the engine is built on is
refused rather than run on one."""
import jax
import pytest

import harness
import tinycell


def test_a_cell_on_more_than_one_chip_is_refused():
    with pytest.raises(SystemExit, match="4 chips"):
        harness.devices(jax, 4, require_tpu=False)
    assert len(harness.devices(jax, 1, require_tpu=False)) == 1


def test_program_and_engine_blocks_reach_the_engine():
    c = tinycell.config("yi-9b-l24")
    c["program"]["kv_dtype"] = "int8"
    mx = tinycell.mix("decode-4k")
    mx["engine"]["mixed_burst"] = 2
    cfg, _, engine = harness.build_engine(c, mx, 2 ** 36 + 5)
    assert cfg.kv_dtype == engine.cfg.kv_dtype == "int8"
    assert cfg.num_layers == c["num_hidden_layers"]
    assert engine.mixed_burst == 2
    assert (engine.num_slots, engine.chunk_size, engine.max_step_tokens) == (
        4, 16, 36)


def test_window_counts_stop_at_the_close_not_after_the_profiler(monkeypatch):
    """The profiler's stop takes seconds on a chip while the engine goes on
    serving; the decode work of the window must not take those tokens in."""
    import time

    import cellspec
    import devtrace

    stop = devtrace.Profiler.stop

    def slow_stop(self):
        time.sleep(1.0)
        stop(self)

    monkeypatch.setattr(devtrace.Profiler, "stop", slow_stop)
    recs = []
    reader = cellspec.reader

    def spy(name):
        fn = reader(name)
        return lambda rec: (recs.append(rec), fn(rec))[1]

    monkeypatch.setattr(cellspec, "reader", spy)
    wl = "yi-9b-l24.decode-4k"
    files = tinycell.files(wl, dtype="bfloat16", limit=0.1)
    files["mix"]["requests"] = 400  # streams go on through the stop
    res = harness.main(["--workload", wl, "--seed", "9", "--seconds", "2",
                        "--trace", "1"], require_tpu=False, files=files)
    rec = recs[0]
    # the closed loop's streams each count their first token apart
    assert 0 < rec["decode"]["tokens"] <= rec["tokens_in_window"]
    assert rec["trace_decode"]["tokens"] <= rec["decode"]["tokens"]
    assert res["metrics"]["decode_mfu"]["value"] > 0
