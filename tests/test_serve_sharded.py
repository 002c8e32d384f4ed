"""Tensor-parallel serving over the device mesh (subprocess, forced CPU
devices): greedy paged decode on an mp>=2 model-parallel mesh must be
BIT-IDENTICAL to the single-device paged engine across full/SWA/GQA/hybrid
configs (including the Pallas paged kernel via shard_map), and a traced
mesh run must produce per-task segment streams that merge mpi2prv-style
into one ``.prv`` that round-trips with the real mesh's task/thread rows
and per-task event conservation."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

ROOT = "/root/repo"


def _run(script: str, timeout: int = 560):
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT, timeout=timeout,
    )


EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousServeEngine

    mesh = make_mesh((1, 2), ("data", "model"))
    # full+GQA / SWA+GQA+MoE / hybrid (rec+attn); kv=2 so GQA kv heads
    # split across the model axis (the tentpole's head-sharded decode)
    cases = [("granite-8b", {}), ("mixtral-8x22b", {}),
             ("recurrentgemma-9b", {})]
    for arch, extra in cases:
        cfg = reduced(get_config(arch), num_layers=2, num_kv_heads=2, **extra)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (4, 16)).astype(np.int32)
        ref = ContinuousServeEngine(cfg, params, num_slots=4, max_len=64,
                                    block_size=16)
        out_ref = ref.serve_batch(prompts, num_tokens=8)
        eng = ContinuousServeEngine(cfg, params, num_slots=4, max_len=64,
                                    block_size=16, mesh=mesh)
        out = eng.serve_batch(prompts, num_tokens=8)
        np.testing.assert_array_equal(out, out_ref, err_msg=arch)
        # decode burst pipelining unchanged by sharding: <=1 sync/iteration
        assert eng.stats["decode_syncs"] <= eng.stats["iterations"]
        print("OK", arch)

    # Pallas paged-decode kernel through shard_map (per-shard head slice,
    # interpret mode off-TPU) against the single-device gather path
    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    ref = ContinuousServeEngine(cfg, params, num_slots=4, max_len=64,
                                block_size=16)
    out_ref = ref.serve_batch(prompts, num_tokens=8)
    eng = ContinuousServeEngine(cfg.replace(kernel_mode="pallas"), params,
                                num_slots=4, max_len=64, block_size=16,
                                mesh=mesh)
    out = eng.serve_batch(prompts, num_tokens=8)
    np.testing.assert_array_equal(out, out_ref, err_msg="paged kernel mp=2")
    assert eng.stats["kernel_dispatch"].get("paged_decode:pallas", 0) > 0, \
        eng.stats["kernel_dispatch"]
    print("OK paged-kernel")

    # head_dim-sharded pool (kv=1, the rules' last resort) + kernel_mode=
    # pallas must fall back to the gather path — a plain pallas_call over a
    # D-sharded pool is an unpartitionable custom call
    cfg1 = reduced(get_config("granite-8b"), num_layers=2)  # kv=1
    model1 = build_model(cfg1)
    params1 = model1.init(jax.random.PRNGKey(0))
    ref1 = ContinuousServeEngine(cfg1, params1, num_slots=2, max_len=64,
                                 block_size=16)
    out_ref1 = ref1.serve_batch(prompts[:2], num_tokens=8)
    eng1 = ContinuousServeEngine(cfg1.replace(kernel_mode="pallas"), params1,
                                 num_slots=2, max_len=64, block_size=16,
                                 mesh=mesh)
    np.testing.assert_array_equal(eng1.serve_batch(prompts[:2], num_tokens=8),
                                  out_ref1, err_msg="hd-sharded fallback")
    print("OK hd-sharded-fallback")
""")


def test_mp_decode_bit_identical_to_single_device():
    r = _run(EQUIV_SCRIPT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert r.stdout.count("OK") == 5, r.stdout


UNIFIED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.core import events as ev
    from repro.core.tracer import Tracer
    from repro.models.model import build_model
    from repro.serve.step import UnifiedServeEngine

    mesh = make_mesh((1, 2), ("data", "model"))
    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [7, 16, 21, 30]  # chunk- and block-boundary crossing
    prompts = [np.random.default_rng(1).integers(
        0, cfg.vocab_size, (L,)).astype(np.int32) for L in lens]

    ref = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                             block_size=16, chunk_size=8)
    rs = [ref.submit(p, 8) for p in prompts]
    out_ref = ref.run()

    tracer = Tracer("serve-unified-mp2").init()
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                             block_size=16, chunk_size=8, mesh=mesh,
                             tracer=tracer)
    rm = [eng.submit(p, 8) for p in prompts]
    out = eng.run()
    trace = tracer.finish()
    for a, b in zip(rs, rm):
        np.testing.assert_array_equal(out_ref[a.rid], out[b.rid])
    # the chunked interleave survives sharding: budget counters emitted and
    # the AOT unified executables' collective schedules replayed per window
    for code in (ev.EV_STEP_BUDGET, ev.EV_CHUNK_TOKENS, ev.EV_DECODE_TOKENS):
        assert (trace.events["type"] == code).sum() > 0, code
    assert len(trace.comms) > 0  # replayed collectives from the unified step
    print("OK unified-mp2")
""")


def test_unified_mp_bit_identical_and_traced():
    r = _run(UNIFIED_SCRIPT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "OK unified-mp2" in r.stdout


TRACE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import pathlib, tempfile
    import jax, numpy as np
    from repro import core as xtrace
    from repro.core import events as ev
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousServeEngine

    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)

    out = pathlib.Path(tempfile.mkdtemp())
    mesh = make_mesh((2, 2), ("data", "model"))  # 2 TASKs x 2 THREADs
    tracer = xtrace.init("serve-mesh")
    eng = ContinuousServeEngine(cfg, params, num_slots=4, max_len=64,
                                block_size=16, mesh=mesh, tracer=tracer,
                                flush_every=4, flush_base=out / "serve")
    eng.serve_batch(prompts, num_tokens=8)
    segments = list(tracer.segments)
    trace = xtrace.finish()

    # per-task segment files, named per task (Extrae per-rank .mpit shape)
    names = [s.name for s in segments]
    assert any(".task0000." in n for n in names), names
    assert any(".task0001." in n for n in names), names

    paths = xtrace.write_prv(trace, out / "serve", segments=segments)
    parsed = xtrace.parse_prv(paths["prv"])

    # ROW/CPU structure reflects the REAL mesh: 2 tasks x 2 model threads
    assert parsed.num_tasks == 2, parsed.num_tasks
    assert parsed.threads_per_task == [2, 2], parsed.threads_per_task
    row = paths["row"].read_text().splitlines()
    assert row[0] == "LEVEL CPU SIZE 4", row[0]
    assert "THREAD 1.2.2" in row, row[-4:]

    # per-task conservation: collective enters == exits on EVERY task, and
    # records landed on BOTH tasks (HLO collectives attributed by mesh_data)
    coll = parsed.events[parsed.events["type"] == ev.EV_COLLECTIVE]
    for t in range(parsed.num_tasks):
        e = coll[coll["task"] == t]
        enters = int((e["value"] != 0).sum())
        assert enters > 0 and enters == int((e["value"] == 0).sum()), (t, enters)
        st = parsed.states[parsed.states["task"] == t]
        assert len(st) and int(st["end"].max()) <= parsed.t_end
    # threads beyond 0 got records too (model-axis coordinate = THREAD)
    assert int(coll["thread"].max()) == 1
    # comm records stay within the mesh endpoints
    if len(parsed.comms):
        assert int(parsed.comms["rtask"].max()) < parsed.num_tasks
        assert int(parsed.comms["rthread"].max()) < 2
    print("OK trace", parsed.summary())
""")


def test_mesh_trace_per_task_merge_roundtrip():
    r = _run(TRACE_SCRIPT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert r.stdout.startswith("OK trace")


def _overlap_equiv_script(cases: str) -> str:
    return textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        from repro.compat import make_mesh
        from repro.configs import get_config, reduced
        from repro.models.model import build_model
        from repro.serve.step import UnifiedServeEngine

        mesh = make_mesh((1, 2), ("data", "model"))
        lens = [7, 16, 21, 30]  # chunk- and block-boundary crossing
        for arch, repl in CASES:
            cfg = reduced(get_config(arch), num_layers=2, num_kv_heads=2)
            if repl:
                cfg = cfg.replace(**repl)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            prompts = [np.random.default_rng(1).integers(
                0, cfg.vocab_size, (L,)).astype(np.int32) for L in lens]
            outs = {}
            for mode in ("off", "on"):
                eng = UnifiedServeEngine(
                    cfg, params, num_slots=2, max_len=64, block_size=16,
                    chunk_size=8, mesh=mesh, overlap=mode)
                rs = [eng.submit(p, 8) for p in prompts]
                done = eng.run()
                outs[mode] = [done[r.rid] for r in rs]
                # decode-sync invariant: every decode-carrying dispatch is
                # fetched exactly once, flush boundaries notwithstanding
                assert eng.stats["decode_syncs"] == \\
                    eng.stats["decode_dispatches"], (arch, mode, eng.stats)
            assert eng.overlap.enabled and eng.overlap.micro_batches == 2
            assert eng.stats["planned_ahead"] > 0  # two-deep queue engaged
            # canonical metric derives from decode_syncs, <= 1 per iteration
            ts = eng.throughput_stats()
            assert 0 < ts["host_syncs_per_decode_iter"] <= 1.0, ts
            for a, b in zip(outs["off"], outs["on"]):
                np.testing.assert_array_equal(a, b, err_msg=str((arch, repl)))
            print("OK", arch, repl or "base")
    """).replace("CASES", cases)


def test_overlap_bit_identical_mp2():
    """Micro-batched + double-buffered greedy decode == non-overlapped
    sharded oracle: dense GQA and the Pallas span kernel via shard_map."""
    r = _run(_overlap_equiv_script(
        '[("granite-8b", {}), ("granite-8b", {"kernel_mode": "pallas"})]'))
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert r.stdout.count("OK") == 2, r.stdout


def test_overlap_bit_identical_mp2_moe_and_int8():
    """MoE capacity dispatch (token-count coupled) and the quantized int8
    pool survive the micro-batch split bit-exactly."""
    r = _run(_overlap_equiv_script(
        '[("mixtral-8x22b", {}), ("granite-8b", {"kv_dtype": "int8"})]'))
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert r.stdout.count("OK") == 2, r.stdout


SPEC_OVERLAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import pathlib, tempfile
    import jax, numpy as np
    from repro import core as xtrace
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.core import events as ev
    from repro.models.model import build_model
    from repro.serve.spec import make_proposer
    from repro.serve.step import UnifiedServeEngine

    mesh = make_mesh((1, 2), ("data", "model"))
    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [16, 21]
    prompts = [np.random.default_rng(1).integers(
        0, cfg.vocab_size, (L,)).astype(np.int32) for L in lens]

    def build(overlap, tracer=None):
        return UnifiedServeEngine(
            cfg, params, num_slots=2, max_len=64, block_size=16,
            chunk_size=8, mesh=mesh, overlap=overlap, tracer=tracer,
            spec=make_proposer("ngram", cfg, num_slots=2, max_len=64),
            spec_k=3)

    ref = build("off")
    rs = [ref.submit(p, 8) for p in prompts]
    out_ref = ref.run()

    out_dir = pathlib.Path(tempfile.mkdtemp())
    tracer = xtrace.init("spec-ovl")
    eng = build("on", tracer)
    rm = [eng.submit(p, 8) for p in prompts]
    out = eng.run()
    for a, b in zip(rs, rm):
        np.testing.assert_array_equal(out_ref[a.rid], out[b.rid])
    assert eng.overlap.micro_batches == 2
    assert eng.stats["spec_dispatches"] > 0
    assert eng.stats["decode_syncs"] == eng.stats["decode_dispatches"]
    trace = xtrace.finish()
    paths = xtrace.write_prv(trace, out_dir / "spec")
    parsed = xtrace.parse_prv(paths["prv"])

    # EV_COMM_* balance per dispatch: the pair is emitted together at every
    # replayed window end, so counts match exactly on every task — and the
    # sums agree with the engine's accumulated stats
    evs = parsed.events
    ovl = evs[evs["type"] == ev.EV_COMM_OVERLAP_US]
    blk = evs[evs["type"] == ev.EV_COMM_BLOCKED_US]
    assert len(ovl) > 0
    for t in np.unique(evs["task"]):
        n_o = int((ovl["task"] == t).sum())
        n_b = int((blk["task"] == t).sum())
        assert n_o == n_b > 0, (t, n_o, n_b)
    # any single endpoint's sum reproduces the engine's per-dispatch stats
    sel_o = (ovl["task"] == 0) & (ovl["thread"] == 0)
    sel_b = (blk["task"] == 0) & (blk["thread"] == 0)
    assert int(ovl["value"][sel_o].sum()) == eng.stats["comm_overlap_us"]
    assert int(blk["value"][sel_b].sum()) == eng.stats["comm_blocked_us"]
    assert eng.stats["comm_overlap_us"] > 0  # the pipeline actually hid comm
    from repro.core.analysis import comm_overlap_summary
    s = comm_overlap_summary(parsed)
    assert 0.0 < s["overlap_fraction"] < 1.0, s
    print("OK spec-overlap", s["overlap_fraction"])
""")


def test_spec_overlap_and_comm_counter_balance():
    r = _run(SPEC_OVERLAP_SCRIPT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "OK spec-overlap" in r.stdout


RULES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    from jax.sharding import AbstractMesh
    from repro.configs import get_config, reduced
    from repro.sharding.partition import make_serve_rules

    mesh = AbstractMesh((1, 2), ("data", "model"))
    # kv divisible -> pooled KV kv-head sharded, scheduler state replicated
    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    r = make_serve_rules(cfg, mesh)
    assert r.mapping["kv_heads"] == "model"
    assert r.mapping["cache_hd"] is None
    assert r.mapping["act_batch"] is None and r.mapping["cache_batch"] is None
    # kv NOT divisible -> head_dim last resort
    cfg1 = reduced(get_config("granite-8b"), num_layers=2)  # kv=1
    r1 = make_serve_rules(cfg1, mesh)
    assert r1.mapping["kv_heads"] is None and r1.mapping["cache_hd"] == "model"
    # nothing shardable -> loud failure before any compile (padded vocab is
    # always 128-aligned, so an odd model extent is what exposes this)
    mesh3 = AbstractMesh((1, 3), ("data", "model"))
    try:
        make_serve_rules(cfg1, mesh3)
    except ValueError as e:
        assert "model axis" in str(e)
        print("OK rules")
    else:
        raise AssertionError("misconfigured mesh was not rejected")
""")


def test_serve_rules_decisions_and_loud_failure():
    r = _run(RULES_SCRIPT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "OK rules" in r.stdout
