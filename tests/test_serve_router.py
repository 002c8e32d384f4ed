"""Multi-replica router: affinity scoring, routed-vs-single bit-exactness
(incl. quantized KV + a spec lane on one replica), sticky sessions,
bounce/requeue TTFT preservation, replica-death rerouting, disaggregated
prefill/decode handoff, and the merged cross-replica trace invariants."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import events as ev
from repro.core.paraver import parse_prv
from repro.serve.queue import RequestQueue
from repro.serve.router import PrefixAffinity, Router, replica_envs

# workers are their own jax processes — force the CPU backend and keep
# compiles single-device regardless of what the host test process does
WORKER_ENV = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
RED = {"num_layers": 2}
ENGINE = {"num_slots": 2, "max_len": 64, "block_size": 16, "chunk_size": 8}
VOCAB = 128  # < every reduced vocab


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (L,)).astype(np.int32) for L in lens]


def _oracle(prompts, gen, *, kv_dtype=None, seed=2205):
    """Single in-process UnifiedServeEngine over the same requests."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models.model import build_model
    from repro.serve.step import UnifiedServeEngine

    cfg = reduced(get_config("granite-8b"), **RED)
    if kv_dtype:
        cfg = cfg.replace(kv_dtype=kv_dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = UnifiedServeEngine(cfg, params, **ENGINE)
    reqs = [eng.submit(p, gen) for p in prompts]
    out = eng.run()
    return [out[r.rid] for r in reqs]


# ----------------------------------------------------------------------
# one process per chip: replica environments, subprocess-free
# ----------------------------------------------------------------------
@pytest.mark.parametrize("platforms,chips", [("cpu", 4), ("", 0)])
def test_replica_envs_share_env_without_a_chip(platforms, chips):
    env = {"JAX_PLATFORMS": platforms, "KEEP": "1"}
    envs = replica_envs(3, env, chips=chips)
    assert envs == [env] * 3
    assert all(e is not env for e in envs)


def test_replica_envs_pin_one_chip_per_replica():
    envs = replica_envs(4, {"KEEP": "1"}, chips=4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["KEEP"] == "1" and e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               for e in envs)
    ports = {e["TPU_MESH_CONTROLLER_PORT"] for e in envs}
    assert len(ports) == 4  # no two replicas share a controller


def test_replica_envs_refuse_more_replicas_than_chips():
    with pytest.raises(RuntimeError, match="2 replicas need one TPU chip "
                                           "each, but this host has 1"):
        replica_envs(2, {}, chips=1)


# ----------------------------------------------------------------------
# affinity scoring: deterministic, subprocess-free
# ----------------------------------------------------------------------
def test_prefix_affinity_scoring_deterministic():
    """Same prefix -> same (publishing) replica wins with a block-resolution
    token score; a cold prefix scores zero everywhere (-> least-loaded
    fallback at the router); scoring is a pure function of published
    state."""
    aff = PrefixAffinity(block_size=16)
    for r in range(3):
        aff.add_replica(r)
    base = np.arange(40, dtype=np.int32)  # 2 full blocks + 8-token tail
    aff.publish(1, base)
    # same 32-token prefix, different tail -> replica 1 scores 2 blocks
    warm = np.concatenate([base[:32], np.full(10, 99, np.int32)])
    scores = aff.score(warm, [0, 1, 2])
    assert scores == {0: 0, 1: 32, 2: 0}
    assert aff.score(warm, [0, 1, 2]) == scores  # deterministic
    # divergence INSIDE the first block kills the whole chain (hashes chain
    # off the parent), so a one-token flip scores cold
    cold = base.copy()
    cold[3] += 1
    assert aff.score(cold, [0, 1, 2]) == {0: 0, 1: 0, 2: 0}
    # partial overlap: only the leading resident RUN counts
    aff.publish(2, base[:16])
    assert aff.score(warm, [1, 2]) == {1: 32, 2: 16}
    # death drops the set
    aff.drop_replica(1)
    assert aff.score(warm, [1, 2])[1] == 0


def test_bounce_preserves_arrival_ns():
    """Satellite regression: a request bounced off a full replica keeps its
    ORIGINAL arrival_ns (TTFT must cover the bounce), while per-admission
    state resets for the next replica's fresh prefill."""
    q = RequestQueue()
    req = q.submit(np.arange(8, dtype=np.int32), 4, arrival_ns=123456789)
    got = q.pop()
    assert got is req
    got.slot = 1
    got.tokens = [5, 6]
    got.t_admit_ns = got.t_first_ns = 999
    got.prefix_hit_tokens = 16
    back = q.bounce(got)
    assert back is req
    assert req.arrival_ns == 123456789  # THE invariant: TTFT keeps counting
    assert req.bounces == 1
    assert req.slot == -1 and req.tokens == [] and req.t_first_ns == -1
    assert req.prefix_hit_tokens == 0
    assert q.peek() is req  # front of the queue, not the back


# ----------------------------------------------------------------------
# routed == single engine, bit for bit
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype,per_replica", [
    (None, None),
    ("int8", {1: {"spec": "ngram", "spec_k": 3}}),  # heterogeneous fleet
], ids=["fp16", "int8+spec-lane"])
def test_routed_matches_single_engine(kv_dtype, per_replica):
    """Greedy output per request is bit-identical whether the requests are
    served by one local engine or spread over a 2-replica routed fleet —
    replicas init identical params (PRNGKey(0), same reduced cfg) and
    greedy decode is batching-order-independent; the spec lane on replica
    1 is output-invariant by the speculative-decoding contract."""
    lens = [7, 20, 33, 18, 25]
    prompts = _prompts(lens, seed=3)
    want = _oracle(prompts, 8, kv_dtype=kv_dtype)
    cfg = {"kv_dtype": kv_dtype} if kv_dtype else None
    with Router("granite-8b", num_replicas=2, route="prefix", reduced=RED,
                cfg=cfg, engine=ENGINE, per_replica=per_replica,
                worker_env=WORKER_ENV) as router:
        reqs = [router.submit(p, 8) for p in prompts]
        out = router.run()
        # spread across BOTH replicas (unique prompts -> least-loaded)
        served = {router.request_info[r.rid]["replica"] for r in reqs}
        assert all(not p for p in router.pending)
        assert router.stats["route_decisions"] == len(prompts)
    for req, exp in zip(reqs, want):
        np.testing.assert_array_equal(out[req.rid], exp)
    assert served == {0, 1}


def test_sticky_sessions_and_prefix_hits_across_turns():
    """Turn 2 of a session must land on the replica already holding its KV:
    round-robin would alternate replicas, but the sticky map pins the
    session — observable as real prefix-cache hits on the second turn."""
    prompts = _prompts([32, 32], seed=5)
    with Router("granite-8b", num_replicas=2, route="rr", reduced=RED,
                engine=ENGINE, worker_env=WORKER_ENV) as router:
        r0 = router.submit(prompts[0], 4, session="alpha")
        r1 = router.submit(prompts[1], 4, session="beta")
        router.run()
        first = dict(router.session_of)
        assert first["alpha"] != first["beta"]  # rr spread them
        # turn 2: same 32-token prefix + the turn-1 tokens as continuation
        t2 = [router.submit(
            np.concatenate([p, router.results[r.rid]]), 4, session=s)
            for p, r, s in ((prompts[0], r0, "alpha"),
                            (prompts[1], r1, "beta"))]
        router.run()
        assert dict(router.session_of) == first  # sticky under rr
        for req in t2:
            # 32-token shared prefix = 2 blocks resident from turn 1
            assert router.request_info[req.rid]["prefix_hit_tokens"] >= 32


def test_full_replica_bounces_and_ttft_spans_bounce():
    """A 1-replica fleet with max_inflight=1 forces every queued request to
    bounce until capacity frees; the bounced requests finish with their
    original arrival_ns intact (regression for TTFT resetting on
    re-admission)."""
    prompts = _prompts([10, 12, 14], seed=7)
    with Router("granite-8b", num_replicas=1, route="least-loaded",
                reduced=RED, engine=ENGINE, max_inflight=1,
                worker_env=WORKER_ENV) as router:
        t0 = 11111  # deterministic arrival epoch, distinct per request
        reqs = [router.submit(p, 4, arrival_ns=t0 + i)
                for i, p in enumerate(prompts)]
        out = router.run()
        assert router.stats["bounces"] >= 2
        for i, req in enumerate(reqs):
            assert len(out[req.rid]) == 4
            assert req.arrival_ns == t0 + i  # bounce never reset arrival
            # worker-measured TTFT used the original arrival -> it spans
            # the bounce wait, so it is monotonically large and positive
            assert router.request_info[req.rid]["ttft_ns"] > 0


def test_replica_death_reroutes_inflight_requests():
    """Killing a replica with admitted work mid-flight must not lose
    requests: the router buries it, drops its affinity/sticky state, and
    bounces its in-flight requests to the survivor — results complete and
    still match the single-engine oracle."""
    prompts = _prompts([9, 17, 26, 13], seed=9)
    want = _oracle(prompts, 6)
    with Router("granite-8b", num_replicas=2, route="least-loaded",
                reduced=RED, engine=ENGINE, worker_env=WORKER_ENV) as router:
        reqs = [router.submit(p, 6) for p in prompts]
        router._dispatch()  # place requests, nothing collected yet
        victim = max((h for h in router.handles),
                     key=lambda h: len(router.pending[h.idx]))
        assert router.pending[victim.idx]  # it held in-flight work
        router.kill_replica(victim.idx)
        assert router.stats["deaths"] == 1
        assert router.stats["bounces"] >= 1
        assert victim.idx not in router.affinity.resident
        out = router.run()
        survivor = next(h for h in router.handles if h.alive)
        assert survivor.idx != victim.idx
    for req, exp in zip(reqs, want):
        np.testing.assert_array_equal(out[req.rid], exp)


# ----------------------------------------------------------------------
# merged cross-replica trace
# ----------------------------------------------------------------------
def test_merged_trace_invariants(tmp_path):
    """ONE .prv spanning router + every replica: host x device rows,
    EV_ROUTE_DECISION balance against admits, and per-replica block
    conservation (FREE + ACTIVE + CACHED == num_blocks - 1 at the final
    gauge) straight off the merged events."""
    prompts = _prompts([8, 19, 24, 31], seed=11)
    with Router("granite-8b", num_replicas=2, route="prefix", reduced=RED,
                engine=ENGINE, trace=True, worker_env=WORKER_ENV) as router:
        reqs = [router.submit(p, 5) for p in prompts]
        router.run()
        num_blocks = {1 + h.idx: None for h in router.handles}
        paths = router.close(tmp_path / "fleet")
        for h in router.handles:
            num_blocks[1 + h.idx] = h.num_blocks
    trace = parse_prv(paths["prv"])
    assert trace.num_tasks == 3  # router + 2 replicas
    assert len(trace.threads_per_task) == 3
    # .row declares one THREAD row per fleet task
    row_text = paths["row"].read_text()
    for t in (1, 2, 3):
        assert f"THREAD 1.{t}.1" in row_text
    evs = trace.events
    route = evs[evs["type"] == ev.EV_ROUTE_DECISION]
    assert len(route) == len(reqs) == len(prompts)
    assert (route["task"] == 0).all()  # router decisions live on task 0
    assert set(route["value"]) <= {1, 2}
    hits = evs[evs["type"] == ev.EV_ROUTE_PREFIX_HITS]
    assert len(hits) == len(route)  # one expected-hits counter per decision
    # every replica task carries engine events; the router carries none
    for t in (1, 2):
        assert (evs["task"] == t).any()
    retired = evs[evs["type"] == ev.EV_REQ_RETIRE]
    assert len(retired) == len(reqs)
    # block conservation per replica from its LAST gauge triple
    for t in (1, 2):
        final = {}
        for code in (ev.EV_BLOCKS_FREE, ev.EV_BLOCKS_CACHED,
                     ev.EV_BLOCKS_ACTIVE):
            sel = evs[(evs["task"] == t) & (evs["type"] == code)]
            assert len(sel), f"task {t} never emitted gauge {code}"
            final[code] = int(sel["value"][np.argmax(sel["time"])])
        assert sum(final.values()) == num_blocks[t] - 1  # block 0 reserved


def test_disaggregated_handoff(tmp_path):
    """--disaggregate: prompts prefill on replica 0, KV blocks stream to
    the decode replica (EV_KV_XFER_BYTES > 0), the decode admission
    prefix-hits the transferred blocks, decode-side TTFT spans the whole
    handoff, and with an int8 pool the wire is lossless so greedy output
    still matches the single-engine oracle bit for bit."""
    prompts = _prompts([35, 40], seed=13)  # >= 2 full blocks each
    want = _oracle(prompts, 6, kv_dtype="int8")
    with Router("granite-8b", num_replicas=2, route="prefix",
                disaggregate=True, reduced=RED, cfg={"kv_dtype": "int8"},
                engine=ENGINE, trace=True, worker_env=WORKER_ENV) as router:
        reqs = [router.submit(p, 6) for p in prompts]
        out = router.run()
        assert router.stats["kv_xfers"] == len(prompts)
        assert router.stats["kv_xfer_bytes"] > 0
        # the transferred blocks were HIT, not recomputed: 2 full blocks of
        # the 35-token prompt, 2 of the 40-token one
        assert router.stats["prefix_hit_tokens"] >= 64
        info = [router.request_info[r.rid] for r in reqs]
        paths = router.close(tmp_path / "disagg")
    for req, exp in zip(reqs, want):
        np.testing.assert_array_equal(out[req.rid], exp)
    trace = parse_prv(paths["prv"])
    evs = trace.events
    xfer = evs[evs["type"] == ev.EV_KV_XFER_BYTES]
    assert len(xfer) == len(prompts) and (xfer["value"] > 0).all()
    assert (xfer["task"] == 0).all()  # the router records the handoff
    # end-to-end TTFT: the decode replica (task 2) emitted one TTFT per
    # request, measured from the ORIGINAL arrival — so it must be at least
    # as large as the worker-reported prefill-side share
    ttft_decode = evs[(evs["type"] == ev.EV_REQ_TTFT_US) & (evs["task"] == 2)]
    assert len(ttft_decode) == len(prompts)
    assert all(i["ttft_ns"] > 0 for i in info)
