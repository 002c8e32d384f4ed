"""Hypothesis property tests on system invariants.

The dedicated CI property step sets ``REPRO_REQUIRE_HYPOTHESIS=1`` so a
missing hypothesis install fails LOUDLY there instead of silently skipping
the whole file (developer machines without it still skip gracefully).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

if os.environ.get("REPRO_REQUIRE_HYPOTHESIS"):
    import hypothesis
else:
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="hypothesis not installed (see requirements-test.txt)"
    )
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import events as ev
from repro.core.analysis import bandwidth_timeline, connectivity, time_fractions
from repro.core.hlo_comm import CollectiveOp
from repro.core.records import COMM_DTYPE, EVENT_DTYPE, STATE_DTYPE, Trace, sort_trace
from repro.core.tracer import Tracer
from repro.train.step import pick_microbatches


# ----------------------------------------------------------------------
# tracer invariants
# ----------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 2**40)), max_size=50))
def test_tracer_preserves_all_events(pairs):
    tracer = Tracer().init()
    for code_off, val in pairs:
        tracer.emit(ev.USER_EVENT_BASE + code_off, val)
    trace = tracer.finish()
    user = trace.events[trace.events["type"] >= ev.USER_EVENT_BASE]
    assert len(user) == len(pairs)  # no event is ever dropped
    # multiset of (type, value) preserved
    got = sorted((int(t), int(v)) for t, v in zip(user["type"], user["value"]))
    want = sorted((ev.USER_EVENT_BASE + c, v) for c, v in pairs)
    assert got == want
    assert np.all(np.diff(trace.events["time"]) >= 0)  # sorted timeline


@settings(max_examples=20, deadline=None)
@given(st.lists(st.sampled_from(sorted(ev.STATE_LABELS)), min_size=1, max_size=8))
def test_state_nesting_is_well_formed(stack_states):
    tracer = Tracer().init()

    def nest(states):
        if not states:
            return
        with tracer.state(states[0]):
            nest(states[1:])

    nest(stack_states)
    trace = tracer.finish()
    st_ = trace.states
    assert np.all(st_["end"] >= st_["begin"])
    # total state-time of thread 0 == makespan (states partition the timeline)
    t0 = st_[(st_["task"] == 0) & (st_["thread"] == 0)]
    covered = int((t0["end"] - t0["begin"]).sum())
    assert abs(covered - trace.t_end) <= len(t0) + 1  # rounding slack


# ----------------------------------------------------------------------
# analysis conservation laws
# ----------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_bandwidth_conserves_bytes_and_connectivity_counts(data):
    n = data.draw(st.integers(2, 6))
    t_end = 1_000_000
    msgs = data.draw(st.lists(
        st.tuples(st.integers(0, n - 1), st.integers(0, n - 1),
                  st.integers(0, t_end - 2), st.integers(1, 2**24)),
        min_size=1, max_size=30))
    comms = []
    for src, dst, t0, size in msgs:
        t1 = data.draw(st.integers(t0 + 1, t_end))
        comms.append((src, 0, dst, 0, t0, t0, t1, t1, size, 0))
    trace = sort_trace(Trace(
        app_name="p", num_tasks=n, threads_per_task=[1] * n,
        node_of_task=list(range(n)),
        states=np.empty(0, STATE_DTYPE), events=np.empty(0, EVENT_DTYPE),
        comms=np.array(comms, COMM_DTYPE), event_types={}, t_end=t_end,
    ))
    counts, sizes = connectivity(trace)
    assert counts.sum() == len(msgs)
    assert sizes.sum() == sum(m[3] for m in msgs)
    centers, series, peak = bandwidth_timeline(trace, buckets=50, by="task")
    width = centers[1] - centers[0]
    total = series.sum() * width / 1e9 * 1e6
    assert abs(total - sizes.sum()) / max(sizes.sum(), 1) < 0.05


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_time_fractions_bounded_and_complete(data):
    """Non-overlapping routine intervals => per-task fractions in [0,1] and
    their sum <= 1."""
    t_end = 1_000_000
    tracer = Tracer().init()
    base = tracer.t0
    cursor = 0
    n_int = data.draw(st.integers(1, 12))
    for _ in range(n_int):
        gap = data.draw(st.integers(0, 20_000))
        dur = data.draw(st.integers(1, 50_000))
        if cursor + gap + dur >= t_end:
            break
        val = data.draw(st.sampled_from(list(ev.COLL_IDS.values())))
        tracer.inject_event(0, 0, base + cursor + gap, ev.EV_COLLECTIVE, val)
        tracer.inject_event(0, 0, base + cursor + gap + dur, ev.EV_COLLECTIVE, 0)
        cursor += gap + dur
    trace = tracer.finish()
    trace.t_end = t_end
    fr = time_fractions(trace, ev.EV_COLLECTIVE)
    total = sum(v["mean"] * trace.num_tasks for v in fr.values())
    for v in fr.values():
        assert 0.0 <= v["mean"] <= 1.0 + 1e-9
    assert total <= 1.0 + 1e-6


# ----------------------------------------------------------------------
# collective cost model invariants
# ----------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(kind=st.sampled_from(["all-reduce", "all-gather", "reduce-scatter", "all-to-all"]),
       group=st.integers(1, 512), bytes_=st.integers(1, 2**32))
def test_wire_bytes_bounds(kind, group, bytes_):
    if kind == "all-gather":
        op = CollectiveOp("x", kind, bytes_ * group, bytes_, group, 1)
    elif kind == "reduce-scatter":
        op = CollectiveOp("x", kind, bytes_, bytes_ * group, group, 1)
    else:
        op = CollectiveOp("x", kind, bytes_, bytes_, group, 1)
    w = op.wire_bytes_per_device()
    assert w >= 0
    factor = 2.0 if kind == "all-reduce" else 1.0
    assert w <= factor * op.operand_bytes * (1 if kind != "all-gather" else group)
    if group == 1:
        assert w == 0.0  # single-participant collectives move nothing


# ----------------------------------------------------------------------
# microbatch picker
# ----------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(b_log=st.integers(0, 10), dp_log=st.integers(0, 6), desired=st.integers(1, 64))
def test_pick_microbatches_invariants(b_log, dp_log, desired):
    b, dp = 2 ** b_log, 2 ** dp_log
    m = pick_microbatches(b, dp, desired)
    assert 1 <= m <= max(desired, 1)
    assert b % m == 0
    if (b // m) % dp != 0:
        # only allowed when even m=1 cannot satisfy dp-divisibility
        assert b % dp != 0


# ----------------------------------------------------------------------
# paged span attention vs a dense float64 oracle
# ----------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_paged_span_attend_matches_dense_oracle(data):
    """The unified/spec engines' span primitive over ragged row_len, span
    widths, window masks, and NULL-block table padding: scatter-then-gather
    through per-row block tables must equal dense causal attention over the
    row's logical [W*bs] cache view (float64 reference; padded queries are
    garbage by contract and excluded)."""
    import types

    import jax.numpy as jnp

    from repro.models.attention import _paged_span_attend
    from repro.serve.block_pool import NULL_BLOCK

    rng_seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(rng_seed)
    b = data.draw(st.integers(1, 3))
    bs = data.draw(st.sampled_from([2, 4]))
    w = data.draw(st.integers(2, 3))
    q_width = data.draw(st.integers(1, 5))
    kh, g, d = 2, 2, 4
    window = data.draw(st.sampled_from([None, 3, 5]))
    nb = 1 + b * w  # block 0 is NULL
    cap = w * bs

    row_start = np.zeros(b, np.int32)
    row_len = np.zeros(b, np.int32)
    real_w = np.zeros(b, np.int64)
    tables = np.full((b, w), NULL_BLOCK, np.int32)
    for i in range(b):
        # a span never outgrows its slot's capacity (w blocks of bs)
        row_len[i] = data.draw(st.integers(0, min(q_width, cap)))
        hi = cap - int(row_len[i])
        row_start[i] = data.draw(st.integers(0, hi))
        end = int(row_start[i]) + int(row_len[i])
        # enough real blocks to hold the span; the rest stay NULL padding
        lo_w = -(-end // bs) if end else 1
        real_w[i] = data.draw(st.integers(max(lo_w, 1), w))
        tables[i, :real_w[i]] = 1 + i * w + np.arange(real_w[i])

    pool_k = rng.standard_normal((nb, bs, kh, d)).astype(np.float32)
    pool_v = rng.standard_normal((nb, bs, kh, d)).astype(np.float32)
    q = rng.standard_normal((b, q_width, kh * g, d)).astype(np.float32)
    k_new = rng.standard_normal((b, q_width, kh, d)).astype(np.float32)
    v_new = rng.standard_normal((b, q_width, kh, d)).astype(np.float32)
    positions = row_start[:, None] + np.arange(q_width, dtype=np.int32)[None]

    cfg = types.SimpleNamespace(kernel_mode="xla", kv_dtype="fp16")
    out, new_cache = _paged_span_attend(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        {"k": jnp.asarray(pool_k), "v": jnp.asarray(pool_v)},
        jnp.asarray(row_start), jnp.asarray(row_len), jnp.asarray(positions),
        jnp.asarray(tables), window, cfg)
    out = np.asarray(out)

    # ---- reference: scatter in numpy, then dense masked attention ----
    ref_k, ref_v = pool_k.copy(), pool_v.copy()
    for i in range(b):
        for j in range(int(row_len[i])):
            pos = int(row_start[i]) + j
            blk = int(tables[i, pos // bs])
            ref_k[blk, pos % bs] = k_new[i, j]
            ref_v[blk, pos % bs] = v_new[i, j]
    # real blocks hold exactly the oracle's scatter; the NULL block absorbs
    # padding-column scribbles by design and is excluded
    np.testing.assert_allclose(np.asarray(new_cache["k"])[1:], ref_k[1:],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_cache["v"])[1:], ref_v[1:],
                               rtol=1e-6)

    from repro.kernels.attention import dense_ref

    for i in range(b):
        if not int(row_len[i]):
            continue
        kg = ref_k[tables[i]].reshape(cap, kh, d)
        vg = ref_v[tables[i]].reshape(cap, kh, d)
        n = int(row_len[i])
        expect = dense_ref(
            q[i:i + 1, :n], kg[None], vg[None],
            positions[i:i + 1, :n], np.arange(cap, dtype=np.int32),
            causal=True, window=window)
        np.testing.assert_allclose(
            out[i, :n], expect[0], rtol=2e-4, atol=2e-5,
            err_msg=f"row {i} (seed {rng_seed})")


@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_kernel_fallback_never_changes_numerics(data):
    """Dispatch is an implementation detail: the span primitive under
    ``kernel_mode="pallas"`` (interpret-mode kernel) and ``"xla"`` (gather)
    must agree to float tolerance, and a greedy argmax over a fixed random
    projection of the outputs must be IDENTICAL whenever the top-2 margin
    is non-degenerate — i.e. the fallback can never flip a served token."""
    import types

    import jax.numpy as jnp

    from repro.models.attention import _paged_span_attend
    from repro.serve.block_pool import NULL_BLOCK

    rng_seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(rng_seed)
    b = data.draw(st.integers(1, 2))
    bs, w, q_width = 4, 3, data.draw(st.integers(1, 4))
    kh, g, d = 2, 2, 8  # head_dim % 8 == 0 so pallas is eligible
    window = data.draw(st.sampled_from([None, 5]))
    nb = 1 + b * w
    cap = w * bs

    row_start = np.zeros(b, np.int32)
    row_len = np.zeros(b, np.int32)
    tables = np.full((b, w), NULL_BLOCK, np.int32)
    for i in range(b):
        row_len[i] = data.draw(st.integers(1, q_width))
        row_start[i] = data.draw(st.integers(0, cap - int(row_len[i])))
        end = int(row_start[i]) + int(row_len[i])
        real_w = data.draw(st.integers(-(-end // bs), w))
        tables[i, :real_w] = 1 + i * w + np.arange(real_w)

    pool_k = rng.standard_normal((nb, bs, kh, d)).astype(np.float32)
    pool_v = rng.standard_normal((nb, bs, kh, d)).astype(np.float32)
    q = rng.standard_normal((b, q_width, kh * g, d)).astype(np.float32)
    k_new = rng.standard_normal((b, q_width, kh, d)).astype(np.float32)
    v_new = rng.standard_normal((b, q_width, kh, d)).astype(np.float32)
    positions = row_start[:, None] + np.arange(q_width, dtype=np.int32)[None]

    outs = {}
    for mode in ("xla", "pallas"):
        cfg = types.SimpleNamespace(kernel_mode=mode, kv_dtype="fp16")
        o, _ = _paged_span_attend(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            {"k": jnp.asarray(pool_k), "v": jnp.asarray(pool_v)},
            jnp.asarray(row_start), jnp.asarray(row_len),
            jnp.asarray(positions), jnp.asarray(tables), window, cfg)
        outs[mode] = np.asarray(o)

    valid = np.arange(q_width)[None, :] < row_len[:, None]
    a = np.where(valid[..., None, None], outs["xla"], 0.0)
    p = np.where(valid[..., None, None], outs["pallas"], 0.0)
    np.testing.assert_allclose(p, a, rtol=2e-5, atol=2e-5,
                               err_msg=f"seed {rng_seed}")

    # greedy stability: project onto a fixed random "unembedding" and
    # require identical argmax wherever the decision isn't a coin flip
    proj = np.random.default_rng(0).standard_normal(
        (kh * g * d, 64)).astype(np.float32)
    la = a.reshape(b, q_width, -1) @ proj
    lp = p.reshape(b, q_width, -1) @ proj
    top2 = np.sort(la, axis=-1)[..., -2:]
    margin_ok = (top2[..., 1] - top2[..., 0]) > 1e-4
    same = la.argmax(-1) == lp.argmax(-1)
    assert np.all(same | ~(margin_ok & valid)), f"seed {rng_seed}"


# ----------------------------------------------------------------------
# block pool: fork / free / evict interleavings
# ----------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_block_pool_fork_free_evict_interleavings(data):
    """Random interleavings of alloc / fork / cow / free / register / claim
    against a reference model of holders (one ref per block per holder).

    Invariants after EVERY operation:
      * exact refcount conservation — ``pool.ref(b)`` equals the number of
        model holders referencing ``b`` (implies shared blocks are never
        evicted out from under a holder);
      * FREE/ACTIVE/CACHED partition the pool (``check_invariants``);
      * ``num_shared`` counts exactly the blocks with >= 2 holders;
      * ``cow`` copies IFF the block is shared — a privately held block is
        never spuriously copied, a shared one is never written in place.
    """
    import collections as _c

    from repro.serve.block_pool import BlockPool

    nb = data.draw(st.integers(4, 12))
    pool = BlockPool(nb, block_size=4)
    holders: list[list[int]] = []
    next_hash = [1]  # synthetic chain hashes for register/claim

    def check():
        want = _c.Counter(b for hold in holders for b in hold)
        for b in range(1, nb):
            assert pool.ref(b) == want[b], (b, want)
        assert pool.num_shared() == sum(1 for v in want.values() if v > 1)
        assert pool.num_active() == len(want)
        pool.check_invariants()

    n_ops = data.draw(st.integers(1, 40))
    for _ in range(n_ops):
        op = data.draw(st.sampled_from(
            ["alloc", "fork", "cow", "free", "register", "claim"]))
        if op == "alloc":
            n = data.draw(st.integers(1, 3))
            if n > pool.available():
                with pytest.raises(MemoryError):
                    pool.alloc(n)
            else:
                holders.append(pool.alloc(n))
        elif op == "fork" and holders:
            parent = holders[data.draw(st.integers(0, len(holders) - 1))]
            forks0 = pool.stats["forks"]
            child = pool.fork(parent)
            assert child == list(parent)  # aliases, never copies
            assert pool.stats["forks"] == forks0 + 1
            holders.append(list(child))
        elif op == "cow" and holders:
            hold = holders[data.draw(st.integers(0, len(holders) - 1))]
            if not hold:
                continue
            j = data.draw(st.integers(0, len(hold) - 1))
            bid = hold[j]
            shared = pool.ref(bid) > 1
            if shared and pool.available() == 0:
                with pytest.raises(MemoryError):
                    pool.cow(bid)
            else:
                copies0 = pool.stats["cow_copies"]
                new, copied = pool.cow(bid)
                assert copied == shared  # copy IFF shared
                if copied:
                    assert new != bid and pool.ref(new) == 1
                    hold[j] = new
                    assert pool.stats["cow_copies"] == copies0 + 1
                else:
                    assert new == bid
        elif op == "free" and holders:
            hold = holders.pop(data.draw(st.integers(0, len(holders) - 1)))
            pool.free(hold)
        elif op == "register" and holders:
            hold = holders[data.draw(st.integers(0, len(holders) - 1))]
            if not hold:
                continue
            bid = hold[data.draw(st.integers(0, len(hold) - 1))]
            pool.register(bid, next_hash[0])
            next_hash[0] += 1
        elif op == "claim":
            cached = [(h, b) for h, b in zip(pool.resident_hashes(),
                                             map(pool.resident,
                                                 pool.resident_hashes()))
                      if pool.ref(b) >= 0 and pool._hash_of[b] is not None]
            if cached:
                _, bid = cached[data.draw(st.integers(0, len(cached) - 1))]
                pool.claim([bid])
                holders.append([bid])
        check()

    # drain: every holder releases; the pool must conserve exactly
    for hold in holders:
        pool.free(hold)
    holders.clear()
    check()
    assert pool.num_active() == 0
    assert pool.num_free() + pool.num_cached() == nb - 1
    # a drained block cannot be double-freed
    if nb > 1:
        with pytest.raises(ValueError):
            pool.free([1])
