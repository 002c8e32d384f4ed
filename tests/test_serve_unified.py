"""Unified token-budget serve step: greedy-decode equivalence against the
legacy two-path engine (full / SWA / GQA / MoE / hybrid / encdec), the
chunked-prefill x prefix-cache x preemption-resume three-way interaction,
budget invariants straight off the trace counters, and the chunk/decode
interleave the tentpole promises."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import events as ev
from repro.core.tracer import Tracer
from repro.models.model import build_model
from repro.serve.engine import ContinuousServeEngine
from repro.serve.step import UnifiedServeEngine

_CACHE = {}


def _setup(arch, **kw):
    key = (arch, tuple(sorted(kw.items())))
    if key not in _CACHE:
        cfg = reduced(get_config(arch), num_layers=2, **kw)
        model = build_model(cfg)
        _CACHE[key] = (cfg, model.init(jax.random.PRNGKey(0)))
    return _CACHE[key]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32) for L in lens]


def _extras(cfg, n, seed=1):
    rng = np.random.default_rng(seed)
    ex = {}
    if cfg.family == "vlm":
        ex["patch_embeds"] = rng.standard_normal(
            (n, cfg.num_patches, cfg.vision_dim)).astype(np.float32)
    if cfg.family == "encdec":
        ex["frames"] = rng.standard_normal(
            (n, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    return ex


# ----------------------------------------------------------------------
# oracle equivalence: unified step == legacy two-path engine, bit for bit
# ----------------------------------------------------------------------
@pytest.mark.parametrize("arch,kw,what", [
    ("granite-8b", {}, "full attention + GQA, chunked"),
    ("granite-8b", {"attention_window": 12}, "dense + SWA, chunked"),
    ("yi-9b", {}, "full attention + GQA 4:1, chunked"),
    ("mixtral-8x22b", {}, "SWA + GQA + MoE, chunked"),
    ("recurrentgemma-9b", {}, "hybrid, whole-prompt admission"),
    ("whisper-small", {}, "encdec, whole-prompt admission"),
])
def test_unified_matches_legacy_oracle(arch, kw, what):
    """Variable lengths crossing chunk AND block boundaries; chunk_size 8
    forces multi-chunk streaming for every prompt >= 9 tokens."""
    cfg, params = _setup(arch, **kw)
    lens = [7, 16, 21, 30]
    prompts = _prompts(cfg, lens, seed=2)
    exs = _extras(cfg, len(lens))
    legacy = ContinuousServeEngine(cfg, params, num_slots=2, max_len=64,
                                   block_size=16)
    rl = [legacy.submit(p, 8, extras={k: v[i] for k, v in exs.items()})
          for i, p in enumerate(prompts)]
    out_l = legacy.run()
    uni = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                             block_size=16, chunk_size=8)
    ru = [uni.submit(p, 8, extras={k: v[i] for k, v in exs.items()})
          for i, p in enumerate(prompts)]
    out_u = uni.run()
    for a, b in zip(rl, ru):
        np.testing.assert_array_equal(out_l[a.rid], out_u[b.rid], err_msg=what)
    expect_chunked = cfg.family in ("dense", "moe")
    assert uni.chunkable == expect_chunked, what


def test_budget_and_interleave_visible_in_trace():
    """The per-iteration EV_STEP_BUDGET/EV_CHUNK_TOKENS/EV_DECODE_TOKENS
    triple (a) never exceeds max_step_tokens and (b) shows at least one
    iteration carrying BOTH chunk and decode tokens — a long prompt
    streaming in while an earlier request keeps decoding, the interleave
    the legacy engine cannot produce."""
    cfg, params = _setup("granite-8b")
    tracer = Tracer("serve-unified-budget").init()
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=96,
                             block_size=16, chunk_size=8,
                             max_step_tokens=10, tracer=tracer)
    short, long_ = _prompts(cfg, [5, 60], seed=3)
    r_short = eng.submit(short, 24)
    r_long = eng.submit(long_, 4)
    out = eng.run()
    trace = tracer.finish()
    assert len(out[r_short.rid]) == 24 and len(out[r_long.rid]) == 4
    evs = trace.events
    by = {code: evs[evs["type"] == code]["value"]
          for code in (ev.EV_STEP_BUDGET, ev.EV_CHUNK_TOKENS,
                       ev.EV_DECODE_TOKENS)}
    assert len(by[ev.EV_STEP_BUDGET]) > 0
    assert (by[ev.EV_STEP_BUDGET] <= eng.max_step_tokens).all()
    np.testing.assert_array_equal(
        by[ev.EV_STEP_BUDGET],
        by[ev.EV_CHUNK_TOKENS] + by[ev.EV_DECODE_TOKENS])
    mixed = (by[ev.EV_CHUNK_TOKENS] > 0) & (by[ev.EV_DECODE_TOKENS] > 0)
    assert mixed.any(), "no iteration interleaved chunk prefill with decode"
    # the 60-token prompt must have streamed in over several 8-token chunks
    assert (by[ev.EV_CHUNK_TOKENS] > 0).sum() >= 8


def test_counter_triple_cadence_for_whole_prompt_families():
    """Non-chunkable configs fold their whole-prompt prefill tokens into
    the next dispatch's triple: same cadence for all three counters and
    STEP_BUDGET == CHUNK + DECODE at every sample (regression: the
    whole-prefill path used to emit a lone EV_CHUNK_TOKENS, misaligning
    the arrays)."""
    cfg, params = _setup("recurrentgemma-9b")
    tracer = Tracer("serve-whole-budget").init()
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=48,
                             block_size=16, tracer=tracer)
    assert not eng.chunkable
    for p in _prompts(cfg, [9, 14, 11], seed=6):
        eng.submit(p, 6)
    eng.run()
    trace = tracer.finish()
    evs = trace.events
    by = {code: evs[evs["type"] == code]["value"]
          for code in (ev.EV_STEP_BUDGET, ev.EV_CHUNK_TOKENS,
                       ev.EV_DECODE_TOKENS)}
    n = len(by[ev.EV_STEP_BUDGET])
    assert n > 0 and all(len(v) == n for v in by.values())
    np.testing.assert_array_equal(
        by[ev.EV_STEP_BUDGET],
        by[ev.EV_CHUNK_TOKENS] + by[ev.EV_DECODE_TOKENS])
    assert int(by[ev.EV_CHUNK_TOKENS].sum()) == 9 + 14 + 11


def test_single_compile_shape_for_diverse_prompt_lengths():
    """Every distinct prompt length mints a grouped-prefill executable on
    the legacy engine; the unified chunk path serves them all from the ONE
    [1, chunk_size] shape (plus decode-burst shapes shared with legacy)."""
    cfg, params = _setup("granite-8b")
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                             block_size=16, chunk_size=8)
    for p in _prompts(cfg, [5, 9, 13, 17, 21, 26], seed=4):
        eng.submit(p, 4)
    eng.run()
    shapes = {s for s in ("prefill", "chunk")
              if getattr(eng, f"_{s}")._cache_size() > 0}
    assert not shapes, f"unified engine used legacy prefill paths: {shapes}"
    # chunk-carrying step shapes + power-of-two decode bursts — bounded by
    # log2(max_decode_burst), NOT by the number of distinct prompt lengths
    # (the legacy engine compiles one prefill executable per length)
    assert eng._unified._cache_size() <= 2 + 4


# ----------------------------------------------------------------------
# chunked prefill x prefix cache x preemption-resume (three-way)
# ----------------------------------------------------------------------
def test_chunked_prefix_preemption_three_way():
    """A preempted request whose prompt blocks stayed resident (CACHED)
    must, on resume, re-hit its own prefix — skipping whole chunks — and
    still produce bit-identical output, with FREE/ACTIVE/CACHED conserved.

    The pool is sized so request A's decode growth drains it while B
    decodes: A is preempted (its registered prompt blocks go ACTIVE ->
    CACHED), B's retirement returns blocks, and A's recompute resume
    resolves its own prompt out of the prefix cache.  This also regression-
    covers the resumed-request position math: scheduled tokens re-prefilled
    into the start position must not be double-counted, or the burst
    clamps to zero steps and the engine livelocks."""
    cfg, params = _setup("granite-8b")
    tracer = Tracer("serve-unified-preempt").init()
    # strict per-iteration stepping (mixed_burst=1, one stream) reproduces
    # the tightest decode-growth schedule — the pool dries mid-decode
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=40,
                             block_size=8, num_blocks=8, chunk_size=8,
                             chunk_rows=1, mixed_burst=1,
                             prefix_cache=True, tracer=tracer)
    prompts = _prompts(cfg, [16, 16], seed=8)
    gens = [24, 8]
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    out = eng.run()
    trace = tracer.finish()
    assert eng.stats["preemptions"] > 0
    # the resumed request re-admitted with a nonzero prefix hit: its own
    # prompt blocks were registered at completion, freed on preemption
    # (ACTIVE -> CACHED), and resolved again on resume
    resumed = [r for r in reqs if r.preemptions > 0]
    assert resumed and all(r.prefix_hit_tokens == 16 for r in resumed)
    hits = trace.events[trace.events["type"] == ev.EV_PREFIX_HIT_TOKENS]
    assert (np.asarray(hits["value"]) > 0).any()
    # bit-identical to uncontended solo runs despite preempt + warm resume
    for r, p, g in zip(reqs, prompts, gens):
        assert len(out[r.rid]) == g
        solo = UnifiedServeEngine(cfg, params, num_slots=1, max_len=40,
                                  block_size=8, chunk_size=8)
        s = solo.submit(p, g)
        np.testing.assert_array_equal(out[r.rid], solo.run()[s.rid],
                                      err_msg=f"req {r.rid}")
    # conservation: every block accounted for, none leaked ACTIVE
    eng.pool.check_invariants()
    assert eng.pool.num_active() == 0
    assert (eng.pool.num_free() + eng.pool.num_cached()
            == eng.pool.num_blocks - 1)


def test_prefix_hits_skip_whole_chunks():
    """Warm == cold bit-for-bit; the hit prefix is never re-streamed (the
    chunk cursor starts at the hit boundary, asserted via token accounting
    and the trace counter)."""
    cfg, params = _setup("granite-8b")
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, (32,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, (6,))
                               .astype(np.int32)]) for _ in range(3)]
    cold = UnifiedServeEngine(cfg, params, num_slots=1, max_len=64,
                              block_size=16, chunk_size=8, prefix_cache=False)
    rc = [cold.submit(p, 6) for p in prompts]
    out_cold = cold.run()
    warm = UnifiedServeEngine(cfg, params, num_slots=1, max_len=64,
                              block_size=16, chunk_size=8, prefix_cache=True)
    rw = [warm.submit(p, 6) for p in prompts]
    out_warm = warm.run()
    for a, b in zip(rc, rw):
        np.testing.assert_array_equal(out_cold[a.rid], out_warm[b.rid])
    assert [r.prefix_hit_tokens for r in rw] == [0, 32, 32]
    assert warm.stats["prefill_tokens"] == cold.stats["prefill_tokens"] - 64


# ----------------------------------------------------------------------
# deterministic sampling: same seed => same tokens, across engines
# ----------------------------------------------------------------------
def test_same_seed_reproducible_at_temperature():
    """temperature>0 decode must be a pure function of (--seed, traffic):
    two fresh engines with the same seed produce identical tokens; a
    different seed diverges somewhere (both unified and legacy engines,
    plus top-k/top-p filters in the loop)."""
    cfg, params = _setup("granite-8b")
    prompts = _prompts(cfg, [9, 20], seed=12)

    def wave(cls, seed):
        eng = cls(cfg, params, num_slots=2, max_len=64, block_size=16,
                  temperature=0.9, top_k=8, top_p=0.95, seed=seed)
        rs = [eng.submit(p, 12) for p in prompts]
        out = eng.run()
        return [out[r.rid] for r in rs]

    for cls in (UnifiedServeEngine, ContinuousServeEngine):
        a, b = wave(cls, seed=5), wave(cls, seed=5)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=cls.__name__)
        c = wave(cls, seed=6)
        assert any(not np.array_equal(x, y) for x, y in zip(a, c)), \
            f"{cls.__name__}: different seeds produced identical streams"


# ----------------------------------------------------------------------
# engine edges
# ----------------------------------------------------------------------
def test_budget_must_cover_decode_slots():
    cfg, params = _setup("granite-8b")
    with pytest.raises(ValueError, match="max_step_tokens"):
        UnifiedServeEngine(cfg, params, num_slots=4, max_len=64,
                           max_step_tokens=3)


def test_exact_capacity_fill_and_slot_reuse():
    """A request filling its cache exactly decodes to completion through
    the chunked path, and slots recycle across waves unchanged."""
    cfg, params = _setup("granite-8b")
    eng = UnifiedServeEngine(cfg, params, num_slots=1, max_len=8,
                             block_size=4, chunk_size=4)
    r = eng.submit(np.arange(3, dtype=np.int32), 6)
    out = eng.run()
    assert len(out[r.rid]) == 6 and eng.pool.num_active() == 0
    wide = UnifiedServeEngine(cfg, params, num_slots=1, max_len=64,
                              block_size=16)
    w = wide.submit(np.arange(3, dtype=np.int32), 6)
    np.testing.assert_array_equal(out[r.rid], wide.run()[w.rid])
    # second wave through the same engine (slot + register reuse)
    r2 = eng.submit(np.arange(3, dtype=np.int32), 6)
    np.testing.assert_array_equal(eng.run()[r2.rid], out[r.rid])


def test_max_new_tokens_one_completes_at_chunk():
    """The first sampled token IS the whole generation: the request must
    retire off the completing chunk without entering decode."""
    cfg, params = _setup("granite-8b")
    eng = UnifiedServeEngine(cfg, params, num_slots=2, max_len=32,
                             block_size=16, chunk_size=8)
    prompts = _prompts(cfg, [5, 17], seed=9)
    reqs = [eng.submit(p, 1) for p in prompts]
    out = eng.run()
    ref = ContinuousServeEngine(cfg, params, num_slots=2, max_len=32,
                                block_size=16)
    rr = [ref.submit(p, 1) for p in prompts]
    out_ref = ref.run()
    for a, b in zip(reqs, rr):
        np.testing.assert_array_equal(out[a.rid], out_ref[b.rid])


def test_mellum_serve_matches_greedy_forward_past_the_window():
    """mellum2-12b's pattern (three windowed layers, then a full YaRN layer;
    dropless experts) through chunked prefill across the window, then
    paged decode past it: every served token is the argmax of the
    teacher-forced forward over the prompt and the tokens served before
    it.  The forward with the window on every layer, or with plain RoPE,
    picks other tokens on the same sequences."""
    cfg = reduced(get_config("mellum2-12b"), attention_window=8,
                  vocab_size=128)
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    prompts = _prompts(cfg, [21, 30], seed=5)
    uni = UnifiedServeEngine(cfg, params, num_slots=2, max_len=64,
                             block_size=16, chunk_size=8)
    reqs = [uni.submit(p, 12) for p in prompts]
    out = uni.run()
    seqs = [np.concatenate([p, out[r.rid]]).astype(np.int32)
            for p, r in zip(prompts, reqs)]

    def agree(c):
        fwd = jax.jit(lambda p, t: build_model(c).forward(
            p, {"tokens": t}, mode="train")[0])
        return [np.array_equal(
            np.asarray(fwd(params, s[None, :-1]))[0, len(p) - 1:].argmax(-1),
            out[r.rid]) for s, p, r in zip(seqs, prompts, reqs)]

    assert agree(cfg) == [True, True]
    for other in (cfg.replace(block_pattern=("moe_sliding",) * 4),
                  cfg.replace(rope_yarn=None)):
        assert not all(agree(other)), other.block_pattern
