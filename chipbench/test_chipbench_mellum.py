"""The Mellum2 MoE architecture (``arch/mellum_moe.py``) at small sizes on
the CPU: the program's serve steps against the float32 reference through
prefill chunks that cross the window and paged decode past it, the whole
small cell run through the harness, the draws, the program's widths and
the work counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cellspec
import harness
import refmodel
import seedweights
import tinycell
import workcount

SEED = 2 ** 40 + 5
NAME, CELL = "mellum2-l8", "mellum2-l8.decode-8k"
ARCH = cellspec.arch("mellum_moe")


def _program(c, **change):
    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config(c["arch"]).replace(num_layers=c["num_hidden_layers"],
                                        **c["program"]).replace(**change)
    return build_model(cfg)


def _reference_logits(c, tok):
    h = refmodel.hidden(SEED, c, tok[None], qblk=32)
    return np.asarray(jnp.matmul(h[0], refmodel.lm_head(SEED, c),
                                 precision=jax.lax.Precision.HIGHEST))


# The published program, then programs that each leave out part of the
# mathematics: capacity buffers at the published factor 1.25 (tokens
# dropped), plain RoPE on the full layer, no window, a window on the full
# layer too.
CHANGES = {
    "published": {},
    "drops-tokens": {"moe_impl": "sort", "capacity_factor": 1.25},
    "no-yarn": {"rope_yarn": None},
    "no-window": {"attention_window": None},
    "all-sliding": {"block_pattern": ("moe_sliding",) * 4},
}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_serve_steps_match_the_reference_logits(change):
    """The unified step's two halves, as the engine calls them: the span
    step over a 40-token prompt in chunks of 8 (the 16-position window is
    crossed from the third chunk on), then 24 paged decode steps through the
    block table, teacher-forced.  Every position's logits against the
    reference's.  Tolerance 1e-4: both are float32 on the CPU and differ in
    summation order only (2.9e-6 measured, on logits of standard deviation
    near 1); each change that leaves out part of the mathematics is off by
    0.77 or more."""
    c = tinycell.config(NAME)
    model = _program(c, **CHANGES[change])
    params = seedweights.program_params(SEED, c, model.abstract_params())
    tok = np.random.default_rng(3).integers(0, c["vocab_size"], 64)
    tok = tok.astype(np.int32)
    bs, prompt = 8, 40
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          model.paged_cache_specs(1, 10, bs))
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]  # block 0 is the NULL one
    span, decode = jax.jit(model.span_step), jax.jit(model.decode_step)
    out = []
    for s in range(0, prompt, 8):
        caches, lg = span(params, caches, jnp.asarray(tok[None, s:s + 8]),
                          jnp.array([s], jnp.int32), jnp.array([8], jnp.int32),
                          table)
        out.append(np.asarray(lg[0]))
    for p in range(prompt, len(tok)):
        caches, lg = decode(params, caches, jnp.asarray(tok[p:p + 1]),
                            jnp.array([p], jnp.int32), block_tables=table)
        out.append(np.asarray(lg))
    got = np.concatenate(out)[:, :c["vocab_size"]]
    err = float(np.abs(got - _reference_logits(c, tok)).max())
    if change == "published":
        assert err < 1e-4, err
    else:
        assert err > 0.5, err


def test_the_small_cell_is_correct_and_the_reference_tells_kinds_apart(
        monkeypatch):
    """The whole cell at the small size through the harness, unified
    engine and all: correct at the limit 0.05, and on the same served
    tokens the reference with plain RoPE on the full layer, or with no
    window, reads a gap over it."""
    limit = 0.05
    files = tinycell.files(CELL, limit=limit)
    files["mix"]["check"] = {"tokens": 400, "max_total": 4096, "max_seqs": 8}
    window = files["config"]["sliding_window"]
    calls = []
    real = refmodel.served_gaps

    def spy(*a, **k):
        calls.append((a, k))
        return real(*a, **k)

    monkeypatch.setattr(refmodel, "served_gaps", spy)
    res = harness.main(["--workload", CELL, "--seed", str(2 ** 33 + 11),
                        "--seconds", "2", "--trace", "0"],
                       require_tpu=False, files=files)
    assert res["correct"], res["checks"]
    (seed, c, seqs, starts), kw = calls[0]
    assert min(map(len, seqs)) > 2 * window  # decoded past the window
    rope = {t: dict(r) for t, r in c["rope_parameters"].items()}
    rope["full_attention"] = dict(rope["sliding_attention"])
    for other in (dict(c, rope_parameters=rope),
                  dict(c, sliding_window=10 ** 6)):
        gaps = real(seed, other, seqs, starts, **kw)["gap"]
        assert max(gaps) > limit, max(gaps)


def test_draws_follow_the_param_map():
    """Sub-layer j of unit u is layer 4u + j; its expert leaves hold every
    expert by published index, as the reference draws them."""
    c = tinycell.config(NAME)
    c["num_hidden_layers"] = 8
    c["param_map"] = ARCH.param_map(c)
    model = _program(c)
    p = seedweights.program_params(SEED, c, model.abstract_params())
    units = p["stack"]["units"]
    fc = refmodel._frozen(c)
    key = seedweights.base_key(SEED)
    for layer in (1, 7):
        u, j = divmod(layer, 4)
        sub = units[f"sub{j}"]
        w = refmodel._draw(key, layer, c=fc, names=ARCH.LAYER)
        np.testing.assert_array_equal(sub["moe"]["experts"]["w_up"][u],
                                      w["w_up"])
        np.testing.assert_array_equal(sub["moe"]["router"]["w"][u],
                                      w["router"])
        np.testing.assert_array_equal(
            sub["attn"]["wq"]["w"][u].reshape(w["wq"].shape), w["wq"])
    assert seedweights.ids(ARCH)["router"] == seedweights.new_id("router")
    assert seedweights.ids(ARCH)["w_up"] == cellspec.arch("dense_gqa").IDS["w_up"]


def test_the_configuration_file_and_the_program_agree():
    from repro.configs import get_config

    c = cellspec.config(NAME)
    assert c["param_map"] == ARCH.param_map(c)
    cfg = get_config(c["arch"]).replace(num_layers=c["num_hidden_layers"])
    want = ARCH.program(c)
    assert {k: getattr(cfg, k) for k in want} == want
    assert [ARCH.kind(c, i)[:2] for i in range(4)] == (
        [("sliding_attention", 1024)] * 3 + [("full_attention", None)])
    # weights held: 8 layers of 417.7 M parameters and the untied head
    # and embedding, bf16, with float32 norm gains
    assert seedweights.param_bytes(c) == 7_590_011_904
    assert ARCH.shapes(c)["w_gate"] == (2304, 896)
    assert ARCH.experts(c) == {"w_gate": 64, "w_up": 64, "w_down": 64}


def _attended_by_hand(spans, window):
    return sum(min(c0 + k, window or 10 ** 9) for c0, n in spans
               for k in range(1, n + 1))


def test_work_counts_with_and_without_spans():
    c = cellspec.config(NAME)
    spans = [(4000, 30), (1000, 40), (3072, 25)]
    tokens = sum(n for _, n in spans)
    ctx = sum(workcount.sum_contexts(*s) for s in spans)
    for fn, args in ((workcount.decode_flops, (c, tokens, ctx)),
                     (workcount.decode_bytes, (c, 10, ctx)),
                     (workcount.paged_decode_bytes, (c, ctx, tokens)),
                     (workcount.paged_decode_flops, (c, ctx))):
        assert fn(*args) is None  # without spans: nothing to count
    # 6 sliding layers capped at 1024 positions, 2 full layers uncapped
    attended = 6 * _attended_by_hand(spans, 1024) + 2 * ctx
    assert attended < 8 * ctx
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    active = 8 * (attn + 2304 * 64 + 8 * 3 * 2304 * 896) + 2304 * 98304
    assert workcount.matmul_params(c) == active
    assert workcount.decode_flops(c, tokens, ctx, spans) == (
        2 * active * tokens + 4 * 32 * 128 * attended)
    kv = 2 * 4 * 128 * 2
    assert workcount.paged_decode_flops(c, ctx, spans) == 4 * 32 * 128 * attended
    assert workcount.paged_decode_bytes(c, ctx, tokens, spans) == (
        kv * attended + 8 * 2 * 32 * 128 * 2 * tokens)
    # bytes: the weights outside the experts every step, the experts that
    # the step's 9.5 rows route to (64 (1 - (7/8)^9.5) = 46.2 of 64)
    expert = 3 * 2304 * 896 * 2
    held = workcount.weight_bytes(c) - 8 * 64 * expert
    distinct = 64 * (1 - (1 - 8 / 64) ** (tokens / 10))
    assert ARCH.distinct_experts(c, tokens / 10) == pytest.approx(distinct)
    assert workcount.decode_bytes(c, 10, ctx, spans) == pytest.approx(
        10 * (held + 8 * distinct * expert) + kv * attended, rel=1e-12)
    assert ARCH.expert_flops(c, tokens) == 2 * 8 * 8 * 3 * 2304 * 896 * tokens
    assert ARCH.expert_bytes(c, 10, tokens) == pytest.approx(
        8 * (10 * distinct * expert + 2 * tokens * 8 * 2304 * 2), rel=1e-12)
