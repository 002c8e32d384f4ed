"""The unified attention-kernel family: dispatch rules, autotune cache
round-trip, deprecation shim, and engine-level pallas-vs-XLA greedy
bit-exactness (dispatch must be an implementation detail)."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced
from repro.core import events as ev
from repro.kernels.attention import autotune, dispatch


@pytest.fixture(autouse=True)
def _fresh_tuner(tmp_path, monkeypatch):
    """Every test gets an empty memo + private disk cache and no observer."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "tune.json"))
    monkeypatch.delenv(autotune.SEARCH_ENV, raising=False)
    monkeypatch.delenv(dispatch.MODE_ENV, raising=False)
    autotune.clear_memory()
    autotune.set_observer(None)
    yield
    autotune.clear_memory()
    autotune.set_observer(None)


# ----------------------------------------------------------------------
# dispatch rule table
# ----------------------------------------------------------------------


def _resolve(mode, variant="paged_decode", **kw):
    kw.setdefault("head_dim", 64)
    kw.setdefault("kv_heads", 2)
    kw.setdefault("dtype", "float32")
    kw.setdefault("block_size", 16)
    return dispatch.resolve(mode, variant, **kw)


def test_dispatch_rule_table():
    # mode=xla short-circuits everything
    assert _resolve("xla", platform="tpu").backend == "xla"
    # auto: pallas only where a real Mosaic backend exists
    assert _resolve("auto", platform="tpu").backend == "pallas"
    assert _resolve("auto", platform="cpu").backend == "xla"
    assert "no Mosaic" in _resolve("auto", platform="cpu").reason
    # ... and on TPU an auto-mode fallback is an error, not a quiet XLA run
    assert _resolve("auto", platform="cpu", head_dim=20).backend == "xla"
    with pytest.raises(dispatch.KernelFallbackError, match="head_dim 20"):
        _resolve("auto", platform="tpu", head_dim=20)
    # pallas: forced even off-TPU (interpret mode), but never for
    # unsupported dtype / non-lane-tileable head_dim / vetoed call sites
    assert _resolve("pallas", platform="cpu").backend == "pallas"
    assert _resolve("pallas", platform="tpu", dtype="float64").backend == "xla"
    assert _resolve("pallas", platform="tpu", head_dim=20).backend == "xla"
    d = _resolve("pallas", platform="tpu", supported=False,
                 why="head_dim sharded 2-way")
    assert d.backend == "xla" and "sharded" in d.reason
    # decisions carry the trace-event identity
    assert _resolve("pallas", platform="tpu").event_value == \
        dispatch.KERNEL_VARIANT_IDS["paged_decode:pallas"]
    with pytest.raises(ValueError):
        dispatch.resolve("fast", "dense", head_dim=64, kv_heads=2,
                         dtype="float32")
    with pytest.raises(ValueError):
        _resolve("auto", variant="flash3")


def test_mode_env_override(monkeypatch):
    cfg = get_config("granite-8b")
    assert dispatch.mode_from(cfg) == "auto"
    monkeypatch.setenv(dispatch.MODE_ENV, "xla")
    assert dispatch.mode_from(cfg) == "xla"
    monkeypatch.setenv(dispatch.MODE_ENV, "warp")
    with pytest.raises(ValueError):
        dispatch.mode_from(cfg)


def test_config_zoo_dispatches_pallas_on_tpu():
    """Acceptance: under kernel_mode=auto every dense/MoE config's shapes
    dispatch the Pallas path for every variant when the platform is TPU —
    the kernels are the hot path, not the opt-in path."""
    for name in ARCHS:
        cfg = get_config(name)
        if cfg.family not in ("dense", "moe"):
            continue
        plan = dispatch.engine_plan(cfg, block_size=16, platform="tpu")
        for variant, decision in plan.items():
            assert decision.backend == "pallas", (name, variant, decision)
    # head-dim sharding vetoes it: on TPU in auto mode that is an error
    # naming the reason, never a silent XLA fallback; mode=pallas keeps the
    # per-variant fallback with the reason preserved
    with pytest.raises(dispatch.KernelFallbackError, match="sharded 2-way"):
        dispatch.engine_plan(get_config("granite-8b"), block_size=16,
                             hd_shards=2, platform="tpu")
    plan = dispatch.engine_plan(
        get_config("granite-8b").replace(kernel_mode="pallas"),
        block_size=16, hd_shards=2, platform="tpu")
    assert all(d.backend == "xla" and "sharded" in d.reason
               for d in plan.values())


# ----------------------------------------------------------------------
# autotune persistent cache
# ----------------------------------------------------------------------


def test_autotune_search_persists_and_warm_hits(monkeypatch):
    monkeypatch.setenv(autotune.SEARCH_ENV, "search")
    events = []
    autotune.set_observer(lambda c, v: events.append((c, v)))
    measured = []

    def measure(params):
        measured.append(params)
        return 0.002 if params.get("block_q") == 64 else 0.005

    kw = dict(head_dim=64, kv_heads=2, block_size=16, window=None,
              dtype="float32", platform="cpu")
    params = autotune.params_for("dense", measure=measure, **kw)
    assert params == {"block_q": 64, "block_k": 128}
    assert len(measured) == len(autotune.candidates_for("dense", head_dim=64))
    assert (ev.EV_AUTOTUNE_SEARCH, len(measured)) in events

    # the search result is on disk, keyed by the full shape/config point
    store = json.loads(autotune.cache_path().read_text())
    key = autotune.tune_key("dense", **kw)
    assert store[key]["params"] == params
    assert store[key]["searched"] == len(measured)

    # cold process (memo dropped): reload from disk, NO re-measure
    autotune.clear_memory()
    measured.clear()
    events.clear()
    again = autotune.params_for("dense", measure=measure, **kw)
    assert again == params and measured == []
    assert (ev.EV_AUTOTUNE_HIT, autotune.HIT_WARM) in events

    # a different shape point is a different key -> fresh search
    autotune.params_for("dense", measure=measure, **{**kw, "head_dim": 128})
    assert len(measured) == len(autotune.candidates_for("dense", head_dim=128))


def test_autotune_default_mode_never_searches_or_writes():
    banned = lambda params: pytest.fail("measured without REPRO_AUTOTUNE=search")  # noqa: E731
    events = []
    autotune.set_observer(lambda c, v: events.append((c, v)))
    kw = dict(head_dim=64, kv_heads=2, block_size=16, window=None,
              dtype="float32", platform="cpu")
    for variant in dispatch.VARIANTS:
        params = autotune.params_for(variant, measure=banned, **kw)
        assert params == autotune.default_params(variant)
    assert not autotune.cache_path().exists()
    assert (ev.EV_AUTOTUNE_HIT, autotune.HIT_HEURISTIC) in events


def test_autotune_corrupt_cache_degrades_to_defaults():
    autotune.cache_path().write_text("{not json")
    kw = dict(head_dim=64, kv_heads=2, block_size=16, window=None,
              dtype="float32", platform="cpu")
    assert autotune.params_for("paged_span", **kw) == \
        autotune.default_params("paged_span")


# ----------------------------------------------------------------------
# config shim: deprecated flags map onto kernel_mode
# ----------------------------------------------------------------------


def test_deprecated_flags_map_to_kernel_mode():
    base = reduced(get_config("granite-8b"), num_layers=1)
    with pytest.warns(DeprecationWarning, match="use_paged_kernel"):
        cfg = base.replace(use_paged_kernel=True)
    assert cfg.kernel_mode == "pallas"
    with pytest.warns(DeprecationWarning, match="use_flash_kernel"):
        cfg = base.replace(use_flash_kernel=True)
    assert cfg.kernel_mode == "pallas"
    with pytest.raises(ValueError):
        base.replace(kernel_mode="turbo")


# ----------------------------------------------------------------------
# engine-level: greedy decode is bit-exact across the dispatch boundary
# ----------------------------------------------------------------------


def test_engine_greedy_bit_exact_pallas_vs_xla():
    """Forcing the kernels end-to-end (prefill chunks ride the span path,
    decode the paged kernel, interpret mode on CPU) serves the SAME tokens
    as the XLA gather path, and the engine accounts every dispatch."""
    from repro.models.model import build_model
    from repro.serve.engine import ContinuousServeEngine

    cfg = reduced(get_config("granite-8b"), num_layers=2, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (3, 12)).astype(np.int32)

    outs, engines = {}, {}
    for mode in ("xla", "pallas"):
        eng = ContinuousServeEngine(cfg.replace(kernel_mode=mode), params,
                                    num_slots=3, max_len=48, block_size=16)
        outs[mode] = eng.serve_batch(prompts, num_tokens=6)
        engines[mode] = eng

    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    counts = engines["pallas"].stats["kernel_dispatch"]
    assert counts.get("paged_decode:pallas", 0) > 0, counts
    assert "paged_decode:pallas" not in engines["xla"].stats["kernel_dispatch"]
