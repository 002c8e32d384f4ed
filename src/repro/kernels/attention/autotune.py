"""Per-(shape, config) autotune layer for the attention-kernel family.

AttentionEngine-style policy search, scoped to what actually matters on
TPU for these kernels: the block/grid/VMEM-tiling parameters
(``block_q``/``block_k`` for dense flash, ``block_q`` over the folded
query dim for the ragged span kernel; paged decode derives its tiling
from the shapes it is given — table entries per grid step from the block
size and table width, every kv head in one step — so its candidate set
is the trivial one).

Two layers:

* an in-process memo (``_memory``) so a long serve run resolves each
  (variant, shape) once;
* a persistent JSON cache on disk, keyed by
  ``v2|{variant}|hd{head_dim}|kh{kv_heads}|bs{block_size}|w{window}|{dtype}|{kv_dtype}|{platform}``
  so the *second run* of any config reloads tuned parameters instead of
  re-searching.  (v2 added the KV storage dtype: int8/fp8 pools fuse
  dequant into the kernels, so they must not share tuned tilings with
  fp16 pools.  v1 entries in an old cache file simply never match — the
  lookup degrades to heuristics/search, never to a wrong reuse.)  Location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
  ``~/.cache/repro/attention_autotune.json``.  Writes are atomic
  (tmp + rename) so concurrent runs can share one cache file.

Search is opt-in via ``REPRO_AUTOTUNE=search`` (it compiles and times
every candidate — cheap on TPU, dominated by compile time in interpret
mode).  Without it, resolution uses previously-persisted parameters when
present and static heuristics otherwise, and never writes the cache.

Tuner activity is observable in the merged ``.prv`` through
EV_AUTOTUNE_SEARCH / EV_AUTOTUNE_HIT (see ``core/events.py``); the
engines subscribe a tracer via :func:`set_observer`.
"""
from __future__ import annotations

import json
import os
import pathlib
import tempfile
import time
from typing import Callable

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
SEARCH_ENV = "REPRO_AUTOTUNE"
KEY_VERSION = 2

# EV_AUTOTUNE_HIT values (mirrored in core/events.py labels)
HIT_WARM = 1       # persisted search result reused (no re-search)
HIT_HEURISTIC = 2  # static default parameters (no search requested)

_memory: dict[str, dict] = {}
_observer: Callable[[int, int], None] | None = None


def set_observer(fn: Callable[[int, int], None] | None) -> None:
    """Subscribe ``fn(event_code, value)`` to autotune/dispatch events
    (the engines pass ``tracer.emit``)."""
    global _observer
    _observer = fn


def notify(code: int, value: int) -> None:
    if _observer is not None:
        _observer(code, value)


def cache_path() -> pathlib.Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "attention_autotune.json"


def tune_key(variant: str, *, head_dim: int, kv_heads: int, block_size: int,
             window: int | None, dtype: str, platform: str,
             kv_dtype: str = "fp16") -> str:
    w = "none" if window is None else str(window)
    return (f"v{KEY_VERSION}|{variant}|hd{head_dim}|kh{kv_heads}"
            f"|bs{block_size}|w{w}|{dtype}|{kv_dtype}|{platform}")


def clear_memory() -> None:
    """Drop the in-process memo (test hook; disk cache is untouched)."""
    _memory.clear()


def _load_disk() -> dict:
    path = cache_path()
    try:
        with open(path) as fh:
            data = json.load(fh)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _persist(key: str, entry: dict) -> None:
    path = cache_path()
    store = _load_disk()  # merge with concurrent writers' entries
    store[key] = entry
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        with os.fdopen(fd, "w") as fh:
            json.dump(store, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is an optimization; never fail the run over it


def candidates_for(variant: str, *, head_dim: int) -> list[dict]:
    if variant == "dense":
        return [
            {"block_q": 128, "block_k": 128},
            {"block_q": 64, "block_k": 128},
            {"block_q": 128, "block_k": 256},
            {"block_q": 256, "block_k": 256},
        ]
    if variant == "paged_span":
        # tiles over the folded Q*G dim; None = one tile (no extra grid axis)
        return [{"block_q": None}, {"block_q": 16}, {"block_q": 64}]
    return [{}]  # paged_decode: tiling follows the shapes (ops.py)


def default_params(variant: str) -> dict:
    """Static heuristics used when no search was requested/persisted."""
    if variant == "dense":
        return {"block_q": 128, "block_k": 128}
    if variant == "paged_span":
        return {"block_q": None}
    return {}


def _measure_default(variant: str, *, head_dim: int, kv_heads: int,
                     block_size: int, window: int | None, dtype: str,
                     kv_dtype: str = "fp16"):
    """Build a measure closure over synthetic inputs at serve-like scale.

    Concrete (non-traced) arrays execute eagerly, so this works even when
    resolution happens inside a jit trace.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention import ops

    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(0)

    if variant == "dense":
        b, s, d = 1, 256, head_dim
        q = jax.random.normal(key, (b, s, kv_heads, d), dt)

        def measure(params: dict) -> float:
            fn = lambda: ops.flash_attention(  # noqa: E731
                q, q, q, causal=True, window=window, **params)
            fn().block_until_ready()  # compile
            t0 = time.perf_counter()
            fn().block_until_ready()
            return time.perf_counter() - t0
        return measure

    bs = max(block_size, 1)
    nb, w, d = 16, 4, head_dim
    kp = jax.random.normal(key, (nb, bs, kv_heads, d), dt)
    if kv_dtype != "fp16":
        # time what will actually run: a quantized pool with scale leaves
        from repro.core import quant

        qv, sc = quant.kv_quantize(kp, kv_dtype)
        cache = {"k": qv, "v": qv, "k_scale": sc, "v_scale": sc}
    else:
        cache = {"k": kp, "v": kp}
    bt = jnp.tile(jnp.arange(1, w + 1, dtype=jnp.int32), (2, 1))

    if variant == "paged_span":
        qlen = 32
        q = jax.random.normal(key, (2, qlen, kv_heads, d), dt)
        st = jnp.zeros((2,), jnp.int32)
        ln = jnp.full((2,), qlen, jnp.int32)

        def measure(params: dict) -> float:
            fn = lambda: ops.paged_span_attention(  # noqa: E731
                cache, q, bt, st, ln, window=window, **params)
            fn().block_until_ready()
            t0 = time.perf_counter()
            fn().block_until_ready()
            return time.perf_counter() - t0
        return measure

    q = jax.random.normal(key, (2, 1, kv_heads, d), dt)
    idx = jnp.full((2,), w * bs - 1, jnp.int32)

    def measure(params: dict) -> float:
        fn = lambda: ops.paged_attention(  # noqa: E731
            cache, q, bt, idx, window=window, **params)
        fn().block_until_ready()
        t0 = time.perf_counter()
        fn().block_until_ready()
        return time.perf_counter() - t0
    return measure


def params_for(variant: str, *, head_dim: int, kv_heads: int,
               block_size: int, window: int | None, dtype: str,
               platform: str,
               measure: Callable[[dict], float] | None = None,
               kv_dtype: str = "fp16") -> dict:
    """Tuned kernel parameters for one (variant, shape, platform) point.

    Lookup order: in-process memo -> disk cache -> (search if
    ``REPRO_AUTOTUNE=search``, else static heuristics).  ``measure`` is
    injectable for tests; it maps a candidate params dict to seconds.
    """
    from repro.core import events as ev

    key = tune_key(variant, head_dim=head_dim, kv_heads=kv_heads,
                   block_size=block_size, window=window, dtype=dtype,
                   platform=platform, kv_dtype=kv_dtype)
    search = os.environ.get(SEARCH_ENV, "") == "search"

    entry = _memory.get(key)
    if entry is None:
        disk = _load_disk().get(key)
        if isinstance(disk, dict) and "params" in disk:
            entry = disk
            _memory[key] = entry
    if entry is not None and (entry.get("searched", 0) > 0 or not search):
        notify(ev.EV_AUTOTUNE_HIT,
               HIT_WARM if entry.get("searched", 0) > 0 else HIT_HEURISTIC)
        return dict(entry["params"])

    if not search:
        params = default_params(variant)
        _memory[key] = {"params": params, "searched": 0}
        notify(ev.EV_AUTOTUNE_HIT, HIT_HEURISTIC)
        return dict(params)

    cands = candidates_for(variant, head_dim=head_dim)
    if measure is None:
        measure = _measure_default(variant, head_dim=head_dim,
                                   kv_heads=kv_heads, block_size=block_size,
                                   window=window, dtype=dtype,
                                   kv_dtype=kv_dtype)
    timed = [(measure(dict(c)), i) for i, c in enumerate(cands)]
    best_t, best_i = min(timed)
    entry = {
        "params": dict(cands[best_i]),
        "searched": len(cands),
        "best_us": round(best_t * 1e6, 1),
    }
    _memory[key] = entry
    _persist(key, entry)
    notify(ev.EV_AUTOTUNE_SEARCH, len(cands))
    return dict(entry["params"])
