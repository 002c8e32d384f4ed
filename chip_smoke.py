#!/usr/bin/env python3
"""Chip smoke: serve granite-8b at published widths on a TPU, and check it.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded path on four chips

One chip: the serve CLI (``repro.launch.serve``, unified engine) serves 8
requests (prompt 512, gen 64, 8 slots) of granite-8b at published widths,
cut to 24 of its 36 layers so the bf16 weights (11.3 GB) fit next to the
KV pool.  The run must plan and dispatch the Pallas ``paged_decode`` and
``paged_span`` kernels.  The same CLI then serves the same requests with
``--kernel-mode xla`` on the same weights (seeded), and both must give the
same greedy tokens for each request's first 8 positions.  Last, the
first-step logits of both kernel paths (``model.span_step`` on two
prompts) must agree within a bf16 tolerance.

Four chips: full-depth granite-8b (36 layers, 16.5 GB of bf16 weights,
more than one chip holds) served with ``--mp 4``; then the 24-layer config
at ``--mp 4`` against the same config on one chip, first 8 greedy tokens
per request equal.

Every phase that fails exits non-zero.  With no TPU visible, or outside a
checkout of the repo, the script exits non-zero and prints no result.  The
last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "granite-8b"
CUT_LAYERS = 24
FULL_LAYERS = 36
REQUESTS, PROMPT, GEN, SLOTS = 8, 512, 64, 8
CHECK_POSITIONS = 8
# first-step logits, Pallas vs XLA: the two paths round attention
# probabilities differently (f32 in the kernel, bf16 before the PV matmul
# on XLA); bf16 keeps 8 mantissa bits, so each of the 24 layers may move
# the residual stream by ~2^-9 of its scale.  Bound the worst logit by
# 8% of the logits' spread (standard deviation) on the reference path.
LOGIT_TOL = 0.08


class SmokeError(RuntimeError):
    pass


def _check(ok: bool, msg: str):
    if not ok:
        raise SmokeError(msg)


def _serve_args(layers: int, *extra: str) -> list[str]:
    return ["--arch", ARCH, "--layers", str(layers),
            "--requests", str(REQUESTS), "--prompt-len", str(PROMPT),
            "--gen", str(GEN), "--slots", str(SLOTS), *extra]


class _CompileClock:
    """Seconds JAX spends compiling for the backend, from its own events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _serve(label: str, argv: list[str], clock: _CompileClock) -> dict:
    import jax

    from repro.launch import serve

    held = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    print(f"[smoke] {label}: {held / 1e9:.2f} GB in use before; serve "
          f"{' '.join(argv)}", flush=True)
    c0, t0 = clock.seconds, time.perf_counter()
    report = serve.run(argv)
    wall = time.perf_counter() - t0
    stats = report["stats"]
    mem = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] {label}: {wall:.1f}s wall, "
          f"{clock.seconds - c0:.1f}s compiling, {stats['tokens']} tokens at "
          f"{stats['tok_per_s']:.1f} tok/s (engine loop, compiles included), "
          f"peak {mem.get('peak_bytes_in_use', 0) / 1e9:.2f} GB of "
          f"{mem.get('bytes_limit', 0) / 1e9:.2f} GB on device 0; "
          f"plan {report['plan']}; dispatches "
          f"{stats.get('kernel_dispatch', {})}", flush=True)
    outs = report["outputs"]
    _check(len(outs) == REQUESTS, f"{label}: {len(outs)} of {REQUESTS} "
                                  f"requests finished")
    vocab = report["cfg"].vocab_size
    for i, o in enumerate(outs):
        _check(len(o) == GEN and int(o.min()) >= 0 and int(o.max()) < vocab,
               f"{label}: request {i} returned {len(o)} tokens in "
               f"[{o.min()}, {o.max()}]")
    return report


def _check_pallas(label: str, report: dict):
    plan, counts = report["plan"], report["stats"]["kernel_dispatch"]
    for variant in ("paged_decode", "paged_span"):
        _check(plan.get(variant) == "pallas",
               f"{label}: {variant} planned on {plan.get(variant)}")
        _check(counts.get(f"{variant}:pallas", 0) > 0,
               f"{label}: no Pallas {variant} dispatch counted ({counts})")


def _same_prefix(label: str, a: dict, b: dict):
    """Greedy tokens of the first CHECK_POSITIONS positions, per request."""
    bad = [i for i, (x, y) in enumerate(zip(a["outputs"], b["outputs"]))
           if list(x[:CHECK_POSITIONS]) != list(y[:CHECK_POSITIONS])]
    for i in bad:
        print(f"[smoke] {label}: request {i}: "
              f"{list(a['outputs'][i][:CHECK_POSITIONS])} vs "
              f"{list(b['outputs'][i][:CHECK_POSITIONS])}", flush=True)
    _check(not bad, f"{label}: greedy tokens differ in the first "
                    f"{CHECK_POSITIONS} positions of requests {bad}")
    print(f"[smoke] {label}: first {CHECK_POSITIONS} greedy tokens equal "
          f"for all {len(a['outputs'])} requests", flush=True)


def _first_step_logits(layers: int):
    """Logits of two 32-token prompts through ``model.span_step``, on the
    Pallas and the XLA attention paths, same seeded weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config(ARCH).replace(num_layers=layers)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rows, qlen, bs = 2, 32, 16
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, qlen)), jnp.int32)
    nb = rows * qlen // bs
    tables = jnp.arange(1, nb + 1, dtype=jnp.int32).reshape(rows, -1)
    start = jnp.zeros((rows,), jnp.int32)
    length = jnp.full((rows,), qlen, jnp.int32)
    out = {}
    for mode in ("auto", "xla"):
        model = build_model(cfg.replace(kernel_mode=mode))
        pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            model.paged_cache_specs(rows, nb + 1, bs))
        _, logits = jax.jit(model.span_step)(params, pool, tokens, start,
                                             length, tables)
        out[mode] = np.asarray(logits, np.float32)
    return out["auto"], out["xla"]


def _check_logits(pallas, xla):
    import numpy as np

    _check(bool(np.isfinite(pallas).all() and np.isfinite(xla).all()),
           "first-step logits are not finite")
    spread = float(xla.std())
    worst = float(np.abs(pallas - xla).max())
    top1 = float((pallas.argmax(-1) == xla.argmax(-1)).mean())
    print(f"[smoke] first-step logits, Pallas vs XLA: max |diff| {worst:.4f}"
          f" = {worst / spread:.4f} of the logit std {spread:.4f} (limit "
          f"{LOGIT_TOL}); top-1 agreement {top1:.3f} over "
          f"{pallas.shape[0] * pallas.shape[1]} positions", flush=True)
    _check(worst <= LOGIT_TOL * spread,
           f"first-step logits differ by {worst:.4f} > {LOGIT_TOL} x "
           f"{spread:.4f}")


def _one_chip(clock: _CompileClock):
    pallas = _serve("pallas", _serve_args(CUT_LAYERS), clock)
    _check_pallas("pallas", pallas)
    gc.collect()  # the engine's weights go before the next run's arrive
    xla = _serve("xla", _serve_args(CUT_LAYERS, "--kernel-mode", "xla"),
                 clock)
    _check(set(xla["plan"].values()) == {"xla"},
           f"xla run planned {xla['plan']}")
    _same_prefix("pallas vs xla", pallas, xla)
    del pallas, xla
    gc.collect()
    _check_logits(*_first_step_logits(CUT_LAYERS))


def _four_chips(clock: _CompileClock):
    full = _serve("full depth mp=4", _serve_args(FULL_LAYERS, "--mp", "4"),
                  clock)
    _check_pallas("full depth mp=4", full)
    del full
    gc.collect()
    mp4 = _serve("cut depth mp=4", _serve_args(CUT_LAYERS, "--mp", "4"), clock)
    _check_pallas("cut depth mp=4", mp4)
    gc.collect()
    one = _serve("cut depth one chip", _serve_args(CUT_LAYERS), clock)
    _same_prefix("mp=4 vs one chip", mp4, one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[smoke] FAILED: {ROOT} is not a checkout of the repo "
              f"(no src/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.cache import use_compile_cache

    devices = jax.devices()
    d0, want = devices[0], 4 if args.four_chips else 1
    if d0.platform != "tpu" or len(devices) < want:
        print(f"[smoke] FAILED: need {want} TPU chip(s), JAX sees "
              f"{len(devices)} {d0.platform} device(s)", file=sys.stderr)
        return 2
    print(f"[smoke] device: {d0.platform} {d0.device_kind} x{len(devices)}; "
          f"compile cache {use_compile_cache()}", flush=True)
    clock = _CompileClock()
    t0 = time.perf_counter()
    try:
        (_four_chips if args.four_chips else _one_chip)(clock)
    except SmokeError as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] passed in {time.perf_counter() - t0:.1f}s "
          f"({clock.seconds:.1f}s compiling)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
