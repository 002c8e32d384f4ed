"""HLO cost model: trip-count awareness, dot FLOPs, slice-aware bytes —
synthetic modules + a real compiled scan (vs hand-computed ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_cost import analyze_hlo, computation_multipliers, parse_module

SYNTH = """\
HloModule m

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p.1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p.1), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i.1, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i2, %d)
}

ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%z, %a)
  ROOT %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body
}
"""


def test_synthetic_while_trip_count():
    hc = analyze_hlo(SYNTH)
    # dot: 2 * 8*8 * 8 = 1024 flops, x5 trips (+ tiny add at 1 flop x5)
    assert hc.while_trip_counts == {"w": 5}
    assert hc.flops == pytest.approx(5 * (2 * 8 * 8 * 8) + 5 * 1, rel=0.01)


def test_parse_module_structure():
    comps, entry = parse_module(SYNTH)
    assert entry == "main"
    assert set(comps) == {"cond", "body", "main"}
    mult = computation_multipliers(comps, entry)
    assert mult["body"] == 5
    assert mult["cond"] == 6  # trips + 1 evaluations
    assert mult["main"] == 1


def test_real_scan_vs_ground_truth():
    """Compiled 6-layer scanned matmul: exact dot FLOPs recovered."""
    L, B, D = 6, 4, 32

    def f(params, x):
        def layer(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(layer, x, params)
        return jnp.sum(h)

    params = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    c = jax.jit(jax.grad(f)).lower(params, x).compile()
    hc = analyze_hlo(c.as_text())
    # fwd dot + 2 bwd dots per layer
    dot_flops = 3 * L * 2 * B * D * D
    assert hc.flops == pytest.approx(dot_flops, rel=0.15)  # + elementwise
    # XLA's built-in analysis undercounts by ~L
    xla = (c.cost_analysis() or {}).get("flops", 0)
    assert hc.flops > 3 * xla


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(d, _):
                return jnp.tanh(d @ d), None

            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None

        y, _ = jax.lax.scan(outer, x, None, length=4)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    hc = analyze_hlo(c.as_text())
    assert hc.flops == pytest.approx(12 * 2 * 8 * 8 * 8, rel=0.2)  # 4x3 dots


def test_collectives_scaled_by_trips():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.compat import make_mesh
        from repro.core.hlo_cost import analyze_hlo
        mesh = make_mesh((4,), ("d",))

        def f(ws, x):
            def layer(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(layer, x, ws)
            return jnp.sum(h)

        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "d", None)),
                                     NamedSharding(mesh, P(None, "d")))
                    ).lower(ws, x).compile()
        hc = analyze_hlo(c.as_text(), total_devices=4)
        names = [cc.name for cc in hc.collectives]
        assert any("(x5)" in n for n in names), names  # in-scan collective x trips
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd="/root/repo", timeout=300)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
