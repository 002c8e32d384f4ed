"""MoE unit tests: dispatch implementations agree when drop-free, capacity
dropping behaves, aux loss responds to imbalance."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models.moe import moe_block, moe_decl
from repro.models.params import init_params


def _setup(cf=8.0, impl="einsum", e=8, k=2, seed=0):
    cfg = reduced(get_config("mixtral-8x22b"), num_layers=2).replace(
        capacity_factor=cf, moe_impl=impl, num_experts=e, experts_per_token=k,
        attention_window=None,
    )
    params = init_params(jax.random.PRNGKey(seed), moe_decl(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, cfg.d_model), jnp.float32)
    return cfg, params, x


def test_einsum_and_sort_agree_when_dropfree():
    cfg_e, params, x = _setup(cf=8.0, impl="einsum")
    cfg_s = cfg_e.replace(moe_impl="sort")
    y_e, aux_e = jax.jit(lambda p, v: moe_block(p, v, cfg_e))(params, x)
    y_s, aux_s = jax.jit(lambda p, v: moe_block(p, v, cfg_s))(params, x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_e), float(aux_s), rtol=1e-5)


def test_capacity_dropping_changes_output():
    cfg_hi, params, x = _setup(cf=8.0)
    cfg_lo = cfg_hi.replace(capacity_factor=0.25)  # force drops
    y_hi, _ = moe_block(params, x, cfg_hi)
    y_lo, _ = moe_block(params, x, cfg_lo)
    # dropped tokens fall back to (shared experts only / zero routed path)
    assert not np.allclose(np.asarray(y_hi), np.asarray(y_lo))
    assert np.isfinite(np.asarray(y_lo)).all()


def test_aux_loss_detects_imbalance():
    cfg, params, x = _setup()
    x = jnp.abs(x)  # positive features so a linear router can skew all tokens
    # balanced router ~= uniform: aux approaches 1 (E * sum(f*p) with f=p=1/E)
    params_bal = dict(params)
    params_bal["router"] = {"w": jnp.zeros_like(params["router"]["w"])}
    _, aux_bal = moe_block(params_bal, x, cfg)
    # heavily skewed router (all mass on expert 0): much larger aux
    skew = jnp.zeros_like(params["router"]["w"]).at[:, 0].set(10.0)
    params_skew = dict(params)
    params_skew["router"] = {"w": skew}
    _, aux_skew = moe_block(params_skew, x, cfg)
    assert float(aux_bal) < 1.5
    assert float(aux_skew) > 2.0
    assert float(aux_skew) > 1.5 * float(aux_bal)


def test_shared_experts_always_active():
    cfg = reduced(get_config("deepseek-moe-16b"), num_layers=2).replace(
        capacity_factor=0.01)  # routed path drops almost everything
    params = init_params(jax.random.PRNGKey(0), moe_decl(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model), jnp.float32)
    y, _ = moe_block(params, x, cfg)
    assert float(jnp.abs(y).mean()) > 0  # shared experts still contribute


def _dropless(k=1, seed=0):
    cfg = reduced(get_config("mellum2-12b"))
    assert cfg.moe_impl == "dropless"
    cfg = cfg.replace(experts_per_token=k)
    params = init_params(jax.random.PRNGKey(seed), moe_decl(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, cfg.d_model),
                          jnp.float32)
    return cfg, params, x


def test_dropless_is_exact_when_every_token_routes_to_one_expert():
    """A zero router ties every expert; top-1 then sends all 32 tokens to
    expert 0 with gate 1.  The dropless path gives each of them that
    expert's SwiGLU (float32, to rounding); the capacity path at the
    published capacity factor 1.25 keeps 32 * 1/8 * 1.25 = 5 of them."""
    cfg, params, x = _dropless(k=1)
    params = dict(params, router={"w": jnp.zeros_like(params["router"]["w"])})
    y, _ = moe_block(params, x, cfg)
    ex = {n: w[0] for n, w in params["experts"].items()}
    want = (jax.nn.silu(x @ ex["w_gate"]) * (x @ ex["w_up"])) @ ex["w_down"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    y_cap, _ = moe_block(params, x, cfg.replace(moe_impl="sort",
                                                capacity_factor=1.25))
    kept = np.abs(np.asarray(y_cap)).sum(-1) > 0
    assert kept.sum() == 5


def test_dropless_matches_the_capacity_paths_when_nothing_drops():
    cfg, params, x = _dropless(k=2)
    y, aux = jax.jit(lambda p, v: moe_block(p, v, cfg))(params, x)
    for impl in ("einsum", "sort"):
        other = cfg.replace(moe_impl=impl, capacity_factor=8.0)
        y_o, aux_o = jax.jit(lambda p, v: moe_block(p, v, other))(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_o), rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(float(aux), float(aux_o), rtol=1e-5)
