"""Rotary embedding per layer kind: YaRN (arXiv:2309.00071) against a direct
numpy evaluation of its formula, the plain path unchanged, and mellum2-12b's
pattern of window and full layers (window and YaRN by kind)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import Yarn
from repro.models import transformer as tf
from repro.models.layers import rope, yarn_inv_freq

MELLUM = get_config("mellum2-12b").rope_yarn


def _yarn_np(d, theta, y):
    """The formula in float64: inv_freq_i = theta^(-2i/D) ((1 - g_i)/s +
    g_i), g_i = 1 - clip((i - low)/(high - low), 0, 1), low = floor(D
    ln(L0/(2 pi b_fast)) / (2 ln theta)), high = ceil(... b_slow ...),
    clamped to [0, D/2 - 1]."""
    at = lambda b: d * math.log(y.original_max_position_embeddings
                                / (2 * math.pi * b)) / (2 * math.log(theta))
    low = min(max(math.floor(at(y.beta_fast)), 0), d // 2 - 1)
    high = min(max(math.ceil(at(y.beta_slow)), 0), d // 2 - 1)
    i = np.arange(d // 2, dtype=np.float64)
    g = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return theta ** (-2.0 * i / d) * ((1.0 - g) / y.factor + g)


# (head dim, theta, yarn): mellum2-12b as published (low 18, high 35), and a
# small one whose low end clamps at 0 (low -2 -> 0, high 3); neither high
# reaches D/2 - 1, where Hugging Face's clamp (D - 1) and D/2 - 1 differ
CASES = {
    "published": (128, 5e5, MELLUM),
    "small": (32, 5e5, MELLUM._replace(original_max_position_embeddings=64)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_yarn_frequencies_follow_the_formula(case):
    d, theta, y = CASES[case]
    # float32 constants: relative rounding 2^-24 on each of theta^x and 1/x
    np.testing.assert_allclose(yarn_inv_freq(d, theta, y),
                               _yarn_np(d, theta, y), rtol=1e-6)


@pytest.mark.parametrize("case", sorted(CASES))
def test_yarn_rotation_follows_the_formula(case):
    d, theta, y = CASES[case]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 48, 2, d)).astype(np.float32)
    pos = np.concatenate([np.arange(24), 8191 - np.arange(24)]).astype(np.int32)
    got = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta, y))
    ang = pos[:, None] * _yarn_np(d, theta, y)[None]  # [S, D/2], float64
    cos = np.cos(ang)[:, None] * y.attention_factor
    sin = np.sin(ang)[:, None] * y.attention_factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # float32 rounding: at the first 24 positions a few ulps of |x| (a few
    # units, times the 1.28 attention factor); near 8191 the float32 angle
    # p * f errs by up to 8192 * 2^-24 * 2 rad (1e-3) on top
    np.testing.assert_allclose(got[:, :24], want[:, :24], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got[:, 24:], want[:, 24:], atol=4e-3, rtol=0)
    plain = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta))
    assert np.abs(got - want).max() < 0.01 * np.abs(plain - want).max()


def test_plain_rope_is_unchanged():
    """Without YaRN the rotation is the one every other model runs, bit for
    bit (the formula as it stood before YaRN)."""
    x = np.random.default_rng(1).standard_normal((2, 16, 4, 128)).astype(
        np.float32)
    pos = jnp.arange(16, dtype=jnp.int32)[None] + jnp.array([[0], [4000]])
    theta = 5e6
    freq = 1.0 / (theta ** (jnp.arange(64, dtype=jnp.float32) / 64))
    ang = pos[..., :, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[..., :, None, :], jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :64], x[..., 64:]
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_array_equal(np.asarray(rope(jnp.asarray(x), pos, theta)),
                                  np.asarray(want))


def test_mellum_layers_take_their_kinds_window_and_rope():
    cfg = get_config("mellum2-12b")
    kinds = tf.unit_kinds(cfg)
    assert kinds == ("moe_sliding",) * 3 + ("moe_full",)
    assert tf.scan_counts(cfg) == (7, 0)
    assert [tf.layer_window(cfg, k) for k in kinds] == [1024] * 3 + [None]
    assert [tf.layer_yarn(cfg, k) for k in kinds] == [None] * 3 + [MELLUM]
    assert MELLUM == Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    # a config without a pattern keeps one window (or none) on every layer
    mix = get_config("mixtral-8x22b")
    assert tf.unit_kinds(mix) == ("moe",)
    assert tf.layer_yarn(mix, "moe") is None
    # the small preset keeps one whole period and the published routing
    small = reduced(cfg)
    assert small.num_layers == 4 and small.moe_impl == "dropless"
    assert small.rope_yarn == MELLUM and small.attention_window == 16
    # a tuple from a configuration file is taken as Yarn's fields
    assert cfg.replace(rope_yarn=tuple(MELLUM)).rope_yarn.factor == 16.0
