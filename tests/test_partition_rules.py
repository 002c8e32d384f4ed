"""Sharding-rule unit tests: divisibility decisions, de-dup, overrides,
per-shape behaviour — no devices needed (pure PartitionSpec logic)."""
from __future__ import annotations

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.sharding.partition import Rules, constrain, make_rules, padded_vocab, use_rules


@pytest.fixture(scope="module")
def mesh():
    # abstract mesh: no devices touched
    return AbstractMesh((16, 16), ("data", "model"))


def test_padded_vocab():
    assert padded_vocab(50280) == 50304
    assert padded_vocab(32768) == 32768
    assert padded_vocab(92553) % 128 == 0
    assert padded_vocab(92553) >= 92553


def test_dense_tp_decisions(mesh):
    cfg = get_config("granite-8b")
    r = make_rules(cfg, mesh, SHAPES["train_4k"])
    assert r.mapping["q_heads"] == "model"      # 32 % 16 == 0
    assert r.mapping["kv_heads"] is None        # 8 % 16 != 0 -> replicated
    assert r.mapping["mlp"] == "model"
    assert r.mapping["embed"] == "data"         # FSDP
    assert r.mapping["act_batch"] == ("data",)  # no pod axis in this mesh


def test_whisper_heads_not_shardable(mesh):
    cfg = get_config("whisper-small")
    r = make_rules(cfg, mesh, SHAPES["train_4k"])
    assert r.mapping["q_heads"] is None  # 12 % 16 != 0
    assert r.mapping["mlp"] == "model"   # 3072 % 16 == 0


def test_moe_ep_vs_tp(mesh):
    deepseek = make_rules(get_config("deepseek-moe-16b"), mesh, SHAPES["train_4k"])
    assert deepseek.mapping["experts"] == "model"      # 64 % 16 == 0 -> EP
    assert deepseek.mapping["expert_mlp"] is None
    mixtral = make_rules(get_config("mixtral-8x22b"), mesh, SHAPES["train_4k"])
    assert mixtral.mapping["experts"] is None          # 8 % 16 != 0
    assert mixtral.mapping["expert_mlp"] == "model"    # TP inside experts


def test_decode_cache_seq_sharding(mesh):
    cfg = get_config("granite-8b")  # kv=8 not shardable 16-way
    dec = make_rules(cfg, mesh, SHAPES["decode_32k"])
    # sequence-dim sharding preferred (head_dim sharding makes XLA gather
    # the whole cache per token — see EXPERIMENTS.md section Perf, cell 2)
    assert dec.mapping["cache_seq"] == "model"
    assert dec.mapping["cache_hd"] is None
    train = make_rules(cfg, mesh, SHAPES["train_4k"])
    assert train.mapping["cache_seq"] is None  # never in training
    # SWA arch: ring capacity (window) is what must divide
    mix = make_rules(get_config("mixtral-8x22b"), mesh, SHAPES["long_500k"])
    assert mix.mapping["cache_seq"] == "model"  # 4096-slot ring % 16 == 0
    # kv-shardable arch keeps kv-head sharding
    dq = make_rules(get_config("codeqwen1.5-7b"), mesh, SHAPES["decode_32k"])
    assert dq.mapping["cache_kv"] == "model" and dq.mapping["cache_seq"] is None


def test_long500k_batch1_not_sharded(mesh):
    cfg = get_config("mamba2-370m")
    r = make_rules(cfg, mesh, SHAPES["long_500k"])
    assert r.mapping["act_batch"] is None  # B=1 cannot shard over 16


def test_pspec_dedup(mesh):
    cfg = get_config("deepseek-moe-16b")
    r = make_rules(cfg, mesh, SHAPES["train_4k"])
    # experts and ff both map to "model": first dim wins, second drops
    assert r.pspec(("act_experts", None, "act_ff")) == P("model", None, None)


def test_overrides_validated(mesh):
    cfg = get_config("granite-8b")
    with pytest.raises(KeyError):
        make_rules(cfg, mesh, SHAPES["train_4k"], overrides={"bogus_axis": "model"})
    r = make_rules(cfg, mesh, SHAPES["train_4k"], overrides={"embed": None})
    assert r.mapping["embed"] is None


def test_multipod_axes():
    mesh3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    cfg = get_config("granite-8b")
    r = make_rules(cfg, mesh3, SHAPES["train_4k"])
    assert r.mapping["act_batch"] == ("pod", "data")
    assert r.pspec(("act_batch", None)) == P(("pod", "data"), None)


def test_constrain_is_noop_without_rules():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert constrain(x, ("act_batch", None)) is x
