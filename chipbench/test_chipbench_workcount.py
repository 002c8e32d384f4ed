"""Work counts against hand counts for both configurations' shapes."""
import json
import pathlib

import pytest

import seedweights
import workcount

HERE = pathlib.Path(__file__).resolve().parent


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# per layer: q and o 4096x4096 each, k and v 4096 x (kv_heads*128), three
# 4096 x d_ff MLP matrices; plus the 4096 x vocab LM head
HAND = {
    "granite-8b-l18": dict(
        layer=2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336,
        layers=18, head=4096 * 49152, embed=49152 * 4096, kv=2 * 8 * 128 * 2),
    "yi-9b-l24": dict(
        layer=2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008,
        layers=24, head=4096 * 64000, embed=64000 * 4096, kv=2 * 4 * 128 * 2),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_flops_and_bytes_per_token(name):
    c, h = cfg(name), HAND[name]
    params = h["layers"] * h["layer"] + h["head"]
    assert workcount.matmul_params(c) == params
    # bf16 matrices and embedding, float32 norm gains (two per layer + final)
    nbytes = 2 * (params + h["embed"]) + 4 * 4096 * (2 * h["layers"] + 1)
    assert seedweights.param_bytes(c) == nbytes
    assert workcount.weight_bytes(c) == nbytes - 2 * h["embed"]
    assert workcount.kv_bytes_per_position(c) == h["kv"]
    # one token attending 1000 positions: 2 FLOPs per weight, and
    # QK^T + PV = 4 * heads * head_dim per layer per position
    att = 4 * 32 * 128 * h["layers"] * 1000
    assert workcount.decode_flops(c, 1, 1000) == 2 * params + att
    # one step of 4 tokens at 1000 positions each
    assert workcount.decode_bytes(c, 1, 4000) == (
        workcount.weight_bytes(c) + h["kv"] * h["layers"] * 4000)
    qo = 2 * 32 * 128 * 2
    assert workcount.paged_decode_bytes(c, 4000, 4) == h["layers"] * (
        h["kv"] * 4000 + qo * 4)


def test_kv_per_token_and_weights_match_the_stated_sizes():
    # the sizes the cells were planned with: 72 KiB and 48 KiB per token,
    # 8.66 GB and 9.35 GB of weights
    assert workcount.kv_bytes_per_position(cfg("granite-8b-l18")) * 18 == 72 * 1024
    assert workcount.kv_bytes_per_position(cfg("yi-9b-l24")) * 24 == 48 * 1024
    assert round(seedweights.param_bytes(cfg("granite-8b-l18")) / 1e9, 2) == 8.66
    assert round(seedweights.param_bytes(cfg("yi-9b-l24")) / 1e9, 2) == 9.35


def test_sum_contexts():
    # tokens after a context of 10: they attend 11, 12, 13
    assert workcount.sum_contexts(10, 3) == 11 + 12 + 13
    assert workcount.sum_contexts(5, 0) == 0
