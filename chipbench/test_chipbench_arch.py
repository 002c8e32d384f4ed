"""Architectures as files: the draws, the reference and the work counts of
the dense GQA configurations read as they did before the architecture
moved into ``arch/dense_gqa.py``, and an architecture that a test brings
as a new file (a sliding window on every layer) runs a whole cell and
is told apart from the dense reference."""
import hashlib
import json
import shutil

import jax
import numpy as np
import pytest

import cellspec
import harness
import refmodel
import seedweights
import tinycell
import workcount

SEED = 2 ** 40 + 3
# sha256 (first 16 hex digits) of (dtype, shape) and the bytes of every
# leaf program_params draws for SEED, taken at commit
# 80e22642495da0a977d6f55b0754e6b4658360b7, before the move.  Both tiny
# configurations have the same shapes and so the same draws.
DIGESTS = {
    "float32": {
        "embed/embedding": "e72802ae031b7925",
        "embed/lm_head": "5787d125ca7f7697",
        "final_norm/scale": "bdb58f9f70412af9",
        "stack/units/attn/wk/w": "0e8dd85edcf6d256",
        "stack/units/attn/wo/w": "59771abc70387e4f",
        "stack/units/attn/wq/w": "f5156dd78e0ff0f1",
        "stack/units/attn/wv/w": "696770f0f4c4fbcb",
        "stack/units/ln1/scale": "0d1a16f1af04d160",
        "stack/units/ln2/scale": "d468c00bfdd57177",
        "stack/units/mlp/w_down/w": "6600d0f057e74d46",
        "stack/units/mlp/w_gate/w": "e781d02588865d35",
        "stack/units/mlp/w_up/w": "59ac19223cbfa89d",
    },
    "bfloat16": {
        "embed/embedding": "b575b9fddd76e43e",
        "embed/lm_head": "f21157eb3d8fa47a",
        "final_norm/scale": "bdb58f9f70412af9",
        "stack/units/attn/wk/w": "bd806a5953a31472",
        "stack/units/attn/wo/w": "5f05a6469f47555d",
        "stack/units/attn/wq/w": "a5a3afc8e0d94b51",
        "stack/units/attn/wv/w": "5208b466758970ab",
        "stack/units/ln1/scale": "0d1a16f1af04d160",
        "stack/units/ln2/scale": "d468c00bfdd57177",
        "stack/units/mlp/w_down/w": "35e532b3cbe6fcc8",
        "stack/units/mlp/w_gate/w": "49e15eaf706016ce",
        "stack/units/mlp/w_up/w": "fa8c0457d55361c2",
    },
}
# every work count at (tokens 120, steps 8, ctx_sum 300000), same commit
WORK = {
    "yi-9b-l24": dict(
        matmul_params=4414504960,
        weight_bytes=8829812736,
        kv_bytes_per_position=2048,
        attn_flops_per_position=16384,
        decode_flops=1177445990400,
        decode_bytes=85384101888,
        paged_decode_bytes=14792785920,
        paged_decode_flops=117964800000,
        param_bytes=9354100736,
    ),
    "granite-8b-l18": dict(
        matmul_params=4127195136,
        weight_bytes=8254996480,
        kv_bytes_per_position=4096,
        attn_flops_per_position=16384,
        decode_flops=1079000432640,
        decode_bytes=88158371840,
        paged_decode_bytes=22153789440,
        paged_decode_flops=88473600000,
        param_bytes=8657649664,
    ),
}


def _program(c):
    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config(c["arch"]).replace(num_layers=c["num_hidden_layers"],
                                        **c["program"])
    return build_model(cfg)


def _digest(x):
    a = np.asarray(x)
    return hashlib.sha256(str((a.dtype, a.shape)).encode()
                          + a.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["yi-9b-l24", "granite-8b-l18"])
def test_draws_did_not_move(name, dtype):
    c = tinycell.config(name, dtype)
    params = seedweights.program_params(
        SEED, c, _program(c).abstract_params())
    got = {seedweights._path(kp): _digest(x) for kp, x in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == DIGESTS[dtype]


@pytest.mark.parametrize("name", sorted(WORK))
def test_work_counts_did_not_move(name):
    c = cellspec.config(name)
    got = dict(
        matmul_params=workcount.matmul_params(c),
        weight_bytes=workcount.weight_bytes(c),
        kv_bytes_per_position=workcount.kv_bytes_per_position(c),
        attn_flops_per_position=workcount.attn_flops_per_position(c),
        decode_flops=workcount.decode_flops(c, 120, 300_000),
        decode_bytes=workcount.decode_bytes(c, 8, 300_000),
        paged_decode_bytes=workcount.paged_decode_bytes(c, 300_000, 120),
        paged_decode_flops=workcount.paged_decode_flops(c, 300_000),
        param_bytes=seedweights.param_bytes(c))
    assert got == WORK[name]
    assert all(type(v) is int for v in got.values())


def test_layer_ranges_and_expert_shares_draw_what_the_whole_holds():
    """A leaf that holds some layers, or a chip's share of experts, draws
    the values that the whole stack holds at those indices; an index is
    folded in only when given."""
    c = tinycell.config("yi-9b-l24")
    c["num_hidden_layers"] = 4
    d, ff = c["hidden_size"], c["intermediate_size"]
    c["param_map"] = {
        "all": "layers.w_up",
        "odd": {"name": "w_up", "layers": [1, 4, 2]},
        "experts": {"name": "w_up", "layers": [0, 4], "experts": [0, 8]},
        "share": {"name": "w_up", "layers": [1, 4, 2], "experts": [2, 4]},
        "head": {"name": "lm_head"}}
    f32 = lambda *s: jax.ShapeDtypeStruct(s, np.float32)
    tree = {"all": f32(4, d, ff), "odd": f32(2, d, ff),
            "experts": f32(4, 8, d, ff), "share": f32(2, 2, d, ff),
            "head": f32(d, c["vocab_size"])}
    p = jax.tree.map(np.asarray, seedweights.program_params(7, c, tree))
    np.testing.assert_array_equal(p["odd"], p["all"][1::2])
    np.testing.assert_array_equal(p["share"], p["experts"][1::2, 2:4])
    assert not np.array_equal(p["experts"][:, 0], p["all"])
    np.testing.assert_array_equal(p["head"], refmodel.lm_head(7, c))


def test_draw_ids_keep_to_the_rule():
    dense = cellspec.arch("dense_gqa")
    ids = seedweights.ids(dense)
    assert sorted(ids.values()) == list(range(12))
    assert seedweights.new_id("router") >= 2 ** 31

    class Bad:
        IDS = {"router": 12}

    with pytest.raises(ValueError, match="new_id"):
        seedweights.ids(Bad)
    Bad.IDS = {"router": seedweights.new_id("router"), "wq": 5}
    with pytest.raises(ValueError, match="new_id"):
        seedweights.ids(Bad)
    Bad.IDS["wq"] = 4
    assert seedweights.ids(Bad)["router"] == seedweights.new_id("router")


@pytest.mark.parametrize("reference", [None, "dense_moe"])
def test_an_unknown_or_missing_architecture_names_the_known_ones(reference):
    c = cellspec.config("yi-9b-l24")
    c.pop("reference")
    if reference:
        c["reference"] = reference
    with pytest.raises(SystemExit, match=r"known: \['dense_gqa'\]"):
        workcount.matmul_params(c)


# The program's sliding window (``attention_window``, docs/paged_cache.md):
# the query at position p attends the positions in (p - W, p].
WINDOWED = '''"""Dense GQA with a sliding window of ``sliding_window`` positions
on every layer."""
import jax
import jax.numpy as jnp
import numpy as np

import cellspec
import refmodel as rm

base = cellspec.arch("dense_gqa")
KEYS = base.KEYS + ("sliding_window",)
IDS, shapes, experts, kind, tensors = (base.IDS, base.shapes, base.experts,
                                       base.kind, base.tensors)
tiny = base.tiny
matmul_params, kv_bytes_per_position, attn_flops_per_position = (
    base.matmul_params, base.kv_bytes_per_position,
    base.attn_flops_per_position)


def program(c):
    return dict(base.program(c), attention_window=c["sliding_window"])


def _attend(q, k, v, window):
    L, kh, g, d = q.shape
    t = jnp.arange(L)
    live = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=rm.HI) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=rm.HI)


def forward(w, x, pos, kind, *, c, fp8, qblk):
    z = base.dims(c)
    B, L, _ = x.shape
    h = rm._rms(x, w["attn_norm"], z["eps"])
    q = rm._mm(h, w["wq"], fp8).reshape(B, L, z["h"], z["hd"])
    k = rm._mm(h, w["wk"], fp8).reshape(B, L, z["kh"], z["hd"])
    v = rm._mm(h, w["wv"], fp8).reshape(B, L, z["kh"], z["hd"])
    q, k = base._rope(q, pos, z["theta"]), base._rope(k, pos, z["theta"])
    o = jax.vmap(lambda a, b, cc: _attend(a, b, cc, c["sliding_window"]))(
        q.reshape(B, L, z["kh"], z["h"] // z["kh"], z["hd"]), k, v)
    x = x + rm._mm(o.reshape(B, L, z["h"] * z["hd"]), w["wo"], fp8)
    h = rm._rms(x, w["mlp_norm"], z["eps"])
    a = jax.nn.silu(rm._mm(h, w["w_gate"], fp8)) * rm._mm(h, w["w_up"], fp8)
    return x + rm._mm(a, w["w_down"], fp8)


def _attended(c, spans):
    w = c["sliding_window"]
    return sum(min(c0 + k, w) for c0, n in spans for k in range(1, n + 1))


def decode_flops(c, tokens, ctx_sum, spans=None):
    return base.decode_flops(c, tokens, _attended(c, spans))
'''


def test_a_new_architecture_enters_as_files_only(tmp_path, monkeypatch):
    """A window architecture brought as a file: the cell runs and proves
    correct against it, and the dense reference, on the same served
    tokens, reads a gap over the limit."""
    shutil.copy(cellspec.ARCH / "dense_gqa.py", tmp_path)
    (tmp_path / "dense_window.py").write_text(WINDOWED)
    monkeypatch.setattr(cellspec, "ARCH", tmp_path)
    window, limit, wl = 16, 0.05, "yi-9b-l24.decode-4k"
    files = tinycell.files(wl, limit=limit)
    c = files["config"]
    c.update(reference="dense_window", sliding_window=window)
    c["program"]["attention_window"] = window
    files["mix"]["check"] = {"tokens": 400, "max_total": 4096, "max_seqs": 8}
    calls = []
    real = refmodel.served_gaps

    def spy(*a, **k):
        calls.append((a, k))
        return real(*a, **k)

    monkeypatch.setattr(refmodel, "served_gaps", spy)
    res = harness.main(["--workload", wl, "--seed", str(2 ** 33 + 11),
                        "--seconds", "2", "--trace", "0"],
                       require_tpu=False, files=files)
    assert res["correct"], res["checks"]
    (seed, used, seqs, starts), kw = calls[0]
    assert used["reference"] == "dense_window"
    assert min(map(len, seqs)) > 2 * window  # contexts past the window
    dense = real(seed, dict(used, reference="dense_gqa"), seqs, starts, **kw)
    assert max(dense["gap"]) > limit, max(dense["gap"])
    # the work counts ask the configuration's architecture
    assert workcount.decode_flops(c, 3, 63, spans=[(18, 3)]) == (
        workcount.decode_flops(dict(c, reference="dense_gqa"), 3, 48))
