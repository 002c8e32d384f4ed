"""The float32 reference against the program's own forward, at the
program's reduced() widths on the CPU, on weights drawn from one seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import refmodel
import seedweights
import tinycell


@pytest.mark.parametrize("name", ["granite-8b-l18", "yi-9b-l24"])
def test_reference_matches_program_logits(name):
    from repro.configs import get_config
    from repro.models.model import build_model

    c = tinycell.config(name)
    cfg = get_config(c["arch"]).replace(num_layers=c["num_hidden_layers"],
                                        **c["program"])
    model = build_model(cfg)
    seed = 2 ** 40 + 3
    params = seedweights.program_params(seed, c, model.abstract_params())
    tok = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 64))
    logits = model.forward(params, {"tokens": jnp.asarray(tok, jnp.int32)},
                           mode="train")[0]
    h = refmodel.hidden(seed, c, tok.astype(np.int32), qblk=32)
    ref = jnp.matmul(h, refmodel.lm_head(seed, c),
                     precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(logits - ref)))
    assert err < 1e-4 * float(jnp.std(ref)) * 100, err
    assert np.array_equal(np.argmax(logits, -1), np.argmax(ref, -1))


def test_served_gaps_are_zero_for_the_reference_own_greedy_tokens():
    c = tinycell.config("granite-8b-l18")
    seed = 17
    prompt = np.random.default_rng(1).integers(0, c["vocab_size"], 40)
    seq = list(prompt)
    for _ in range(6):  # greedy continuation by the reference itself
        h = refmodel.hidden(seed, c, np.asarray([seq + [0] * (64 - len(seq))],
                                                np.int32), qblk=32)
        lg = jnp.matmul(h[0, len(seq) - 1], refmodel.lm_head(seed, c),
                        precision=jax.lax.Precision.HIGHEST)
        seq.append(int(jnp.argmax(lg)))
    out = refmodel.served_gaps(seed, c, [np.asarray(seq, np.int32)], [40],
                               control=True, qblk=32)
    assert len(out["gap"]) == 6 and max(out["gap"]) == 0.0
    assert len(out["control_gap"]) == 6 and min(out["control_gap"]) >= 0.0
    # an altered token lies below the best by a positive gap
    bad = list(seq)
    bad[-1] = (bad[-1] + 1) % c["vocab_size"]
    alt = refmodel.served_gaps(seed, c, [np.asarray(bad, np.int32)], [40],
                               qblk=32)
    assert alt["gap"][-1] > 0.0
