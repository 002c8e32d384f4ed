"""The check that decides ``correct``, driven through a whole run at a small
size on the CPU (the harness's look for a chip skipped): the program passes,
the control (the reference in float8) fails the same limit, and a run whose
tokens are altered where they are produced comes out not correct."""
import pytest

import harness
import tinycell

WL = "yi-9b-l24.decode-4k"
# The configurations' limits hold at published widths, where the program
# (bfloat16) reads widest gaps up to ~0.08 and the control 0.4-0.8.  At
# this test's size (2 layers of width 128) the program reads ~0.01 and the
# control 0.2-0.3 over some hundreds of tokens; 0.1 lies between them, as
# the chip limits lie between the chip readings.
LIMIT = 0.1


def _run(files, seed, fault=None, control=0):
    return harness.main(["--workload", WL, "--seed", str(seed), "--seconds",
                         "2", "--trace", "0", "--control", str(control)],
                        require_tpu=False, files=files, fault=fault)


def _files():
    f = tinycell.files(WL, dtype="bfloat16", limit=LIMIT)
    f["mix"]["check"] = {"tokens": 800, "max_total": 8192, "max_seqs": 16}
    return f


def test_program_passes_and_control_fails():
    res = _run(_files(), 2 ** 35 + 1, control=1)
    assert res["correct"], res["checks"]
    assert res["control_correct"] is False, res["checks"]
    gap = res["checks"]["control_max_logit_gap"]["value"]
    assert gap > LIMIT, f"control gap {gap} within the limit {LIMIT}"
    assert list(res)[-1] == "checks"
    assert res["metrics"]["decode_tok_s"]["value"] > 0


@pytest.fixture
def altered_tokens(monkeypatch):
    """A token altered where it is produced: every sampled token + 1."""
    import repro.serve.engine as eng
    import repro.serve.step as stp

    orig = eng.sample_logits

    def bad(logits, key, *a, **k):
        return (orig(logits, key, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(eng, "sample_logits", bad)
    monkeypatch.setattr(stp, "sample_logits", bad)


def test_altered_tokens_are_not_correct(altered_tokens):
    res = _run(_files(), 5)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT
