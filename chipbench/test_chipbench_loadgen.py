"""The traffic generator: deterministic per seed, the same work for every
seed, and the stated distributions."""
import json
import pathlib
import statistics

import numpy as np

import loadgen

HERE = pathlib.Path(__file__).resolve().parent


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


BIG_SEED = 2 ** 33 + 12345  # more than 32 bits: seeds may be that large


def test_normal_quantile_matches_known_values():
    p = np.array([0.025, 0.5, 0.8413447460685429, 0.999])
    want = np.array([-1.959963984540054, 0.0, 1.0, 3.090232306167813])
    assert np.allclose(loadgen._normal_quantile(p), want, atol=1e-8)


def test_open_arrivals_deterministic_and_seed_only_permutes():
    m = mix("chat-poisson")
    seg = (20, 51, 10)
    a = loadgen.open_arrivals(m, BIG_SEED, 1000, seg, rate_rps=2.0)
    b = loadgen.open_arrivals(m, BIG_SEED, 1000, seg, rate_rps=2.0)
    c = loadgen.open_arrivals(m, 7, 1000, seg, rate_rps=2.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: another order of the same sizes and gaps, and the same
    # number of requests due in each segment
    win = lambda rs: [r for r in rs if 20 <= r.due_s < 71]
    assert len(win(a)) == len(win(c)) == 102
    assert sorted(len(x.prompt) for x in win(a)) == sorted(len(x.prompt) for x in win(c))
    assert sorted(x.max_new for x in win(a)) == sorted(x.max_new for x in win(c))
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]


def test_open_arrivals_follow_the_stated_distributions():
    m = mix("chat-poisson")
    reqs = loadgen.open_arrivals(m, 3, 1000, (2000,), rate_rps=4.0)
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    assert len(reqs) == 8000  # Poisson at the stated rate
    assert abs(statistics.mean(gaps) - 0.25) < 0.001
    assert abs(statistics.pstdev(gaps) / statistics.mean(gaps) - 1.0) < 0.05
    plen = [len(r.prompt) for r in reqs]
    olen = [r.max_new for r in reqs]
    assert abs(statistics.median(plen) - 512) <= 8
    assert abs(statistics.median(olen) - 128) <= 3
    assert min(plen) >= 64 and max(plen) <= 3072
    assert min(olen) >= 16 and max(olen) <= 1024
    # log-normal spread: the 84th percentile is median * e^sigma
    assert abs(np.percentile(plen, 84.13) / 512 - np.e) < 0.1


def test_closed_streams_start_with_the_same_work_for_every_seed():
    m = mix("decode-4k")
    c = m["engine"]["num_slots"]
    a = loadgen.closed_streams(m, BIG_SEED, 1000)
    b = loadgen.closed_streams(m, 11, 1000)
    assert sorted(len(x.prompt) for x in a[:c]) == sorted(len(x.prompt) for x in b[:c])
    for r in a:
        assert 1024 <= len(r.prompt) <= 3072
        assert len(r.prompt) + r.max_new == m["context"]
    assert abs(statistics.median(len(x.prompt) for x in a[:c]) - 2048) < 60


def test_sessions_extend_their_histories_with_zipf_popularity():
    m = mix("sessions-sysprompt")
    s = loadgen.sessions(m, 5, 1000, (1000,), rate_rps=2.0)
    assert len(s.histories) == m["sessions"]
    system = s.histories[0][:m["system_prompt"]]
    for h in s.histories:
        assert np.array_equal(h[:m["system_prompt"]], system)
    for t in s.turns:
        h = s.histories[t.session]
        assert np.array_equal(t.prompt[:len(h)], h)
        assert 32 <= len(t.prompt) - len(h) <= 256
        assert len(t.prompt) + t.max_new <= m["engine"]["max_len"] + 1
    counts = np.bincount([t.session for t in s.turns], minlength=m["sessions"])
    top = np.sort(counts)[::-1]
    w = loadgen.zipf_weights(m["sessions"], m["zipf_s"])
    assert abs(top[0] / counts.sum() - w[0]) < 0.02  # the hottest session
    assert top[0] > 5 * np.median(counts)

