"""One traffic generator for every mix: reads a mix file, returns requests.

A mix file (``traffic/<mix>.json``) holds parameters only.  Three kinds:

* ``closed``: ``streams`` concurrent requests; a stream that finishes is
  replaced at once by the next request of the list.  ``max_new_tokens`` is
  ``context - prompt`` (each stream runs to a full context).
* ``open``: Poisson arrivals at ``rate_rps``; each request is due at its
  arrival time whether or not earlier ones have finished.
* ``sessions``: like ``open``, but every request is a further turn of one
  of ``sessions`` conversations.  Each conversation is a shared system
  prompt plus 1..3 earlier turns of synthetic history, prefilled in set-up;
  a turn in the window is the conversation's history plus a new message,
  and conversations are picked with Zipf skew.

The seed changes the order and the token ids, never the work: lengths and
inter-arrival gaps are the same stratified quantiles of their distribution
for every seed, permuted by the seed.  So two seeds offer the same multiset
of sizes and gaps, and runs of different seeds differ by arrangement only.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ReqSpec:
    """One request to submit: prompt ids, output budget, due offset (s)."""
    prompt: np.ndarray
    max_new: int
    due_s: float = 0.0
    session: int = -1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named purpose of one seed (any int size)."""
    tag = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 64), tag])


def _normal_quantile(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9), so the generator needs numpy only."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    return out


def strata(n: int) -> np.ndarray:
    """Mid-points of n equal-probability strata: (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths: stratified quantiles of a clipped log-normal
    (``median``, ``sigma``, ``min``, ``max``) or a uniform (``min``, ``max``)."""
    p = strata(n)
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * _normal_quantile(p))
    elif spec["dist"] == "uniform":
        x = spec["min"] + p * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate_rps: float, n: int) -> np.ndarray:
    """n inter-arrival gaps: stratified quantiles of Exp(rate)."""
    return -np.log1p(-strata(n)) / rate_rps


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (int(n),), dtype=np.int64).astype(np.int32)


def closed_streams(mix: dict, seed: int, vocab: int) -> list[ReqSpec]:
    """The ordered request list of a closed loop: the first ``streams``
    start at once (their lengths are the stratified quantiles of the
    prompt distribution over ``streams``, so every seed starts the same
    work), and each later one replaces a stream that finished."""
    c, n = int(mix["engine"]["num_slots"]), int(mix["requests"])
    perm = rng_for(seed, "order")
    plen = np.concatenate([lengths(mix["prompt"], c)[perm.permutation(c)],
                           lengths(mix["prompt"], n)[perm.permutation(n)]])
    rng = rng_for(seed, "tokens")
    ctx = int(mix["context"])
    return [ReqSpec(_tokens(rng, p, vocab), ctx - int(p)) for p in plen]


def schedule(mix: dict, seed: int, rate: float, segments, keys=()):
    """Arrival times (s) over consecutive segments (lead-in, window, tail),
    and a stratified length per arrival for each distribution in ``keys``.
    Each segment of L seconds holds round(rate * L) arrivals whose gaps are
    the stratified quantiles of Exp(rate), scaled to fill the segment and
    permuted by the seed; its lengths are stratified over the same count.
    So every seed's window holds the same number of requests with the same
    multiset of sizes and gaps.  Returns (due, {key: lengths}, counts)."""
    perm = rng_for(seed, "order")
    due, cols, counts, t = [], {k: [] for k in keys}, [], 0.0
    for seg in segments:
        if seg <= 0:
            counts.append(0)
            continue
        n = max(1, int(round(rate * seg)))
        g = gaps(rate, n)[perm.permutation(n)]
        g = g * (seg / g.sum())
        due.append(t + np.cumsum(g) - g[0] / 2)  # inside the segment
        for k in keys:
            cols[k].append(lengths(mix[k], n)[perm.permutation(n)])
        counts.append(n)
        t += seg
    return np.concatenate(due), {k: np.concatenate(v) for k, v in cols.items()}, counts


def open_arrivals(mix: dict, seed: int, vocab: int, segments,
                  rate_rps: float | None = None) -> list[ReqSpec]:
    """Poisson arrivals at ``rate_rps`` (default: the mix's rate) over
    ``segments`` seconds (see :func:`schedule`)."""
    rate = float(rate_rps or mix["rate_rps"])
    due, col, _ = schedule(mix, seed, rate, segments, ("prompt", "output"))
    rng = rng_for(seed, "tokens")
    return [ReqSpec(_tokens(rng, p, vocab), int(o), float(d))
            for d, p, o in zip(due, col["prompt"], col["output"])]


@dataclasses.dataclass
class Sessions:
    histories: list[np.ndarray]  # full history per session (system prompt first)
    turns: list[ReqSpec]  # window traffic, each tagged with its session


def sessions(mix: dict, seed: int, vocab: int, segments,
             n_sessions: int | None = None,
             rate_rps: float | None = None) -> Sessions:
    """Conversations sharing one system prompt, and turns over them."""
    s = int(n_sessions or mix["sessions"])
    perm = rng_for(seed, "order")
    rng = rng_for(seed, "tokens")
    system = _tokens(rng, mix["system_prompt"], vocab)
    lo, hi = mix["history_turns"]
    nturns = lo + (np.arange(s) % (hi - lo + 1))[perm.permutation(s)]
    total = int(nturns.sum())
    msg = lengths(mix["message"], total)[perm.permutation(total)]
    rep = lengths(mix["history_reply"], total)[perm.permutation(total)]
    histories, k = [], 0
    for t in nturns:
        parts = [system]
        for _ in range(int(t)):
            parts.append(_tokens(rng, msg[k], vocab))
            parts.append(_tokens(rng, rep[k], vocab))
            k += 1
        histories.append(np.concatenate(parts))
    rate = float(rate_rps or mix["rate_rps"])
    due, col, counts = schedule(mix, seed, rate, segments, ("message", "output"))
    # Zipf popularity over sessions, by stratified quantiles of its CDF in
    # each segment, so every seed draws each rank equally often; the seed
    # maps ranks to sessions
    cdf = np.cumsum(zipf_weights(s, float(mix["zipf_s"])))
    ranks = np.concatenate([np.minimum(np.searchsorted(cdf, strata(n)), s - 1)
                            [perm.permutation(n)] for n in counts if n])
    ids = perm.permutation(s)[ranks]
    turns = []
    for d, m, o, sid in zip(due, col["message"], col["output"], ids):
        prompt = np.concatenate([histories[sid], _tokens(rng, m, vocab)])
        turns.append(ReqSpec(prompt, int(o), float(d), int(sid)))
    return Sessions(histories, turns)
