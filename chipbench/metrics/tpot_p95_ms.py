"""95th percentile over the requests due in the window of the time per
output token after the first: (last token - first token) / (tokens - 1),
counting the tokens each had received by the cut-off."""
import benchstats


def read(rec):
    vals = [(r["last_ns"] - r["first_ns"]) / (r["n_tokens"] - 1) / 1e6
            for r in rec["rows"] if r["in_window"] and r["n_tokens"] >= 2
            and r["first_ns"] >= 0 and r["last_ns"] > r["first_ns"]]
    return benchstats.percentile(vals, 95) if vals else None
