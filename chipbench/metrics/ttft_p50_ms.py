"""Median time from due to first token over the requests due in the window.
A request with no first token at the cut-off enters with its wait so far."""
import benchstats


def read(rec):
    vals = [((r["first_ns"] if r["first_ns"] >= 0 else rec["cutoff_ns"])
             - r["due_ns"]) / 1e6 for r in rec["rows"] if r["in_window"]]
    return benchstats.percentile(vals, 50) if vals else None
