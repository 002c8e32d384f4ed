"""Load generator: 95th percentile of how late each request due in the
window was submitted after its due time."""
import benchstats


def read(rec):
    vals = [(r["submit_ns"] - r["due_ns"]) / 1e6
            for r in rec["rows"] if r["in_window"]]
    return benchstats.percentile(vals, 95) if vals else None
