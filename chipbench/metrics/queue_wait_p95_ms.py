"""Scheduler: 95th percentile of due -> admission (the engine's t_admit
stamp, at its first prefill dispatch) over the requests due in the window;
one not admitted at the cut-off enters with its wait so far."""
import benchstats


def read(rec):
    vals = [((r["admit_ns"] if r["admit_ns"] >= 0 else rec["cutoff_ns"])
             - r["due_ns"]) / 1e6 for r in rec["rows"] if r["in_window"]]
    return benchstats.percentile(vals, 95) if vals else None
