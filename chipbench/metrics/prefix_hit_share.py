"""Scheduler / pool: prompt tokens served from the prefix cache (the
engine's per-request prefix_hit_tokens counter) over prompt tokens of the
requests due in the window and admitted by the cut-off, in %."""


def read(rec):
    rows = [r for r in rec["rows"] if r["in_window"] and r["admit_ns"] >= 0]
    total = sum(r["prompt_len"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["prefix_hit"] for r in rows) / total
