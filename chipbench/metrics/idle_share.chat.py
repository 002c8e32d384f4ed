"""Device: share of the traced window in which no op ran on the chip, in %."""
import devtrace


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.ops or not rec.get("trace_window_s"):
        return None
    return 100.0 * (1.0 - devtrace.busy_s(tr) / rec["trace_window_s"])
