"""Model step: least HBM bytes the window's decoded tokens need (the
weights once per step of the fixed concurrency, each token's K/V context
once, workcount.decode_bytes) over the window and the chip's peak HBM
bandwidth, in %."""
import workcount


def read(rec):
    d = rec.get("decode")
    if not d or not d["tokens"] or not rec.get("window_s"):
        return None
    steps = d["tokens"] / d["streams"]
    b = workcount.decode_bytes(rec["config"], steps, d["ctx_sum"])
    return 100.0 * b / rec["window_s"] / rec["chips"] / rec["peaks"]["hbm_bytes_s"]
