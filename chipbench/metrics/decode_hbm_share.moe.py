"""Model step, for an architecture whose steps read a share of its
weights: least HBM bytes the window's decoded tokens need (per step the
weights outside the experts and the distinct experts the step's rows
route to, each token's attended K/V once: workcount.decode_bytes with
``spans``) over the window and the chip's peak HBM bandwidth, in %.  None
where the architecture counts nothing from spans."""
import workcount


def read(rec):
    d = rec.get("decode")
    if not d or not d["tokens"] or not rec.get("window_s"):
        return None
    steps = d["tokens"] / d["streams"]
    b = workcount.decode_bytes(rec["config"], steps, d["ctx_sum"], d["spans"])
    if b is None:
        return None
    return 100.0 * b / rec["window_s"] / rec["chips"] / rec["peaks"]["hbm_bytes_s"]
