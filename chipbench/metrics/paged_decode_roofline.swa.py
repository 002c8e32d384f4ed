"""Attention kernels, for an architecture with sliding-window layers: the
paged decode kernel's share of its roofline, as ``paged_decode_roofline``
reads it, but with each layer's attended lengths (a window layer's capped
at its window) worked out from the traced window's rows' spans
(workcount.paged_decode_bytes / _flops with ``spans``).  None where the
architecture counts nothing from spans."""
import devtrace
import workcount

# the Pallas decode kernel as the trace names it (its pallas_call)
KERNEL = ("paged_attention", "_paged_decode_kernel")


def read(rec):
    tr, d = rec.get("trace"), rec.get("trace_decode")
    if tr is None or not d or not d["tokens"]:
        return None
    t = devtrace.op_time_s(tr, KERNEL)
    if t <= 0:
        return None
    c, pk = rec["config"], rec["peaks"]
    b = workcount.paged_decode_bytes(c, d["ctx_sum"], d["tokens"], d["spans"])
    f = workcount.paged_decode_flops(c, d["ctx_sum"], d["spans"])
    if b is None or f is None:
        return None
    return 100.0 * max(b / pk["hbm_bytes_s"], f / pk["bf16_flops"]) / t
