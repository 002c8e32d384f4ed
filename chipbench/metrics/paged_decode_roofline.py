"""Kernels: the paged decode kernel's share of its roofline.  The least
time for the work of the tokens decoded in the traced window (their K/V
context, q and o: workcount.paged_decode_bytes / _flops) is the larger of
bytes over peak HBM bandwidth and FLOPs over peak FLOP/s -- here always
the bytes, at GQA's 2*heads/kv_heads FLOP per byte -- over the summed
device time of the kernel's events, in %."""
import devtrace
import workcount

# the Pallas decode kernel as the trace names it (its pallas_call), and by
# its kernel function should a later change name the call after it
KERNEL = ("paged_attention", "_paged_decode_kernel")


def read(rec):
    tr, d = rec.get("trace"), rec.get("trace_decode")
    if tr is None or not d or not d["tokens"]:
        return None
    t = devtrace.op_time_s(tr, KERNEL)
    if t <= 0:
        return None
    c, pk = rec["config"], rec["peaks"]
    least = max(workcount.paged_decode_bytes(c, d["ctx_sum"], d["tokens"])
                / pk["hbm_bytes_s"],
                workcount.paged_decode_flops(c, d["ctx_sum"]) / pk["bf16_flops"])
    return 100.0 * least / t
