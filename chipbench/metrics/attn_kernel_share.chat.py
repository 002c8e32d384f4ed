"""Kernels: device time of the paged decode and paged span kernels over
the device's busy time in the traced window, in %."""
import devtrace

# the Pallas paged decode and span kernels, by their pallas_call names or
# kernel functions
KERNELS = ("paged_attention", "paged_span_attention", "_paged_decode_kernel",
           "_paged_span_kernel")


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    busy = devtrace.busy_s(tr)
    t = devtrace.op_time_s(tr, KERNELS)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
