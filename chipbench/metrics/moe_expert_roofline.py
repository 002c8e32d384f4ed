"""Expert kernels: the expert grouped matmuls' share of their roofline in
the traced window.  The least time for the expert FFNs of the tokens
decoded there (each step's distinct experts read once per layer, each
routed row's input and output: the architecture's ``expert_bytes`` /
``expert_flops``) is the larger of bytes over peak HBM bandwidth and FLOPs
over peak FLOP/s -- in decode the bytes -- over the summed device time of
the grouped matmuls' events, in %.  None where the architecture has no
experts or the trace no such op."""
import cellspec
import devtrace

# jax.lax.ragged_dot as the TPU compiler names its grouped matmul (the
# HLO instruction of the ragged dot; its group metadata is a separate op)
KERNEL = ("ragged-dot-none",)


def read(rec):
    tr, d = rec.get("trace"), rec.get("trace_decode")
    if tr is None or not d or not d["tokens"]:
        return None
    c, pk = rec["config"], rec["peaks"]
    arch = cellspec.arch(c.get("reference"))
    if not hasattr(arch, "expert_bytes"):
        return None
    t = devtrace.op_time_s(tr, KERNEL)
    if t <= 0:
        return None
    steps = d["tokens"] / rec["mix"]["engine"]["num_slots"]
    least = max(arch.expert_bytes(c, steps, d["tokens"]) / pk["hbm_bytes_s"],
                arch.expert_flops(c, d["tokens"]) / pk["bf16_flops"])
    return 100.0 * least / t
