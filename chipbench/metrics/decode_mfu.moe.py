"""Model step, for an architecture whose tokens multiply a share of its
weights: model FLOPs of the tokens decoded in the window (the active
parameters and each layer's attended context, which the architecture
works out from each row's span: workcount.decode_flops with ``spans``)
over the window and the chip's peak bf16 FLOP/s, in %.  None where the
architecture counts nothing from spans."""
import workcount


def read(rec):
    d = rec.get("decode")
    if not d or not d["tokens"] or not rec.get("window_s"):
        return None
    flops = workcount.decode_flops(rec["config"], d["tokens"], d["ctx_sum"],
                                   d["spans"])
    if flops is None:
        return None
    return 100.0 * flops / rec["window_s"] / rec["chips"] / rec["peaks"]["bf16_flops"]
