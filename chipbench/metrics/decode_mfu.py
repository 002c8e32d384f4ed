"""Model step: model FLOPs of the tokens decoded in the window (weights
and attention over each token's context, workcount.decode_flops) over the
window and the chip's peak bf16 FLOP/s, in %."""
import workcount


def read(rec):
    d = rec.get("decode")
    if not d or not d["tokens"] or not rec.get("window_s"):
        return None
    flops = workcount.decode_flops(rec["config"], d["tokens"], d["ctx_sum"])
    return 100.0 * flops / rec["window_s"] / rec["chips"] / rec["peaks"]["bf16_flops"]
