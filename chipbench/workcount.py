"""Operations and bytes that served tokens need, from the configuration.

Counted from the traffic (how many tokens, at what context), never from how
a kernel is tiled, so a roofline share reads the same work whatever
implements it.  Model FLOPs follow the usual accounting: 2 per weight per
token for every matrix product (the embedding lookup is free), plus the
attention products, 4 * heads * head_dim per layer per attended position.
"""
from __future__ import annotations

import seedweights as sw


def matmul_params(c: dict) -> int:
    """Weights that every token multiplies: the stack's matrices and the
    LM head (norm gains and the embedding table excluded)."""
    z = sw.dims(c)
    d, h, kh, hd, ff = z["d"], z["h"], z["kh"], z["hd"], z["ff"]
    layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff
    return z["layers"] * layer + d * z["v"]


def weight_bytes(c: dict) -> int:
    """Bytes a decode step streams once: every served weight but the
    embedding table (of which a step reads one row per token)."""
    z = sw.dims(c)
    return sw.param_bytes(c) - z["v"] * z["d"] * _item(c)


def _item(c: dict) -> int:
    return 2 if c["torch_dtype"] == "bfloat16" else 4


def kv_bytes_per_position(c: dict) -> int:
    """K and V of one position in one layer."""
    z = sw.dims(c)
    return 2 * z["kh"] * z["hd"] * _item(c)


def attn_flops_per_position(c: dict) -> int:
    """QK^T and PV for one query over one attended position, one layer."""
    z = sw.dims(c)
    return 4 * z["h"] * z["hd"]


def sum_contexts(c0: int, n: int) -> int:
    """Sum of attended lengths of n tokens decoded after a context of c0:
    the k-th new token (k = 1..n) attends c0 + k positions."""
    return n * c0 + n * (n + 1) // 2


def decode_flops(c: dict, tokens: int, ctx_sum: int) -> int:
    """Model FLOPs of ``tokens`` decoded tokens whose attended lengths sum
    to ``ctx_sum``."""
    z = sw.dims(c)
    return (2 * matmul_params(c) * tokens
            + attn_flops_per_position(c) * z["layers"] * ctx_sum)


def decode_bytes(c: dict, steps: float, ctx_sum: int) -> float:
    """Least HBM traffic of decoding: the weights once per step, the K/V
    of every attended position once per token."""
    z = sw.dims(c)
    return (weight_bytes(c) * steps
            + kv_bytes_per_position(c) * z["layers"] * ctx_sum)


def paged_decode_bytes(c: dict, ctx_sum: int, tokens: int) -> int:
    """Least bytes the paged decode kernel moves, over all layers: each
    attended position's K/V once, plus each token's q read and o written."""
    z = sw.dims(c)
    qo = 2 * z["h"] * z["hd"] * _item(c)
    return z["layers"] * (kv_bytes_per_position(c) * ctx_sum + qo * tokens)


def paged_decode_flops(c: dict, ctx_sum: int) -> int:
    return attn_flops_per_position(c) * sw.dims(c)["layers"] * ctx_sum
