"""Operations and bytes that served tokens need, from the configuration.

Counted from the traffic (how many tokens, at what context), never from how
a kernel is tiled, so a roofline share reads the same work whatever
implements it.  Each count is the configuration's architecture's
(``chipbench/arch/<reference>.py``, the same names and signatures), since
which weights a token multiplies and how far it attends is the
architecture's; model FLOPs follow the usual accounting: 2 per weight
multiplied per token (the embedding lookup is free), plus the attention
products over each attended position.

``ctx_sum`` is the sum of the decoded tokens' attended lengths when every
layer attends the whole context; ``spans`` lists each row's (context
before its first decoded token, tokens decoded), from which an
architecture whose layers attend less (a window) works out its own.
"""
from __future__ import annotations

import cellspec


def _arch(c: dict):
    return cellspec.arch(c.get("reference"))


def matmul_params(c: dict) -> int:
    """Weights that a token multiplies: the stack's matrices and the LM
    head (norm gains and the embedding table excluded)."""
    return _arch(c).matmul_params(c)


def weight_bytes(c: dict) -> int:
    """Bytes a decode step streams once: every served weight but the
    embedding table (of which a step reads one row per token)."""
    return _arch(c).weight_bytes(c)


def kv_bytes_per_position(c: dict) -> int:
    """K and V of one position in one layer."""
    return _arch(c).kv_bytes_per_position(c)


def attn_flops_per_position(c: dict) -> int:
    """QK^T and PV for one query over one attended position, one layer."""
    return _arch(c).attn_flops_per_position(c)


def sum_contexts(c0: int, n: int) -> int:
    """Sum of attended lengths of n tokens decoded after a context of c0:
    the k-th new token (k = 1..n) attends c0 + k positions."""
    return n * c0 + n * (n + 1) // 2


def decode_flops(c: dict, tokens: int, ctx_sum: int, spans=None) -> int:
    """Model FLOPs of ``tokens`` decoded tokens whose attended lengths sum
    to ``ctx_sum``."""
    return _arch(c).decode_flops(c, tokens, ctx_sum, spans)


def decode_bytes(c: dict, steps: float, ctx_sum: int, spans=None) -> float:
    """Least HBM traffic of decoding: the weights once per step, the K/V
    of every attended position once per token."""
    return _arch(c).decode_bytes(c, steps, ctx_sum, spans)


def paged_decode_bytes(c: dict, ctx_sum: int, tokens: int, spans=None) -> int:
    """Least bytes the paged decode kernel moves, over all layers: each
    attended position's K/V once, plus each token's q read and o written."""
    return _arch(c).paged_decode_bytes(c, ctx_sum, tokens, spans)


def paged_decode_flops(c: dict, ctx_sum: int, spans=None) -> int:
    return _arch(c).paged_decode_flops(c, ctx_sum, spans)
