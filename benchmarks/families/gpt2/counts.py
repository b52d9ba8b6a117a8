"""Operations and bytes a GPT-2 needs, counted from its sizes (`sizes`
of `weights.py` beside this file).

Every count is of the algorithm, not of an implementation: causal
attention counts the lower triangle with its diagonal (S(S+1)/2 pairs),
the optimizer, recomputation and padding count for nothing. A multiply-
add is two operations.
"""
from __future__ import annotations

from counters import causal_pairs


def matmul_params(s: dict) -> int:
    """Parameters that sit in a matmul a token passes through: QKV, the
    attention output, both MLP matrices in every layer, and the head.
    The embedding tables are lookups and the biases are additions."""
    d, f = s["d"], s["ffn"]
    return s["layers"] * (3 * d * d + d * d + 2 * d * f) + d * s["vocab"]


def total_params(s: dict) -> int:
    d, f = s["d"], s["ffn"]
    per_layer = (4 * d * d + 2 * d * f) + (3 * d + d + f + d) + 4 * d
    return (s["vocab"] * d + s["positions"] * d + s["layers"] * per_layer
            + 2 * d + d * s["vocab"] + s["vocab"])


def attn_flops_fwd(s: dict, pairs: int) -> int:
    """Forward attention operations over `pairs` (query, key) pairs in
    every layer: QK^T and PV, 2 * d each a pair."""
    return s["layers"] * 4 * s["d"] * pairs


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward model operations a trained token: 6 x the
    matmul parameters, and three times the forward attention."""
    return 6 * matmul_params(s) + 3 * attn_flops_fwd(s, causal_pairs(seq)) / seq


def serve_flops(s: dict, prefill_pairs: int, prefill_tokens: int,
                decode_pairs: int, decode_tokens: int) -> int:
    """Forward operations of serving: 2 x the matmul parameters a token
    that passes the stack, and attention over the live context (the
    `pairs` are summed by the caller over requests: a prompt of n gives
    n(n+1)/2, a decoded token at context c gives c)."""
    return (2 * matmul_params(s) * (prefill_tokens + decode_tokens)
            + attn_flops_fwd(s, prefill_pairs + decode_pairs))


def kv_bytes_per_token(s: dict, itemsize: int = 4) -> int:
    """Bytes of one cached token's keys and values over all layers."""
    return 2 * s["layers"] * s["d"] * itemsize


def decode_step_bytes(s: dict, live_kv_tokens: int, itemsize: int = 4,
                      kv_itemsize: int = 4) -> int:
    """Bytes one decode step has to read: every weight once and the live
    keys and values of the active slots (not the cache's capacity)."""
    kv = kv_bytes_per_token(s, kv_itemsize) * live_kv_tokens
    return total_params(s) * itemsize - \
        (s["vocab"] + s["positions"]) * s["d"] * itemsize + kv


def flash_call_shape(s: dict, batch: int, seq: int) -> tuple:
    """(batch, heads, seq, head_dim) of one flash-attention call of a
    training step, which `counters.flash_fwd` / `flash_bwd` count."""
    return batch, s["heads"], seq, s["d"] // s["heads"]
