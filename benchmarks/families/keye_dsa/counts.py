"""Operations and bytes a `keye_dsa` configuration needs on this chip,
counted from its sizes (`sizes` of `weights.py` beside this file).

Every count is of the algorithm, not of an implementation. Attention
counts the *selected* keys only: 4 * head_dim a query head a (query,
selected key) pair, min(t + 1, topk) keys for the query at position t,
however the program reads them (its chunk form computes every visible
pair under a mask; the extra is not model work). The indexer counts every
visible pair: 2 * index_dim a head. Of the routed experts a token's
`top_k` choices reach this chip's `held` of `experts` in that share, so a
token costs `top_k * held / experts` experts here. The head counts for a
prompt's last token and each decoded token only. A multiply-add is two
operations.
"""
from __future__ import annotations


def attn_params(s: dict) -> int:
    d, H, G, Dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    return d * (H + 2 * G) * Dh + H * Dh * d


def index_params(s: dict) -> int:
    Hi, Di = s["index_heads"], s["index_dim"]
    return s["d"] * (Hi * Di + Di + Hi)


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["expert_ffn"]


def layer_matmul_params(s: dict) -> float:
    """Matmul parameters a token passes in a layer on this chip."""
    return (attn_params(s) + index_params(s) + s["d"] * s["experts"]
            + s["top_k"] * s["held"] / s["experts"] * expert_params(s))


def matmul_params(s: dict) -> float:
    """Matmul parameters a token passes through the stack (no head)."""
    return s["layers"] * layer_matmul_params(s)


def routed_params(s: dict) -> int:
    """The parameters of this chip's routed experts."""
    return s["layers"] * s["held"] * expert_params(s)


def total_params(s: dict) -> int:
    """Every parameter this chip holds."""
    d = s["d"]
    norms = 2 * d + 2 * s["head_dim"] + 2 * s["index_dim"]
    layer = (attn_params(s) + index_params(s) + norms + d * s["experts"]
             + s["held"] * expert_params(s))
    return 2 * s["vocab"] * d + d + s["layers"] * layer


def request_pairs(s: dict, n0: int, m: int) -> tuple:
    """(visible, selected) (query, key) pairs a layer of a request whose
    prompt of `n0` tokens was prefilled and that had `m` tokens on the
    host: the queries at positions 0 .. n0 + m - 2, each seeing its
    position + 1 keys and selecting min(that, topk)."""
    if m <= 0:
        return 0, 0
    n, k = n0 + m - 1, s["topk"]
    visible = n * (n + 1) // 2
    low = min(n, k)                     # queries that select all they see
    return visible, low * (low + 1) // 2 + (n - low) * k


def serve_flops(s: dict, *, prefill_tokens: int, decode_tokens: int,
                prompts: int, visible_pairs: int,
                selected_pairs: int) -> float:
    """Forward operations of serving on this chip: 2 x the stack's matmul
    parameters a token that passes it, the head for each prompt's last
    token and each decoded token, the indexer over every visible pair
    and attention over the selected pairs (both summed by the caller
    over requests, a layer: `request_pairs`)."""
    head = 2 * s["d"] * s["vocab"] * (prompts + decode_tokens)
    index = 2 * s["index_heads"] * s["index_dim"] * visible_pairs
    attend = 4 * s["heads"] * s["head_dim"] * selected_pairs
    return (2 * matmul_params(s) * (prefill_tokens + decode_tokens) + head
            + s["layers"] * (index + attend))


def kv_bytes_per_token(s: dict, itemsize: int = 2) -> int:
    """Bytes of one cached token's rows over all layers: a K row and a V
    row of the K/V heads, and the indexer's key row."""
    return s["layers"] * (2 * s["kv_heads"] * s["head_dim"]
                          + s["index_dim"]) * itemsize


def experts_reached(s: dict, live_slots: float) -> float:
    """Held experts a layer's decode step of `live_slots` tokens is
    expected to reach under even routing: each token misses a given
    expert with probability 1 - top_k / experts."""
    miss = 1.0 - s["top_k"] / s["experts"]
    return s["held"] * (1.0 - miss ** max(live_slots, 0.0))


def decode_step_bytes(s: dict, live_kv_tokens: float, live_slots: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read: the weights outside the routed
    experts once (the embedding is a lookup), the held experts a step of
    `live_slots` tokens is expected to reach, every live indexer row, and
    of the K and V rows min(a slot's live rows, topk) a slot a layer (the
    slots taken as equally long)."""
    outside = total_params(s) - routed_params(s) - s["vocab"] * s["d"]
    routed = s["layers"] * experts_reached(s, live_slots) * expert_params(s)
    each = live_kv_tokens / live_slots if live_slots > 0 else 0.0
    rows = s["layers"] * (
        live_kv_tokens * s["index_dim"]
        + live_slots * min(each, s["topk"]) * 2 * s["kv_heads"]
        * s["head_dim"])
    return (outside + routed + rows) * itemsize
