"""Operations and bytes a `sarvam_mla` configuration needs on this chip,
counted from its sizes (`sizes` of `weights.py` beside this file).

Every count is of the algorithm, not of an implementation. Attention is
counted in its cheaper, expanded form, 2 * (nope + rope + v) a head a
(query, key) pair, for prefill and decode alike (the absorbed form a
decode step runs costs more operations and fewer bytes; the extra is not
model work). Of the routed experts a token's `top_k` choices reach this
chip's `held` of `experts` in that share, so a token costs `top_k * held /
experts` experts here. The head counts for a prompt's last token and each
decoded token only. A multiply-add is two operations.
"""
from __future__ import annotations


def attn_params(s: dict) -> int:
    d, H = s["d"], s["heads"]
    return (d * H * (s["nope"] + s["rope"]) + d * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * H * (s["nope"] + s["v"]) + H * s["v"] * d)


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["expert_ffn"]


def layer_matmul_params(s: dict, i: int) -> float:
    """Matmul parameters a token passes in layer `i` on this chip."""
    if i < s["dense_layers"]:
        return attn_params(s) + 3 * s["d"] * s["dense_ffn"]
    return (attn_params(s) + 3 * s["d"] * s["shared_ffn"]
            + s["d"] * s["experts"]
            + s["top_k"] * s["held"] / s["experts"] * expert_params(s))


def matmul_params(s: dict) -> float:
    """Matmul parameters a token passes through the stack (no head)."""
    return sum(layer_matmul_params(s, i) for i in range(s["layers"]))


def total_params(s: dict) -> int:
    """Every parameter this chip holds."""
    d = s["d"]
    norms = 2 * d + (s["nope"] + s["rope"]) + s["kv_rank"]
    n = 2 * s["vocab"] * d + d
    for i in range(s["layers"]):
        n += attn_params(s) + norms
        if i < s["dense_layers"]:
            n += 3 * d * s["dense_ffn"]
        else:
            n += (3 * d * s["shared_ffn"] + d * s["experts"] + s["experts"]
                  + s["held"] * expert_params(s))
    return n


def routed_params(s: dict) -> int:
    """The parameters of this chip's routed experts."""
    return (s["layers"] - s["dense_layers"]) * s["held"] * expert_params(s)


def attn_flops_fwd(s: dict, pairs: int) -> int:
    """Attention operations over `pairs` (query, key) pairs in every
    layer: QK^T over nope + rope and PV over v, a head."""
    return s["layers"] * 2 * s["heads"] * (s["nope"] + s["rope"]
                                           + s["v"]) * pairs


def serve_flops(s: dict, prefill_pairs: int, prefill_tokens: int,
                decode_pairs: int, decode_tokens: int,
                prompts: int = 0) -> float:
    """Forward operations of serving on this chip: 2 x the stack's matmul
    parameters a token that passes it, the head for each prompt's last
    token and each decoded token, and attention over the live context
    (the `pairs` are summed by the caller over requests)."""
    head = 2 * s["d"] * s["vocab"] * (prompts + decode_tokens)
    return (2 * matmul_params(s) * (prefill_tokens + decode_tokens) + head
            + attn_flops_fwd(s, prefill_pairs + decode_pairs))


def kv_bytes_per_token(s: dict, itemsize: int = 2) -> int:
    """Bytes of one cached token's latent rows over all layers."""
    return s["layers"] * (s["kv_rank"] + s["rope"]) * itemsize


def experts_reached(s: dict, live_slots: float) -> float:
    """Held experts a layer's decode step of `live_slots` tokens is
    expected to reach under even routing: each token misses a given
    expert with probability 1 - top_k / experts."""
    miss = 1.0 - s["top_k"] / s["experts"]
    return s["held"] * (1.0 - miss ** max(live_slots, 0.0))


def decode_step_bytes(s: dict, live_kv_tokens: float, itemsize: int = 2,
                      kv_itemsize: int = 2, live_slots: float = None):
    """Bytes one decode step has to read: the weights outside the routed
    experts once (the embedding is a lookup), the held experts a step of
    `live_slots` tokens is expected to reach, and the live latent rows.
    Without `live_slots` every held expert counts."""
    outside = total_params(s) - routed_params(s) - s["vocab"] * s["d"]
    reached = s["held"] if live_slots is None \
        else experts_reached(s, live_slots)
    routed = (s["layers"] - s["dense_layers"]) * reached * expert_params(s)
    return ((outside + routed) * itemsize
            + kv_bytes_per_token(s, kv_itemsize) * live_kv_tokens)
