"""Operations and bytes a `granite_hybrid` configuration needs on this
chip, counted from its sizes (`sizes` of `weights.py` beside this file).

Every count is of the algorithm, not of an implementation. A state-space
layer counts its recurrence: per token and element of the [H, P, N] state
one multiply-add to decay and add the input (the input's outer product is
one more multiply) and one to read it out, 5 operations; the chunked
form's extra work inside a chunk is not model work. Attention counts the
causal (query, key) pairs, 4 * heads * head_dim a pair, in the attention
layers only. The head counts for a prompt's last token and each decoded
token only (the program computes it at every prefill position). A
multiply-add is two operations.
"""
from __future__ import annotations


def mamba_params(s: dict) -> int:
    """Matmul parameters of a state-space mixer: W_in and W_out."""
    return s["d"] * (2 * s["d_inner"] + 2 * s["state"] + s["ssm_heads"]) \
        + s["d_inner"] * s["d"]


def attn_params(s: dict) -> int:
    d, H, G, Dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    return d * (H + 2 * G) * Dh + H * Dh * d


def mlp_params(s: dict) -> int:
    return 3 * s["d"] * s["ffn"]


def matmul_params(s: dict) -> int:
    """Matmul parameters a token passes through the stack (no head)."""
    return (s["mamba_layers"] * mamba_params(s)
            + s["attention_layers"] * attn_params(s)
            + s["layers"] * mlp_params(s))


def total_params(s: dict) -> int:
    """Every parameter: the stack, its norms, the convolutions and the
    per-head scalars, and the embedding the head shares."""
    d, H = s["d"], s["ssm_heads"]
    mamba = (mamba_params(s) + s["conv_kernel"] * s["conv_dim"]
             + s["conv_dim"] + 3 * H + s["d_inner"])
    return (s["mamba_layers"] * mamba + s["attention_layers"] * attn_params(s)
            + s["layers"] * (mlp_params(s) + 2 * d) + d + s["vocab"] * d)


def state_elements(s: dict) -> int:
    """Elements of one slot's state in one state-space layer."""
    return s["ssm_heads"] * s["ssm_head_dim"] * s["state"]


def serve_flops(s: dict, prefill_pairs: int, prefill_tokens: int,
                decode_pairs: int, decode_tokens: int,
                prompts: int = 0) -> float:
    """Forward operations of serving: 2 x the stack's matmul parameters
    a token that passes it, the recurrence of every state-space layer a
    token, attention over the causal pairs (summed by the caller over
    requests: a prompt of n gives n(n+1)/2, a decoded token at context c
    gives c) in each attention layer, and the head for each prompt's last
    token and each decoded token."""
    tokens = prefill_tokens + decode_tokens
    recur = 5 * state_elements(s) * s["mamba_layers"]
    attend = 4 * s["heads"] * s["head_dim"] * s["attention_layers"]
    head = 2 * s["d"] * s["vocab"] * (prompts + decode_tokens)
    return ((2 * matmul_params(s) + recur) * tokens
            + attend * (prefill_pairs + decode_pairs) + head)


def kv_bytes_per_token(s: dict, itemsize: int = 2) -> int:
    """Bytes of one cached token's K and V rows over the attention layers
    (the state-space layers cache no rows)."""
    return s["attention_layers"] * 2 * s["kv_heads"] * s["head_dim"] \
        * itemsize


def state_bytes_per_slot(s: dict, itemsize: int = 2) -> int:
    """Bytes of one slot's state over the state-space layers: the float32
    [H, P, N] state and the convolution's last d_conv - 1 inputs."""
    return s["mamba_layers"] * (
        4 * state_elements(s)
        + (s["conv_kernel"] - 1) * s["conv_dim"] * itemsize)


def ssm_step_bytes(s: dict, slots: float, itemsize: int = 2) -> float:
    """Bytes the one-token state update of a decode step of `slots` slots
    has to move over the state-space layers: each slot's float32 state
    read and written, its convolution tail read and written, and its
    float32 x, B, C and step-size rows read once."""
    rows = 4 * (s["conv_dim"] + s["ssm_heads"])
    return slots * (2 * state_bytes_per_slot(s, itemsize)
                    + s["mamba_layers"] * rows)


def decode_step_bytes(s: dict, live_kv_tokens: float, live_slots: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step has to read or write: every weight once (the
    embedding is the head, which reads it whole), each live slot's state
    read and written, and the live K and V rows."""
    return (total_params(s) * itemsize
            + 2 * live_slots * state_bytes_per_slot(s, itemsize)
            + live_kv_tokens * kv_bytes_per_token(s, itemsize))
