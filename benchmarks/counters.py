"""Operations and bytes that a kernel's algorithm needs, counted from the
shapes of its call, the roofline they give, and the chip's peaks. What a
whole model needs is counted by its family (`families/<family>`).

Every count is of the algorithm, not of an implementation: causal
attention counts the lower triangle with its diagonal (S(S+1)/2 pairs),
the optimizer, recomputation and padding count for nothing. A multiply-
add is two operations.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"peaks.json has no entry for device kind "
                       f"{device_kind!r}: add one with its source")
    return table[device_kind]


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def flash_fwd(batch, heads, seq, dh, itemsize=2):
    """(operations, bytes) of one causal flash-attention forward call."""
    pairs = batch * heads * causal_pairs(seq)
    n = batch * heads * seq * dh
    return 4 * dh * pairs, 4 * n * itemsize + batch * heads * seq * 4


def flash_bwd(batch, heads, seq, dh, itemsize=2):
    """(operations, bytes) of one causal flash-attention backward (dQ, dK
    and dV together): four matmuls over the triangle; the recomputed
    QK^T is not counted. Reads q, k, v, o, do; writes dq, dk, dv."""
    pairs = batch * heads * causal_pairs(seq)
    n = batch * heads * seq * dh
    return 8 * dh * pairs, 8 * n * itemsize + 2 * batch * heads * seq * 4


def roofline_seconds(flops, nbytes, peak) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    tf, tb = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
