"""Drive one run of a learned-sparse-attention, routed-expert cell with
one part of its mathematics left out of the timed path:
`python broken_longctx.py <fault> <workload> <seed> [chip]`, as
`broken_run.py` beside this file does for the faults it knows. The run has
to print `correct: false`.

| fault | what the program does instead |
|---|---|
| `no_selection` | every visible key is attended (topk = the capacity) |
| `half_topk` | half of `topk` keys are selected |
| `index_keys_unrotated` | the indexer's key is cached unrotated (its queries still turn) |
| `no_head_weights` | the index score sums the heads unweighted |
| `kv_heads_misgrouped` | query head j reads K/V head j mod 4 in place of j // 8 |
| `no_renorm` | the chosen experts' weights are their softmax scores, not renormalised over the chosen |
| `no_select_bias` | the router's selection bias is left out: `broken_longdoc.py`'s fault of that name for `sarvam-105b.longdoc`, whose stand-in there predates the scoring rule and takes five arguments only |
"""
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.functional import dsa as S
    from paddle_tpu.nn.functional import latent as L

    sound_attention = S.indexed_attention
    if fault == "no_selection":
        S.indexed_attention = lambda *a, topk, **kw: sound_attention(
            *a, topk=1 << 30, **kw)
    elif fault == "half_topk":
        S.indexed_attention = lambda *a, topk, **kw: sound_attention(
            *a, topk=topk // 2, **kw)
    elif fault == "index_keys_unrotated":
        # the indexer's key is [B, T, dim]; every query and the K rows
        # carry a head axis
        sound_rope = L._rope
        L._rope = lambda x, pos, inv_freq, scale: x if x.ndim == 3 \
            else sound_rope(x, pos, inv_freq, scale)
    elif fault == "no_head_weights":
        sound_tile = S._score_tile
        S._score_tile = lambda qi, w, rows: sound_tile(
            qi, jnp.ones_like(w), rows)
    elif fault == "kv_heads_misgrouped":
        def misgrouped(query, *a, kv_heads, **kw):
            B, T, H, Dh = query.shape
            G, R = kv_heads, H // kv_heads
            # head r * G + g is put where head g * R + r belongs, so it
            # reads K/V head g = j mod G
            q = query._data.reshape(B, T, R, G, Dh).transpose(0, 1, 3, 2, 4)
            ctx, keys = sound_attention(
                Tensor._wrap(q.reshape(B, T, H, Dh)), *a, kv_heads=kv_heads,
                **kw)
            back = ctx._data.reshape(B, T, G, R, Dh).transpose(0, 1, 3, 2, 4)
            return Tensor._wrap(back.reshape(B, T, H, Dh)), keys

        S.indexed_attention = misgrouped
    elif fault == "no_renorm":
        def raw_scores(x, gate_w, bias, top_k, scaling, score="sigmoid"):
            s = L.SCORES[score](jnp.dot(
                x, gate_w, preferred_element_type=jnp.float32))
            chosen, idx = jax.lax.top_k(s, top_k)
            return idx.astype(jnp.int32), scaling * chosen

        L.route_top_k = raw_scores
    elif fault == "no_select_bias":
        sound_route = L.route_top_k
        L.route_top_k = lambda x, gate_w, bias, *a, **kw: sound_route(
            x, gate_w, None, *a, **kw)
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, seed = sys.argv[1:4]
    on_chip = sys.argv[4:] == ["chip"]
    import run

    plant(fault)
    sys.exit(run.main(["--workload", workload, "--seed", seed, "--trace", "0"]
                      + (["--seconds", "15"] if on_chip
                         else ["--seconds", "3", "--rehearse"])))
