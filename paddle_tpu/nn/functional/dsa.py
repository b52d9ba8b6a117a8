"""Functionals of learned sparse attention (DeepSeek-Sparse-Attention as
a grouped-query decoder carries it): an *indexer* scores every cached
position for every query, the `topk` best-scored positions a query are
selected exactly, and the softmax attention runs over the selected rows
only.

    I(t, s) = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])      float32
    S_t     = the topk positions s <= t with the largest I(t, s)
              (every s <= t while t < topk; ties to the lower position,
              as `jax.lax.top_k`)
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // r]
              * scale) v[s, h // r]                  r = heads / kv heads

A layer caches three kinds of row a token (`IndexedKVCache`): the K rows
and V rows of the few K/V heads and the indexer's one key row. Two shapes
of call run the one mathematics, `indexed_attend_plan` says which:

* ``masked`` — a chunk (more than one query a slot). The index scores of
  the chunk against the cached rows are computed a key tile at a time
  (never `[heads, T, S]`), each query's `topk`-th largest score is found
  exactly by bisection over the scores' bit patterns (32 counting passes,
  no sort), and a blockwise softmax (running maximum and sum) visits the
  key tiles at or below the furthest query with the selection as its
  mask. Reading each row once for 2,048 queries is cheaper than gathering
  2,048 rows for each of them.
* ``gather`` — a decode step (one query a slot). The scores of the one
  query against every cached indexer row, `jax.lax.top_k`, and attention
  over the `topk` gathered K and V rows: the step reads `topk` K/V rows a
  slot however long the context is.

Both select exactly S_t (tests/test_sparse_moe_lm.py compares the sets
with `jax.lax.top_k`'s, ties included). Index scores, their statistics
and the softmax are float32; matmuls accumulate in float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ...core import autograd as AG
from .latent import KEY_BLOCK, _NEG, LatentCache
from .ssm import SSMState

__all__ = ["IndexedKVCache", "is_row_cache", "indexed_cache_update",
           "indexed_attend_plan", "index_scores", "kth_largest",
           "indexed_attention", "advance_wide", "read_wide"]

#: the cache of one learned-sparse-attention layer: `k` and `v` [B, cap,
#: kv_heads * head_dim] (the K/V heads side by side in a row), `idx` [B,
#: cap, index_dim], the indexer's normalised, rotated key
IndexedKVCache = collections.namedtuple("IndexedKVCache", ["k", "v", "idx"])

#: keys a pass of the bisection counts at once
COUNT_BLOCK = 4096

#: the caches with no per-head `[.., H, rows, Dh]` layout: rows with no
#: head axis, or a state with no rows at all; what a paged pool, the
#: prefix cache and migration refuse by name
ROW_CACHES = {LatentCache: "a latent cache (LatentCache: one [c | k_rope] "
                           "row a token, no head axis)",
              IndexedKVCache: "an indexed cache (IndexedKVCache: K rows, V "
                              "rows and the indexer's key rows side by side)",
              SSMState: "a state cache (SSMState: one [H, P, N] state a "
                        "slot, no rows)"}


def is_row_cache(cache_tree, kinds=None):
    """The description of the first cache of `ROW_CACHES` (or of those of
    its `kinds`) a cache pytree holds, or None."""
    kinds = tuple(ROW_CACHES) if kinds is None else tuple(kinds)
    for leaf in jax.tree_util.tree_leaves(
            cache_tree, is_leaf=lambda v: isinstance(v, kinds)):
        if isinstance(leaf, kinds):
            return ROW_CACHES[type(leaf)]
    return None


def indexed_cache_update(cache: IndexedKVCache, k, v, idx,
                         pos) -> IndexedKVCache:
    """Write the [B, T, .] new rows of each kind at per-slot positions
    ``pos`` ([B] int32). Inference-only."""
    def write(c, u, p):
        return jax.vmap(
            lambda cb, ub, pb: jax.lax.dynamic_update_slice_in_dim(
                cb, ub.astype(cb.dtype), pb, axis=0)
        )(c, u, jnp.asarray(p, jnp.int32))

    return IndexedKVCache(*(
        AG.apply_nondiff(write, (c, u, pos))
        for c, u in zip(cache, (k, v, idx))))


def indexed_attend_plan(q_len: int, k_len: int,
                        key_block: int = KEY_BLOCK):
    """(form, scores) of `indexed_attention` for `q_len` queries a slot
    against `k_len` rows: form ``gather`` for a decode step's single
    query, ``masked`` otherwise; the masked form runs ``blockwise`` when
    the rows are more than one whole block, else ``dense``."""
    if q_len == 1:
        return "gather", "dense"
    blockwise = k_len > key_block and k_len % key_block == 0
    return "masked", "blockwise" if blockwise else "dense"


def _tile(S: int, block: int) -> int:
    return block if (S > block and S % block == 0) else S


def _visible_tiles(start, T: int, bk: int, total: int):
    """Tiles of `bk` keys at or below the furthest query."""
    return jnp.minimum(
        (jnp.max(start).astype(jnp.int32) + T + bk - 1) // bk, total)


def _ukey(x):
    """float32 -> uint32, monotone: a larger score has a larger key."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = (bits >> 31) == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))


def _score_tile(qi, w, rows):
    """qi [B, T, Hi, Di], w [B, T, Hi] float32, rows [B, K, Di] ->
    [B, T, K] float32: sum over the indexer's heads of w * relu(q . k).
    A sum of -0.0 (every head at rest under negative weights) is given
    as 0.0: the two are one score, and whether a sort tells them apart
    is the platform's."""
    s = jnp.einsum("bthd,bkd->bhtk", qi, rows.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    s = (jax.nn.relu(s) * w.transpose(0, 2, 1)[..., None]).sum(1)
    return jnp.where(s == 0, jnp.float32(0), s)


def index_scores(qi, w, rows, start, key_block: int = KEY_BLOCK):
    """Raw arrays. The index scores of qi [B, T, Hi, Di] with head
    weights w [B, T, Hi] against the indexer rows [B, S, Di], as monotone
    uint32 keys [B, T, S] (`_ukey`); slot b's query t sits at position
    start[b] + t, and a position it cannot see scores -inf. Computed a
    tile of `key_block` keys at a time; tiles above the furthest query
    are not visited."""
    B, T = qi.shape[:2]
    S = rows.shape[1]
    bk = _tile(S, key_block)
    total = S // bk
    qpos = start.astype(jnp.int32)[:, None] + jnp.arange(T)[None, :]
    w = w.astype(jnp.float32)

    def tile(j):
        blk = jax.lax.dynamic_slice_in_dim(rows, j * bk, bk, axis=1)
        kpos = j * bk + jnp.arange(bk)
        s = _score_tile(qi, w, blk)
        return _ukey(jnp.where(kpos[None, None, :] > qpos[..., None],
                               -jnp.inf, s))

    if total == 1:
        return tile(0)
    hidden = _ukey(jnp.float32(-jnp.inf))
    return jax.lax.fori_loop(
        0, _visible_tiles(start, T, bk, total), lambda j, u: jax.lax.dynamic_update_slice_in_dim(
            u, tile(j), j * bk, axis=2),
        jnp.full((B, T, S), hidden, jnp.uint32))


def kth_largest(ukeys, k: int, start):
    """Raw arrays. For every row of uint32 keys [B, T, S]: (v, room) with
    v the k-th largest key, exactly, and room = k - #(keys > v) >= 1,
    how many of the keys equal to v belong to the k largest (those at
    the lowest positions, by `jax.lax.top_k`'s rule). Found a bit at a
    time from the top: v keeps a bit if at least k keys are >= v with it
    set. 32 counting passes over the keys, `COUNT_BLOCK` at a time and
    only over the blocks at or below the furthest query (`start` [B] is
    the position of each slot's first query)."""
    B, T, S = ukeys.shape
    cb = _tile(S, COUNT_BLOCK)
    total = S // cb
    n = _visible_tiles(start, T, cb, total)

    def count(pred, bound):
        """#(keys `pred` bound) a row -> [B, T] int32."""
        def block(j, c):
            blk = jax.lax.dynamic_slice_in_dim(ukeys, j * cb, cb, axis=2)
            return c + pred(blk, bound[..., None]).sum(-1, dtype=jnp.int32)

        zero = jnp.zeros((B, T), jnp.int32)
        if total == 1:
            return block(0, zero)
        # blocks that are not visited hold the lowest key there is
        # (-inf): they add to a count only while the bound is below it
        unseen = pred(_ukey(jnp.float32(-jnp.inf)), bound) \
            * jnp.asarray((total - n) * cb, jnp.int32)
        return jax.lax.fori_loop(0, n, block, zero) + unseen

    def bit(i, v):
        cand = v | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count(jnp.greater_equal, cand) >= k, cand, v)

    v = jax.lax.fori_loop(0, 32, bit, jnp.zeros((B, T), jnp.uint32))
    return v, k - count(jnp.greater, v)


def _attend_masked(q, k_rows, v_rows, ukeys, start, *, groups, topk,
                   key_block):
    """q [B, T, H, Dh] (normalised, rotated, scaled), k_rows / v_rows [B,
    S, G * Dh], ukeys [B, T, S]. Returns ([B, T, H, Dh], keys selected
    and seen [] int32)."""
    B, T, H, Dh = q.shape
    S = k_rows.shape[1]
    G, R = groups, H // groups
    dt = q.dtype
    bk = _tile(S, key_block)
    total = S // bk
    with jax.named_scope("dsa.select"):
        v, room = kth_largest(ukeys, min(topk, S), start)
    q5 = q.reshape(B, T, G, R, Dh)
    qpos = start.astype(jnp.int32)[:, None] + jnp.arange(T)[None, :]
    # tri[i, j] = 1 where i < j: eq @ tri counts the equal keys before j
    tri = jnp.triu(jnp.ones((bk, bk), jnp.bfloat16), 1)

    def block(j, carry):
        m, l, acc, ties, picked = carry
        kb = jax.lax.dynamic_slice_in_dim(k_rows, j * bk, bk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v_rows, j * bk, bk, axis=1)
        u = jax.lax.dynamic_slice_in_dim(ukeys, j * bk, bk, axis=2)
        eq = u == v[..., None]
        before = jnp.einsum("btk,kj->btj", eq.astype(jnp.bfloat16), tri,
                            preferred_element_type=jnp.float32)
        sel = (u > v[..., None]) | (
            eq & (before.astype(jnp.int32) + ties[..., None]
                  < room[..., None]))
        kpos = j * bk + jnp.arange(bk)
        sel &= kpos[None, None, :] <= qpos[..., None]
        s = jnp.einsum("btgrd,bkgd->bgrtk", q5,
                       kb.reshape(B, bk, G, Dh).astype(dt),
                       preferred_element_type=jnp.float32)
        s = jnp.where(sel[:, None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum("bgrtk,bkgd->bgrtd", p.astype(dt),
                        vb.reshape(B, bk, G, Dh).astype(dt),
                        preferred_element_type=jnp.float32)
        return (m_new, l * alpha + p.sum(-1), acc * alpha[..., None] + pv,
                ties + eq.sum(-1, dtype=jnp.int32),
                picked + sel.sum(dtype=jnp.int32))

    init = (jnp.full((B, G, R, T), _NEG, jnp.float32),
            jnp.zeros((B, G, R, T), jnp.float32),
            jnp.zeros((B, G, R, T, Dh), jnp.float32),
            jnp.zeros((B, T), jnp.int32), jnp.zeros((), jnp.int32))
    with jax.named_scope("dsa.attend"):
        if total == 1:
            _, l, acc, _, picked = block(0, init)
        else:
            # a query's first visible tiles may hold none of its keys:
            # what they add under the mask's floor is wiped (alpha = 0)
            # by the first tile that holds one
            n = _visible_tiles(start, T, bk, total)
            _, l, acc, _, picked = jax.lax.fori_loop(0, n, block, init)
        out = (acc / l[..., None]).astype(dt)            # [B, G, R, T, Dh]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh), picked


def _attend_gather(q, k_rows, v_rows, scores, *, groups, topk):
    """q [B, 1, H, Dh], scores [B, S] float32 (-inf where unseen).
    Returns ([B, 1, H, Dh], keys selected and seen [] int32)."""
    B, _, H, Dh = q.shape
    S = k_rows.shape[1]
    G, R = groups, H // groups
    dt = q.dtype
    k = min(topk, S)
    with jax.named_scope("dsa.select"):
        top, idx = jax.lax.top_k(scores, k)              # [B, k]
        seen = top > -jnp.inf
    with jax.named_scope("dsa.attend"):
        kb = jnp.take_along_axis(k_rows, idx[..., None], axis=1)
        vb = jnp.take_along_axis(v_rows, idx[..., None], axis=1)
        s = jnp.einsum("bgrd,bkgd->bgrk", q.reshape(B, G, R, Dh),
                       kb.reshape(B, k, G, Dh).astype(dt),
                       preferred_element_type=jnp.float32)
        s = jnp.where(seen[:, None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(dt),
                         vb.reshape(B, k, G, Dh).astype(dt),
                         preferred_element_type=jnp.float32).astype(dt)
    return out.reshape(B, 1, H, Dh), seen.sum(dtype=jnp.int32)


def indexed_attention(query, index_query, index_weight, cache, start, *,
                      kv_heads, topk, scale, key_block=KEY_BLOCK):
    """Attention of query [B, T, H, Dh] over the `topk` rows of an
    `IndexedKVCache` that the indexer (index_query [B, T, Hi, Di],
    index_weight [B, T, Hi]) scores highest for each query; `start` ([B]
    int32) is the position of each slot's first query. The form is
    `indexed_attend_plan`'s. Returns (ctx [B, T, H, Dh], keys [2] int32:
    the (query, key) pairs visible and the pairs selected).
    Inference-only."""
    T, S = int(query.shape[1]), int(cache.k.shape[1])
    form = indexed_attend_plan(T, S, key_block)[0]

    def f(q, qi, w, kr, vr, ir, st):
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        qpos = st.astype(jnp.int32)[:, None] + jnp.arange(T)[None, :]
        visible = jnp.minimum(qpos + 1, S).sum(dtype=jnp.int32)
        if form == "gather":
            with jax.named_scope("dsa.index"):
                s = _score_tile(qi, w.astype(jnp.float32), ir)[:, 0]
                s = jnp.where(jnp.arange(S)[None, :] > qpos, -jnp.inf, s)
            out, picked = _attend_gather(q, kr, vr, s, groups=kv_heads,
                                         topk=topk)
        else:
            with jax.named_scope("dsa.index"):
                u = index_scores(qi, w, ir, st, key_block)
            out, picked = _attend_masked(
                q, kr, vr, u, st, groups=kv_heads, topk=topk,
                key_block=key_block)
        return out, jnp.stack([visible, picked])

    return AG.apply_nondiff(f, (query, index_query, index_weight, cache.k,
                                cache.v, cache.idx, start))


#: a wide counter's low limb holds this many bits
_LIMB = 30


def advance_wide(counter, amount):
    """Add int32 `amount` [...] (each under 2^30) to a counter [..., 2]
    int32 of (high, low) limbs, low < 2^30: a count that int32 would wrap
    (one 16k prompt is 1.3e8 visible pairs a layer) stays exact to
    2^61."""
    low = counter[..., 1] + amount
    return jnp.stack([counter[..., 0] + (low >> _LIMB),
                      low & ((1 << _LIMB) - 1)], -1)


def read_wide(counter):
    """A host copy of a wide counter [..., 2] -> int64 numpy [...]."""
    import numpy as np

    c = np.asarray(counter).astype(np.int64)
    return (c[..., 0] << _LIMB) + c[..., 1]
