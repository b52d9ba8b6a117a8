"""Functionals of a latent-attention, routed-expert decoder.

The parts a DeepSeek-V2-shaped decoder adds to what `nn.functional` had:
RMSNorm, the rotary rotation with the yarn frequency blend, the gated-SiLU
product, attention over a *latent* cache (one `[c | k_rope]` row a token
instead of per-head K and V) in its two forms, and dropless top-k routing
over the experts a chip holds.

Two forms of the same attention, `latent_attend_plan` chooses:

* ``expanded`` — every cached row is up-projected to its per-head key and
  value (`W_ukv`), scores are (q_nope . k_nope + q_rope . k_rope). The
  cheaper form per (query, key) pair (192 + 128 a head), so a prompt or a
  chunk takes it.
* ``absorbed`` — the up-projection moves onto the query (q~ = W_uk^T
  q_nope) and the output (W_uv u), and all heads score against the one
  576-wide row: 576 + 512 a pair but no per-row up-projection, so a
  decode step (one query a slot, every cached row read once) takes it.

Either runs ``blockwise`` over the keys (running maximum and sum, only
the blocks at or below the furthest query are visited) when the key
length is a whole number of blocks, else ``dense`` over all keys as one
block. Softmax statistics, the router's scores, its top-k and the
weights' normalisation are float32; matmuls accumulate in float32.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core import autograd as AG

__all__ = ["rms_norm", "swiglu", "yarn_inv_freq", "yarn_mscale",
           "LatentCache",
           "latent_cache_update", "latent_attend_plan", "latent_attention",
           "route_top_k", "routed_experts"]

#: the cache of one latent-attention layer: `rows` [B, cap, kv_rank +
#: rope_dim], the normalised latent beside the rotated shared key
LatentCache = collections.namedtuple("LatentCache", ["rows"])

#: keys a block of the blockwise forms
KEY_BLOCK = 512

_NEG = -1e30


# ---------------------------------------------------------------------------
# norm, activation, rotary
# ---------------------------------------------------------------------------


def _rms(a, w, eps):
    a32 = a.astype(jnp.float32)
    y = a32 * jax.lax.rsqrt(jnp.mean(a32 * a32, -1, keepdims=True) + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(a.dtype)


def rms_norm(x, weight=None, epsilon=1e-6):
    """x / sqrt(mean(x^2) + eps) * weight over the last axis; the
    statistics in float32, the result in x's dtype."""
    if weight is None:
        return AG.apply(lambda a: _rms(a, None, epsilon), (x,),
                        name="rms_norm")
    return AG.apply(lambda a, w: _rms(a, w, epsilon), (x, weight),
                    name="rms_norm")


def swiglu(gate_up):
    """silu(gate) * up of a fused [..., 2F] projection -> [..., F]."""
    def f(a):
        g, u = jnp.split(a, 2, axis=-1)
        return (jax.nn.silu(g.astype(jnp.float32))
                * u.astype(jnp.float32)).astype(a.dtype)

    return AG.apply(f, (gate_up,), name="swiglu")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """yarn's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float = 10000.0, factor: float = 1.0,
                  original_max_position: int = 4096, beta_fast: float = 32,
                  beta_slow: float = 1) -> np.ndarray:
    """Inverse frequencies [dim / 2] of a rotary embedding under the
    `deepseek_yarn` blend: dimensions that turn more than `beta_fast`
    times over the original context keep their frequency, those that turn
    less than `beta_slow` times are divided by `factor`, and a linear ramp
    joins the two. `factor` 1 gives the plain rotary frequencies."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(original_max_position / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                 # 1 where the frequency is kept
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def _rope(x, pos, inv_freq, scale):
    """x [B, T, ..., dim], pos [B, T]: the halves (i, i + dim/2) turn
    together (`rotate_half`); angles in float32."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# the latent cache and attention over it
# ---------------------------------------------------------------------------


def latent_cache_update(cache: LatentCache, new_rows, pos) -> LatentCache:
    """Write the [B, T, W] new rows into the [B, cap, W] row store at
    per-slot positions ``pos`` ([B] int32): one vmapped
    dynamic_update_slice, no shape change. Inference-only."""
    def write(c, u, p):
        return jax.vmap(
            lambda cb, ub, pb: jax.lax.dynamic_update_slice_in_dim(
                cb, ub.astype(cb.dtype), pb, axis=0)
        )(c, u, jnp.asarray(p, jnp.int32))

    return LatentCache(AG.apply_nondiff(write, (cache.rows, new_rows, pos)))


def latent_attend_plan(q_len: int, k_len: int, key_block: int = KEY_BLOCK):
    """(form, scores) of `latent_attention` for `q_len` queries a slot
    against `k_len` rows: form ``absorbed`` for a decode step's single
    query, ``expanded`` otherwise; scores ``blockwise`` when the rows are
    more than one whole block, else ``dense``."""
    form = "absorbed" if q_len == 1 else "expanded"
    blockwise = k_len > key_block and k_len % key_block == 0
    return form, "blockwise" if blockwise else "dense"


def _attend(q, rows, w_ukv, start, *, kv_rank, nope, scale, form, key_block,
            visit_all=False):
    """q [B, T, H, nope + rope] (normalised and rotated), rows [B, S,
    kv_rank + rope], w_ukv [kv_rank, H, nope + v], start [B]: slot b's
    query t sits at position start[b] + t and sees rows 0..start[b] + t.
    Returns [B, T, H, v]."""
    B, T, H, _ = q.shape
    S = rows.shape[1]
    v_dim = w_ukv.shape[-1] - nope
    dt = q.dtype
    bk = key_block if (S > key_block and S % key_block == 0) else S
    total = S // bk
    q = (q.astype(jnp.float32) * scale).astype(dt)
    if form == "absorbed":
        # the up-projection moves onto the query: q~ = W_uk^T q_nope
        qt = jnp.einsum("bthn,chn->bthc", q[..., :nope], w_ukv[..., :nope],
                        preferred_element_type=jnp.float32).astype(dt)
        q = jnp.concatenate([qt, q[..., nope:]], -1)
        acc_w = kv_rank
    else:
        acc_w = v_dim
    qpos = start.astype(jnp.int32)[:, None] + jnp.arange(T)[None, :]

    def block(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(rows, j * bk, bk, axis=1)
        if form == "absorbed":
            s = jnp.einsum("bthc,bkc->bhtk", q, blk,
                           preferred_element_type=jnp.float32)
        else:
            kv = jnp.einsum("bkc,chd->bkhd", blk[..., :kv_rank], w_ukv,
                            preferred_element_type=jnp.float32).astype(dt)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    blk[:, :, None, kv_rank:], (B, bk, H, q.shape[-1] - nope)
                )], -1)
            s = jnp.einsum("bthd,bkhd->bhtk", q, k,
                           preferred_element_type=jnp.float32)
        kpos = j * bk + jnp.arange(bk)
        s = jnp.where(kpos[None, None, None, :] > qpos[:, None, :, None],
                      _NEG, s)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(-1)
        if form == "absorbed":
            pv = jnp.einsum("bhtk,bkc->bhtc", p.astype(dt),
                            blk[..., :kv_rank],
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bhtk,bkhd->bhtd", p.astype(dt), kv[..., nope:],
                            preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha[..., None] + pv

    init = (jnp.full((B, H, T), _NEG, jnp.float32),
            jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, acc_w), jnp.float32))
    if total == 1:
        _, l, acc = block(0, init)
    else:
        # only the blocks at or below the furthest query hold a visible row
        n = total if visit_all else jnp.minimum(
            (jnp.max(start).astype(jnp.int32) + T + bk - 1) // bk, total)
        _, l, acc = jax.lax.fori_loop(0, n, block, init)
    out = (acc / l[..., None]).astype(dt)                   # [B, H, T, w]
    if form == "absorbed":
        return jnp.einsum("bhtc,chv->bthv", out, w_ukv[..., nope:],
                          preferred_element_type=jnp.float32).astype(dt)
    return out.transpose(0, 2, 1, 3)


def latent_attention(query, rows, w_ukv, start, *, kv_rank, nope_dim, scale,
                     key_block=KEY_BLOCK, visit_all=False):
    """Causal attention of query [B, T, H, nope + rope] over latent rows
    [B, S, kv_rank + rope] with the up-projection `w_ukv` [kv_rank, H,
    nope + v]; `start` ([B] int32) is the position of each slot's first
    query. The form is `latent_attend_plan`'s. `visit_all` keeps the trip
    count static (every block is visited), which a differentiable
    whole-prompt forward needs. Returns [B, T, H, v]."""
    form = latent_attend_plan(int(query.shape[1]), int(rows.shape[1]),
                              key_block)[0]

    def f(q, r, w, st):
        with jax.named_scope("mla.attend"):
            return _attend(q, r, w, st, kv_rank=kv_rank, nope=nope_dim,
                           scale=scale, form=form, key_block=key_block,
                           visit_all=visit_all)

    if visit_all:
        st = getattr(start, "_data", start)
        return AG.apply(lambda q, r, w: f(q, r, w, st),
                        (query, rows, w_ukv), name="latent_attention")
    return AG.apply_nondiff(f, (query, rows, w_ukv, start))


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts held here
# ---------------------------------------------------------------------------


#: the scoring rules of `route_top_k`: the router's logits -> scores
SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": lambda z: jax.nn.softmax(z, axis=-1)}


def route_top_k(x, gate_w, bias, top_k: int, scaling: float,
                score: str = "sigmoid"):
    """Raw arrays. x [N, D], gate_w [D, E], bias [E] or None -> (idx
    [N, k] int32, weights [N, k] float32): scores are `score` of x W in
    float32 (``sigmoid`` each logit alone, ``softmax`` over the E
    logits), the chosen set is the k largest of score + bias (the bias
    selects and does not weigh), the weights are scaling * score over the
    chosen scores' sum."""
    s = SCORES[score](jnp.dot(x, gate_w,
                              preferred_element_type=jnp.float32))
    pick = s if bias is None else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(pick, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = scaling * chosen / chosen.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), w


def routed_experts(x, gate_w, bias, w_in, w_out, *, top_k, scaling,
                   first_held=0, score="sigmoid"):
    """Raw arrays. The routed part of an expert layer on a chip that
    holds experts ``first_held .. first_held + H - 1`` of the router's E:
    x [N, D], gate_w [D, E], w_in [H, D, 2F] (gate | up), w_out [H, F, D].
    `score` is `route_top_k`'s scoring rule. Every token is routed over
    all E experts (dropless: no capacity); the result is the sum over the
    chosen experts *held here* of weight * FFN_e(x), what the absent
    experts would add is left out. Returns
    (y [N, D], load [H + 1] int32: the assignments that fell on each held
    expert, and last those routed to experts not held)."""
    N, D = x.shape
    H = w_in.shape[0]
    with jax.named_scope("moe.route"):
        idx, w = route_top_k(x, gate_w, bias, top_k, scaling, score=score)
        local = idx - first_held
        held = (local >= 0) & (local < H)
        group = jnp.where(held, local, H).reshape(-1)        # [N * k]
        order = jnp.argsort(group, stable=True)
        load = jnp.zeros((H + 1,), jnp.int32).at[group].add(1)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
    with jax.named_scope("moe.experts"):
        xs = x[order // top_k]                               # [N * k, D]
        sizes = load[:H]
        h = jax.lax.ragged_dot(xs, w_in, sizes)
        g, u = jnp.split(h, 2, axis=-1)
        a = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        y = jax.lax.ragged_dot(a, w_out, sizes)
        # back to token order; rows of experts not held count for nothing
        y = y[back].reshape(N, top_k, D).astype(jnp.float32)
        y = jnp.where(held[..., None], y * w[..., None], 0.0).sum(1)
    return y.astype(x.dtype), load
