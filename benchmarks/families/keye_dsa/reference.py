"""The plain reference of the `keye_dsa` family: the forward pass of a
grouped-query decoder with a learned sparse-attention indexer
(DeepSeek-Sparse-Attention) and softmax top-k routed experts, in
straightforward `jax.numpy`, float32, every matmul at `highest`. No cache,
no batching, no kernels, `jax.lax.top_k` for both selections, the held
experts a plain loop. It imports nothing of the program and takes nothing
the program made: its weights come from `weights.make` and the seed
(bfloat16-valued; each is widened where it is used).

The layer (x = RMSNorm(h), eps from the configuration; t a query
position, s <= t a cached one; the configuration file lists what is
assumed):

* attention: [q | k | v] = W_qkv x as 32 heads of 128 and 4 + 4 heads of
  128, no biases; q and k pass an RMSNorm over each head's values with a
  learned gain, then the rotary rotation at theta over the whole head
  (halves (i, i + 64) turn together); query head j reads K/V head j // 8;
  scale 128^-1/2;
* indexer: [q_idx | k_idx | w] = W_idx x as 16 heads of 64, one key of 64
  (LayerNorm with gain and bias) and 16 weights; queries and key rotated
  over their 64 values; I(t, s) = sum_j w[t, j] relu(q_idx[t, j] .
  k_idx[s]); S_t = `jax.lax.top_k` of I(t, .) over s <= t, 2,048 of them
  (all while t < 2,048; a sum of -0.0 counts as 0.0);
* o[t, j] = softmax over S_t of (q[t, j] . k[s, j // 8] / sqrt(128)) times
  v; h += W_o o;
* experts: p = softmax(W_g x) over the 128 logits, the top 8, weights p_e
  over the chosen eight's sum; h += the sum over the chosen experts held
  here of w_e W_down(silu(W_gate x) * W_up x): this chip's share, which
  goes on to the next layer;
* a final RMSNorm and an untied head.

Attention runs a block of queries at a time (their scores against every
position, `top_k`, the gathered rows), so that a 32k request fits beside
the weights, and only over the blocks that hold a position asked for.

`precision` selects the arithmetic: ``highest`` is the reference itself;
``fp8`` is the control of a bfloat16 serving cell, both operands of every
matmul rounded to float8_e4m3 under a per-tensor scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

_HI = jax.lax.Precision.HIGHEST
_NEG = -1e30


def _qdq_fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _ein(spec, a, b, precision):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _qdq_fp8(a), _qdq_fp8(b)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rotate(x, pos, base):
    """x [S, ..., dim]: halves (i, i + dim / 2) turn together."""
    dim = x.shape[-1]
    inv = (1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
           ).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(h, p, b, s, precision):
    """h [S, D] (normalised) -> q [S, H, Dh], k, v [S, G, Dh], q_idx [S,
    Hi, Di], k_idx [S, Di], w [S, Hi]; normalised and rotated."""
    S = h.shape[0]
    H, G, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    Hi, Di = s["index_heads"], s["index_dim"]
    pos = jnp.arange(S)
    qkv = _ein("sd,de->se", h, p[b + "attn.qkv_proj"], precision)
    q = qkv[:, :H * Dh].reshape(S, H, Dh)
    k = qkv[:, H * Dh:(H + G) * Dh].reshape(S, G, Dh)
    v = qkv[:, (H + G) * Dh:].reshape(S, G, Dh)
    q = _rotate(_rms(q, p[b + "attn.q_norm.weight"], s["eps"]), pos,
                s["rope_base"])
    k = _rotate(_rms(k, p[b + "attn.k_norm.weight"], s["eps"]), pos,
                s["rope_base"])
    ix = _ein("sd,de->se", h, p[b + "attn.index_proj"], precision)
    qi = _rotate(ix[:, :Hi * Di].reshape(S, Hi, Di), pos, s["rope_base"])
    ki = _rotate(_layer_norm(ix[:, Hi * Di:Hi * Di + Di],
                             p[b + "attn.index_norm_weight"],
                             p[b + "attn.index_norm_bias"], s["eps"]),
                 pos, s["rope_base"])
    return q, k, v, qi, ki, ix[:, Hi * Di + Di:]


def index_scores(qi, w, ki, qpos, precision):
    """qi [Q, Hi, Di], w [Q, Hi], ki [S, Di], qpos [Q] -> I [Q, S]
    float32, -inf where s > t."""
    r = jax.nn.relu(_ein("qhd,sd->qhs", qi, ki, precision))
    i = (r * w[..., None]).sum(1)
    i = jnp.where(i == 0, 0.0, i)               # -0.0 is 0.0
    return jnp.where(jnp.arange(ki.shape[0])[None, :] > qpos[:, None],
                     -jnp.inf, i)


def attend_selected(q, k, v, idx, seen, scale, precision):
    """q [Q, H, Dh], k, v [S, G, Dh], idx [Q, n] the selected positions,
    seen [Q, n] which of them the query can see -> [Q, H, Dh]."""
    Q, H, Dh = q.shape
    G = k.shape[1]
    kg, vg = k[idx], v[idx]                      # [Q, n, G, Dh]
    sc = _ein("qgrd,qngd->qgrn", q.reshape(Q, G, H // G, Dh), kg,
              precision) * scale
    sc = jnp.where(seen[:, None, None, :], sc, _NEG)
    pr = jax.nn.softmax(sc, axis=-1)
    return _ein("qgrn,qngd->qgrd", pr, vg, precision).reshape(Q, H, Dh)


def attention(h, p, b, s, precision, upto=None):
    """h [S, D] (already normalised) -> [S, D]; with `upto`, only the
    query blocks that hold a position below it are computed (the rest
    stay zero: no earlier position reads them)."""
    S = h.shape[0]
    H, Dh = s["heads"], s["head_dim"]
    q, k, v, qi, ki, w = project(h, p, b, s, precision)
    n = min(s["topk"], S)
    bq = s["attn_block"] if S % s["attn_block"] == 0 else S

    def block(i, out):
        at = i * bq
        qpos = at + jnp.arange(bq)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, bq, 0)
        top, idx = jax.lax.top_k(
            index_scores(cut(qi), cut(w), ki, qpos, precision), n)
        o = attend_selected(cut(q), k, v, idx, top > -jnp.inf,
                            Dh ** -0.5, precision)
        return jax.lax.dynamic_update_slice_in_dim(out, o, at, 0)

    blocks = S // bq if upto is None else (upto + bq - 1) // bq
    ctx = jax.lax.fori_loop(0, blocks, block,
                            jnp.zeros((S, H, Dh), jnp.float32))
    return _ein("se,ed->sd", ctx.reshape(S, H * Dh), p[b + "attn.o_proj"],
                precision)


def _ffn(x, w_in, w_out, precision):
    g, u = jnp.split(_ein("sd,df->sf", x, w_in, precision), 2, axis=-1)
    return _ein("sf,fd->sd", jax.nn.silu(g) * u, w_out, precision)


def route(h, p, b, s, precision):
    """-> (chosen [S, k] int32, weights [S, k] float32)."""
    pr = jax.nn.softmax(_ein("sd,de->se", h, p[b + "mlp.gate"], precision),
                        axis=-1)
    chosen, idx = jax.lax.top_k(pr, s["top_k"])
    return idx, chosen / chosen.sum(-1, keepdims=True)


def experts(h, p, b, s, precision, first_held=0):
    """The expert layer's share on a chip that holds experts `first_held
    ..`: a plain loop over the held experts. Each expert computes the
    tokens that chose it, gathered to a quarter of the sequence (four
    times an even share); a layer whose routing overflows that computes
    every token for every expert instead: the same sum either way.
    Returns (y [S, D], chosen [S, k])."""
    S, D = h.shape
    idx, w = route(h, p, b, s, precision)
    w_in, w_out = p[b + "mlp.w_in"], p[b + "mlp.w_out"]
    held = w_in.shape[0]
    local = idx - first_held

    def weight_of(e):
        return jnp.where(local == e, w, 0.0).sum(-1)          # [S]

    cap = max(S // 4, 1)

    def gathered(_):
        def one(e, y):
            we = weight_of(e)
            take = jnp.argsort(we == 0, stable=True)[:cap]    # choosers first
            out = _ffn(h[take], w_in[e], w_out[e], precision)
            return y.at[take].add(out * we[take][:, None])

        return jax.lax.fori_loop(0, held, one, jnp.zeros((S, D), jnp.float32))

    def every(_):
        def one(e, y):
            return y + _ffn(h, w_in[e], w_out[e], precision) \
                * weight_of(e)[:, None]

        return jax.lax.fori_loop(0, held, one, jnp.zeros((S, D), jnp.float32))

    counts = jnp.zeros((held + 1,), jnp.int32).at[
        jnp.where((local >= 0) & (local < held), local, held)].add(1)
    return jax.lax.cond(counts[:held].max() <= cap, gathered, every,
                        None), idx


def hidden(p, ids, s, precision="highest", first_held=0, upto=None):
    """Final-RMSNorm hidden state [S, D] of token ids [S]; with `upto`,
    right of positions below it only."""
    h = p["embedding.weight"][ids].astype(jnp.float32)
    for i in range(s["layers"]):
        b = f"blocks.{i}."
        h = h + attention(_rms(h, p[b + "norm1.weight"], s["eps"]), p, b, s,
                          precision, upto)
        y, _ = experts(_rms(h, p[b + "norm2.weight"], s["eps"]), p, b, s,
                       precision, first_held)
        h = h + y
    return _rms(h, p["norm_f.weight"], s["eps"])


def logits(p, ids, s, precision="highest", rows=None, **kw):
    """[S, V] float32 logits of token ids [S] (of `rows` only, if given)."""
    h = hidden(p, ids, s, precision, **kw)
    if rows is not None:
        h = h[rows]
    return _ein("sd,dv->sv", h, p["head"], precision)


def _sizes(cfg, attn_block=128):
    s = W.sizes(cfg)
    s["attn_block"] = attn_block
    return s


# ---------------------------------------------------------------------------
# serving: teacher-forced gaps
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_key: str, control: str, n_rows: int):
    import json

    s = _sizes(json.loads(cfg_key))

    def f(p, ids, tokens, first, n):
        """ids [S] = prompt + served tokens, right-padded; the served
        tokens sit at positions first+1 .. first+n, so the logits that
        chose them are rows first .. first+n-1."""
        S = ids.shape[0]
        rows = jnp.clip(first + jnp.arange(n_rows), 0, S - 1)
        live = jnp.arange(n_rows) < n
        r = logits(p, ids, s, rows=rows, upto=first + n)
        top = r.max(-1)
        gap = top - jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]
        if control:
            low = logits(p, ids, s, control, rows=rows, upto=first + n)
            cgap = top - jnp.take_along_axis(
                r, low.argmax(-1)[:, None], 1)[:, 0]
        else:
            cgap = jnp.zeros_like(gap)
        z = jnp.zeros_like(gap)
        return (jnp.where(live, gap, z), jnp.where(live, cgap, z),
                jnp.where(live, r.std(-1), z))

    return jax.jit(f)


def served_gaps(params, prompt, tokens, *, cfg, pad_to, control=None):
    """For one served request: per served token, how far its logit lies
    below the reference's best at that position (`gap`), the same for
    the token the lower precision `control` puts first (`control_gap`),
    and the standard deviation of the reference's logits there."""
    import json

    keep = {k: v for k, v in cfg.items()
            if k not in ("name", "source", "why", "assumed", "departures",
                         "deployment", "reduced", "family")}
    n0, n = len(prompt), len(tokens)
    n_rows = 1 << max(int(n) - 1, 0).bit_length()
    seq = np.zeros((pad_to,), np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n] = tokens
    tok = np.zeros((n_rows,), np.int32)
    tok[:n] = tokens
    fn = _gap_fn(json.dumps(keep, sort_keys=True), control or "", n_rows)
    gap, cgap, spread = fn(
        params, jnp.asarray(seq), jnp.asarray(tok),
        jnp.asarray(n0 - 1, jnp.int32), jnp.asarray(n, jnp.int32))
    return (np.asarray(gap)[:n], np.asarray(cgap)[:n],
            np.asarray(spread)[:n])
