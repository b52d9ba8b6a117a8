"""The plain reference of the `sarvam_mla` family: the forward pass of a
latent-attention (MLA), routed-expert decoder in straightforward
`jax.numpy`, float32, every matmul at `highest`. No cache, no batching,
attention in its expanded (non-absorbed) form, the experts a plain loop
over the ones held. It imports nothing of the program and takes nothing
the program made: its weights come from `weights.make` and the seed
(bfloat16-valued; each is widened where it is used, a block at a time, so
that the 9 GB stay 9 GB on the chip).

The layer equations (the configuration file lists what is assumed):

* pre-norm residual stack, RMSNorm before attention and before the MLP,
  a final RMSNorm, an untied head, no biases;
* attention: q = W_q h, per head RMSNorm over its nope + rope values,
  the rope part rotated; [c_raw | k_r] = W_dkv h, c = RMSNorm(c_raw),
  k_rope = RoPE(k_r) shared by all heads; [k_nope | v] = W_ukv c a head;
  scores (q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-1/2 * m^2,
  causal softmax; the rotary frequencies are the `deepseek_yarn` blend
  and m = 0.1 * mscale_all_dim * ln(factor) + 1;
* layer 0: down(silu(gate h) * up h);
* expert layers: s = sigmoid(W_g h) in float32, the chosen set T the
  top-k of s + bias, weights scaling * s_e / sum_T s; the result is the
  sum over T within the held experts of w_e FFN_e(h), plus FFN_shared(h):
  this chip's share, which goes on to the next layer.

`precision` selects the arithmetic: ``highest`` is the reference itself;
``fp8`` is the control of a bfloat16 serving cell, both operands of every
matmul rounded to float8_e4m3 under a per-tensor scale.
"""
from __future__ import annotations

import functools
import math
import sys

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


def yarn(rope: dict, dim: int):
    """(inverse frequencies [dim / 2], the factor on cos and sin, m)."""
    base, factor = rope["base"], rope["factor"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32), 1.0, 1.0
    orig = rope["original_max_position"]

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                         0.0, 1.0)
    inv = extra / factor * (1.0 - mask) + extra * mask

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0

    m_all = mscale(rope["mscale_all_dim"])
    return inv.astype(np.float32), mscale(rope["mscale"]) / m_all, m_all


def _rotate(x, pos, inv_freq, factor):
    """x [S, ..., dim]: halves (i, i + dim / 2) turn together."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_blocks(q, k, v, scale, block, precision):
    """softmax(q k^T * scale, causal) v in blocks: q [S, H, dq], k [S, H,
    dq], v [S, H, dv]. Query block i meets key blocks 0..i, with a
    running maximum and sum."""
    S, H, dv = v.shape
    bq = block if S % block == 0 else S
    nq = S // bq
    qb = q.reshape(nq, bq, H, -1)

    def one(i, qi):
        qpos = i * bq + jnp.arange(bq)

        def inner(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * bq, bq, 0)
            vj = jax.lax.dynamic_slice_in_dim(v, j * bq, bq, 0)
            s = _ein("qhd,khd->hqk", qi, kj, precision) * scale
            kpos = j * bq + jnp.arange(bq)
            s = jnp.where(kpos[None, None, :] > qpos[None, :, None], _NEG, s)
            m_new = jnp.maximum(m, s.max(-1))
            a = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            return (m_new, l * a + p.sum(-1),
                    acc * a[..., None] + _ein("hqk,khd->hqd", p, vj,
                                              precision))

        init = (jnp.full((H, bq), _NEG, jnp.float32),
                jnp.zeros((H, bq), jnp.float32),
                jnp.zeros((H, bq, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, i + 1, inner, init)
        return (acc / l[..., None]).transpose(1, 0, 2)       # [bq, H, dv]

    out = jax.lax.map(lambda a: one(a[0], a[1]), (jnp.arange(nq), qb))
    return out.reshape(S, H, dv)


def attention(h, p, b, s, precision):
    """h [S, D] (already normalised) -> [S, D]."""
    S = h.shape[0]
    H, nope, rd, vd, r = s["heads"], s["nope"], s["rope"], s["v"], \
        s["kv_rank"]
    inv, factor, m = yarn(s["rope_cfg"], rd)
    pos = jnp.arange(S)
    q = _ein("sd,de->se", h, p[b + "attn.q_proj"], precision)
    q = q.reshape(S, H, nope + rd)
    if s["qk_norm"]:
        q = _rms(q, p[b + "attn.q_norm.weight"], s["eps"])
    q = jnp.concatenate([q[..., :nope],
                         _rotate(q[..., nope:], pos, inv, factor)], -1)
    ckr = _ein("sd,de->se", h, p[b + "attn.kv_down"], precision)
    c = _rms(ckr[:, :r], p[b + "attn.kv_norm.weight"], s["eps"])
    kr = _rotate(ckr[:, r:], pos, inv, factor)
    kv = _ein("sc,ce->se", c, p[b + "attn.kv_up"], precision)
    kv = kv.reshape(S, H, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, None, :], (S, H, rd))], -1)
    ctx = _causal_blocks(q, k, kv[..., nope:],
                         (nope + rd) ** -0.5 * m * m, s["attn_block"],
                         precision)
    return _ein("se,ed->sd", ctx.reshape(S, H * vd), p[b + "attn.o_proj"],
                precision)


def _ffn(x, w_in, w_out, precision):
    g, u = jnp.split(_ein("sd,df->sf", x, w_in, precision), 2, axis=-1)
    return _ein("sf,fd->sd", jax.nn.silu(g) * u, w_out, precision)


def route(h, p, b, s, precision):
    """-> (chosen [S, k] int32, weights [S, k] float32)."""
    sc = jax.nn.sigmoid(_ein("sd,de->se", h, p[b + "mlp.gate"], precision))
    pick = sc
    if s["select_bias"]:
        pick = sc + p[b + "mlp.select_bias"]
    _, idx = jax.lax.top_k(pick, s["top_k"])
    chosen = jnp.take_along_axis(sc, idx, -1)
    return idx, s["scaling"] * chosen / chosen.sum(-1, keepdims=True)


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
    # weight of expert e for token t, 0 where t did not choose e
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
    y = jax.lax.cond(counts[:held].max() <= cap, gathered, every, None)
    if s["shared_ffn"]:
        y = y + _ffn(h, p[b + "mlp.shared.gate_up"],
                     p[b + "mlp.shared.down"], precision)
    return y, idx


def hidden(p, ids, s, precision="highest", first_held=0,
           with_flips=False):
    """Final-RMSNorm hidden state [S, D] of token ids [S]; with
    `with_flips` also, per routed layer and token, whether the chosen set
    changes when the layer's input is rounded to bfloat16 [layers, S]."""
    h = p["embedding.weight"][ids].astype(jnp.float32)
    flips = []
    for i in range(s["layers"]):
        b = f"blocks.{i}."
        h = h + attention(_rms(h, p[b + "norm1.weight"], s["eps"]), p, b, s,
                          precision)
        x = _rms(h, p[b + "norm2.weight"], s["eps"])
        if i < s["dense_layers"]:
            h = h + _ffn(x, p[b + "mlp.gate_up"], p[b + "mlp.down"],
                         precision)
        else:
            y, idx = experts(x, p, b, s, precision, first_held)
            if with_flips:
                low, _ = route(x.astype(jnp.bfloat16), p, b, s, precision)
                flips.append((jnp.sort(idx, -1) != jnp.sort(low, -1)).any(-1))
            h = h + y
    h = _rms(h, p["norm_f.weight"], s["eps"])
    return (h, jnp.stack(flips)) if with_flips else h


def logits(p, ids, s, precision="highest", rows=None, **kw):
    """[S, V] float32 logits of token ids [S] (of `rows` only, if given)."""
    h = hidden(p, ids, s, precision, **kw)
    if rows is not None:
        h = h[rows]
    return _ein("sd,dv->sv", h, p["head"], precision)


def _sizes(cfg, attn_block=1024):
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
        h, flips = hidden(p, ids, s, with_flips=True)
        seen = jnp.arange(S) <= first + n      # the tokens that were served
        flips = (flips & seen).sum() / (flips.shape[0] * seen.sum())
        r = _ein("sd,dv->sv", h[rows], p["head"], "highest")
        top = r.max(-1)
        gap = top - jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]
        if control:
            low = logits(p, ids, s, control, rows=rows)
            cgap = top - jnp.take_along_axis(
                r, low.argmax(-1)[:, None], 1)[:, 0]
        else:
            cgap = jnp.zeros_like(gap)
        z = jnp.zeros_like(gap)
        return (jnp.where(live, gap, z), jnp.where(live, cgap, z),
                jnp.where(live, r.std(-1), z), flips)

    return jax.jit(f)


def served_gaps(params, prompt, tokens, *, cfg, pad_to, control=None):
    """For one served request: per served token, how far its logit lies
    below the reference's best at that position (`gap`), the same for
    the token the lower precision `control` puts first (`control_gap`),
    and the standard deviation of the reference's logits there. Logs
    (stderr) the share of (token, routed layer) whose chosen set changes
    in the reference when the layer's input is rounded to bfloat16: how
    often a near-tied choice may fall the other way in the program."""
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
    gap, cgap, spread, flips = fn(
        params, jnp.asarray(seq), jnp.asarray(tok),
        jnp.asarray(n0 - 1, jnp.int32), jnp.asarray(n, jnp.int32))
    print(f"[reference] request of {n0} + {n} tokens: chosen sets that "
          f"change under a bfloat16 input, share of (token, layer): "
          f"{float(flips):.3g}", file=sys.stderr, flush=True)
    return (np.asarray(gap)[:n], np.asarray(cgap)[:n],
            np.asarray(spread)[:n])
