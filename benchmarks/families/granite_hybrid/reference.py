"""The plain reference of the `granite_hybrid` family: the forward pass of
granite-4.0-h's `granitemoehybrid` decoder with no experts, in
straightforward `jax.numpy`, float32, every matmul at `highest`. No cache,
no batching, no kernels, and the state-space layer as its recurrence, one
token after another (`jax.lax.fori_loop`): it shares no code with the
program's chunked form. It imports nothing of the program and takes
nothing the program made: its weights come from `weights.make` and the
seed (bfloat16-valued; each is widened where it is used).

The model (x = RMSNorm(h), eps from the configuration, gain only):

* h_0 = E[ids] * embedding_multiplier;
* each layer: h += r * mixer(RMSNorm(h)), then h += r * mlp(RMSNorm(h)),
  r = residual_multiplier; the mixer is `layer_types`' entry;
* mlp(x) = W_down(silu(x W_gate) * x W_up), [gate | up] one matrix;
* attention: [q | k | v] = x W_qkv as 32 heads of 64 and 8 + 8 heads of
  64, no biases, no position encoding, query head j reads K/V head
  j // 4, scores times attention_multiplier, causal; h += W_o o;
* Mamba-2 (H = 64 heads of P = 64, state N = 128, one group):
  [z | xBC | dt] = x W_in; xBC = silu(conv(xBC) + b), the depthwise
  causal convolution of d_conv taps over zeros before position 0;
  [x | B | C] = xBC; D_t = softplus(dt_t + dt_bias); A = -exp(A_log);
  h_t = exp(D_t A) h_{t-1} + D_t x_t (x) B_t from h_{-1} = 0;
  y_t = h_t C_t + D x_t; out = W_out(RMSNorm(y * silu(z)) * g) over all
  H * P values;
* logits = RMSNorm(h) E^T / logits_scaling (the head tied to the
  embedding).

Each layer is one jitted function called in turn, so a 40-layer stack
compiles three small programs. Attention runs a block of queries at a
time and only over the blocks that hold a position asked for; the
recurrence stops after the last position asked for.

`precision` selects the arithmetic: ``highest`` is the reference itself;
``fp8`` is the control of a bfloat16 serving cell, both operands of every
matmul rounded to float8_e4m3 under a per-tensor scale.
"""
from __future__ import annotations

import functools
import json

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


def _key(s: dict) -> str:
    return json.dumps(s, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _mlp_fn(key: str, precision: str):
    s = json.loads(key)

    def f(h, g, w_in, w_out):
        gate, up = jnp.split(_ein("sd,df->sf", _rms(h, g, s["eps"]), w_in,
                                  precision), 2, axis=-1)
        return h + s["residual_multiplier"] * _ein(
            "sf,fd->sd", jax.nn.silu(gate) * up, w_out, precision)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _attention_fn(key: str, precision: str):
    s = json.loads(key)
    H, G, Dh = s["heads"], s["kv_heads"], s["head_dim"]

    def f(h, g, w_qkv, w_o, upto):
        S = h.shape[0]
        qkv = _ein("sd,de->se", _rms(h, g, s["eps"]), w_qkv, precision)
        q = qkv[:, :H * Dh].reshape(S, G, H // G, Dh)
        k = qkv[:, H * Dh:(H + G) * Dh].reshape(S, G, Dh)
        v = qkv[:, (H + G) * Dh:].reshape(S, G, Dh)
        bq = s["attn_block"] if S % s["attn_block"] == 0 else S

        def block(i, out):
            at = i * bq
            qb = jax.lax.dynamic_slice_in_dim(q, at, bq, 0)
            sc = _ein("qgrd,kgd->qgrk", qb, k, precision) \
                * s["attention_multiplier"]
            seen = jnp.arange(S)[None, :] <= (at + jnp.arange(bq))[:, None]
            sc = jnp.where(seen[:, None, None, :], sc, _NEG)
            o = _ein("qgrk,kgd->qgrd", jax.nn.softmax(sc, axis=-1), v,
                     precision)
            return jax.lax.dynamic_update_slice_in_dim(out, o, at, 0)

        ctx = jax.lax.fori_loop(0, (upto + bq - 1) // bq, block,
                                jnp.zeros((S, G, H // G, Dh), jnp.float32))
        return h + s["residual_multiplier"] * _ein(
            "se,ed->sd", ctx.reshape(S, H * Dh), w_o, precision)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _mamba_fn(key: str, precision: str):
    s = json.loads(key)
    Hs, P, N, K = s["ssm_heads"], s["ssm_head_dim"], s["state"], \
        s["conv_kernel"]
    di = Hs * P

    def f(h, g, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, w_out,
          upto):
        S = h.shape[0]
        proj = _ein("sd,de->se", _rms(h, g, s["eps"]), w_in, precision)
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * N], \
            proj[:, 2 * di + 2 * N:]
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
        conv = sum(padded[j:j + S] * conv_w[j].astype(jnp.float32)
                   for j in range(K)) + conv_b.astype(jnp.float32)
        xbc = jax.nn.silu(conv)
        x = xbc[:, :di].reshape(S, Hs, P)
        Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
        dt = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
        A = -jnp.exp(a_log.astype(jnp.float32))

        def step(t, carry):
            state, ys = carry
            state = jnp.exp(dt[t] * A)[:, None, None] * state \
                + (dt[t][:, None] * x[t])[..., None] * Bm[t][None, None, :]
            return state, ys.at[t].set((state * Cm[t][None, None, :]).sum(-1))

        _, y = jax.lax.fori_loop(
            0, upto, step, (jnp.zeros((Hs, P, N), jnp.float32),
                            jnp.zeros((S, Hs, P), jnp.float32)))
        y = (y + d_skip.astype(jnp.float32)[:, None] * x).reshape(S, di)
        y = _rms(y * jax.nn.silu(z), norm_w, s["eps"])
        return h + s["residual_multiplier"] * _ein("se,ed->sd", y, w_out,
                                                   precision)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _head_fn(key: str, precision: str):
    s = json.loads(key)

    def f(h, g, emb, rows):
        return _ein("sd,vd->sv", _rms(h[rows], g, s["eps"]), emb,
                    precision) / s["logits_scaling"]

    return jax.jit(f)


def logits(p, ids, s, precision="highest", rows=None, upto=None):
    """[len(rows), V] float32 logits of token ids [S] at `rows` (all
    positions if None); positions from `upto` on are left out of the
    recurrence and attention (no earlier position reads them)."""
    key = _key(s)
    S = int(ids.shape[0])
    upto = jnp.asarray(S if upto is None else upto, jnp.int32)
    h = p["embedding.weight"][ids].astype(jnp.float32) \
        * s["embedding_multiplier"]
    for i, kind in enumerate(s["layer_types"]):
        b = f"blocks.{i}."
        if kind == "mamba":
            m = b + "attn."
            h = _mamba_fn(key, precision)(
                h, p[b + "norm1.weight"], *(p[m + n] for n in (
                    "in_proj", "conv_weight", "conv_bias", "dt_bias", "A_log",
                    "D", "norm_weight", "out_proj")), upto)
        else:
            h = _attention_fn(key, precision)(
                h, p[b + "norm1.weight"], p[b + "attn.qkv_proj"],
                p[b + "attn.o_proj"], upto)
        h = _mlp_fn(key, precision)(h, p[b + "norm2.weight"],
                                    p[b + "mlp.gate_up"], p[b + "mlp.down"])
    rows = jnp.arange(S) if rows is None else rows
    return _head_fn(key, precision)(h, p["norm_f.weight"],
                                    p["embedding.weight"], rows)


def _sizes(cfg, attn_block=512):
    s = W.sizes(cfg)
    s["attn_block"] = attn_block
    return s


# ---------------------------------------------------------------------------
# serving: teacher-forced gaps
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gap_fn(control: str):
    def f(r, low, tokens, n):
        live = jnp.arange(r.shape[0]) < n
        top = r.max(-1)
        gap = top - jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]
        if low is not None:
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
    and the standard deviation of the reference's logits there. The
    served tokens sit at positions n0 .. n0 + n - 1, so the logits that
    chose them are rows n0 - 1 .. n0 + n - 2."""
    keep = {k: v for k, v in cfg.items()
            if k not in ("name", "source", "why", "assumed", "departures",
                         "deployment", "reduced", "family")}
    s = _sizes(keep)
    n0, n = len(prompt), len(tokens)
    n_rows = 1 << max(int(n) - 1, 0).bit_length()
    seq = np.zeros((pad_to,), np.int32)
    seq[:n0] = prompt
    seq[n0:n0 + n] = tokens
    tok = np.zeros((n_rows,), np.int32)
    tok[:n] = tokens
    rows = jnp.asarray(np.clip(n0 - 1 + np.arange(n_rows), 0, pad_to - 1))
    ids = jnp.asarray(seq)
    r = logits(params, ids, s, rows=rows, upto=n0 + n - 1)
    low = (logits(params, ids, s, control, rows=rows, upto=n0 + n - 1)
           if control else None)
    gap, cgap, spread = _gap_fn(control or "")(
        r, low, jnp.asarray(tok), jnp.asarray(n, jnp.int32))
    return (np.asarray(gap)[:n], np.asarray(cgap)[:n],
            np.asarray(spread)[:n])
