"""The plain reference: GPT-2's forward pass, loss, gradients and AdamW in
straightforward `jax.numpy`, float32, every matmul at `highest`.

It imports nothing of the program and takes nothing the program made:
its weights come from `weights.stacked` and the seed. The departures from
the published model that the program forces (untied head with a bias,
the exact erf GELU, the (3, head, head_dim) order of the fused QKV
outputs) are followed here and listed in each configuration file.

`precision` selects the arithmetic:

* ``highest`` — the reference itself;
* ``bf16``    — the control of a float32 serving cell: weights and
  activations in bfloat16 (norm and softmax statistics in float32);
* ``fp8``     — the control of a bf16-amp training cell: both operands of
  every matmul rounded to float8_e4m3 under a per-tensor scale.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from . import weights as W

_HI = jax.lax.Precision.HIGHEST


def _qdq_fp8(x):
    """Round to float8_e4m3fn under a per-tensor absmax scale; the
    gradient passes straight through."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    if precision == "highest":
        return jnp.matmul(x, w, precision=_HI)
    if precision == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)
    if precision == "fp8":
        return jnp.matmul(_qdq_fp8(x), _qdq_fp8(w), precision=_HI)
    raise ValueError(f"unknown precision {precision!r}")


def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _block(h, lw, heads, eps, precision):
    """One pre-LN decoder block. h [B, S, D]; lw: this layer's leaves."""
    B, S, D = h.shape
    dh = D // heads
    x = _ln(h, lw["ln1.weight"], lw["ln1.bias"], eps)
    qkv = _mm(x, lw["attn.qkv.weight"], precision) \
        + lw["attn.qkv.bias"].astype(h.dtype)
    qkv = qkv.reshape(B, S, 3, heads, dh).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]                      # [B, H, S, dh]
    if precision == "bf16":
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
    else:
        qq, kk = (_qdq_fp8(q), _qdq_fp8(k)) if precision == "fp8" \
            else (q, k)
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk, precision=_HI)
    s = s * dh ** -0.5
    pos = jnp.arange(S)
    s = jnp.where(pos[None, :] > pos[:, None], -1e30, s)
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    if precision == "bf16":
        ctx = jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), v,
                         preferred_element_type=jnp.float32
                         ).astype(jnp.bfloat16)
    else:
        pp, vv = (_qdq_fp8(p), _qdq_fp8(v)) if precision == "fp8" \
            else (p, v)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", pp, vv, precision=_HI)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    a = _mm(ctx, lw["attn.out_proj.weight"], precision) \
        + lw["attn.out_proj.bias"].astype(h.dtype)
    h = h + a
    x = _ln(h, lw["ln2.weight"], lw["ln2.bias"], eps)
    m = _mm(x, lw["fc1.weight"], precision) + lw["fc1.bias"].astype(h.dtype)
    m = jax.nn.gelu(m, approximate=False)
    return h + _mm(m, lw["fc2.weight"], precision) \
        + lw["fc2.bias"].astype(h.dtype)


def hidden(params, ids, *, heads, eps, precision="highest"):
    """Final-LayerNorm hidden state [B, S, D] of token ids [B, S]."""
    S = ids.shape[1]
    act = jnp.bfloat16 if precision == "bf16" else jnp.float32
    h = (params["embed.weight"][ids]
         + params["pos_embed.weight"][:S][None]).astype(act)
    layers = {n: params[f"blocks.{n}"] for n in W.BLOCK_NAMES}

    @jax.checkpoint
    def body(h, lw):
        return _block(h, lw, heads, eps, precision), None

    h, _ = jax.lax.scan(body, h, layers)
    return _ln(h, params["ln_f.weight"], params["ln_f.bias"], eps)


def logits(params, ids, *, heads, eps, precision="highest"):
    h = hidden(params, ids, heads=heads, eps=eps, precision=precision)
    out = _mm(h, params["head.weight"], precision)
    return out.astype(jnp.float32) + params["head.bias"]


def _sum_token_loss(params, ids, labels, *, heads, eps, precision):
    lg = logits(params, ids, heads=heads, eps=eps, precision=precision)
    lse = jax.nn.logsumexp(lg, -1)
    got = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - got)


# ---------------------------------------------------------------------------
# serving: teacher-forced gaps
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gap_fn(heads: int, eps: float, control: str):
    def f(params, ids, tokens, first, n):
        """ids [1, S] = prompt + served tokens, right-padded; the served
        tokens sit at positions first+1 .. first+n, so the logits that
        chose them are rows first .. first+n-1."""
        ref = logits(params, ids, heads=heads, eps=eps)[0]
        S = ref.shape[0]
        rows = jnp.clip(first + jnp.arange(tokens.shape[0]), 0, S - 1)
        live = jnp.arange(tokens.shape[0]) < n
        r = ref[rows]
        top = r.max(-1)
        gap = top - jnp.take_along_axis(r, tokens[:, None], 1)[:, 0]
        if control:
            low = logits(params, ids, heads=heads, eps=eps,
                         precision=control)[0][rows]
            cgap = top - jnp.take_along_axis(
                r, low.argmax(-1)[:, None], 1)[:, 0]
        else:
            cgap = jnp.zeros_like(gap)
        spread = r.std(-1)
        z = jnp.zeros_like(gap)
        return (jnp.where(live, gap, z), jnp.where(live, cgap, z),
                jnp.where(live, spread, z))

    return jax.jit(f)


def served_gaps(params, prompt, tokens, *, cfg, pad_to, control=None):
    """For one served request: per served token, how far its logit lies
    below the reference's best at that position (`gap`), the same for
    the token the lower precision `control` puts first (`control_gap`),
    and the standard deviation of the reference's logits there."""
    import numpy as np

    s = W.sizes(cfg)
    n0, n = len(prompt), len(tokens)
    seq = np.zeros((1, pad_to), np.int32)
    seq[0, :n0] = prompt
    seq[0, n0:n0 + n] = tokens
    tok = np.zeros((pad_to,), np.int32)
    tok[:n] = tokens
    gap, cgap, spread = _gap_fn(s["heads"], s["eps"], control)(
        params, jnp.asarray(seq), jnp.asarray(tok),
        jnp.asarray(n0 - 1, jnp.int32), jnp.asarray(n, jnp.int32))
    return (np.asarray(gap)[:n], np.asarray(cgap)[:n],
            np.asarray(spread)[:n])


# ---------------------------------------------------------------------------
# training: three AdamW steps
# ---------------------------------------------------------------------------


def leaf_norms(tree: dict):
    """L2 norm of every leaf of a stacked tree (the fused QKV leaves as
    their three projections): name -> scalar, stacked leaves give one
    norm a layer."""
    out = {}
    for name, a in W.split_fused(tree).items():
        if name.startswith("blocks."):
            out[name] = jnp.sqrt(jnp.sum(
                a.astype(jnp.float32) ** 2, axis=tuple(range(1, a.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2))
    return out


def flat_norms(norms: dict) -> dict:
    """`leaf_norms` output -> {program leaf name: float}."""
    import numpy as np

    out = {}
    for name, v in norms.items():
        v = np.asarray(v)
        if name.startswith("blocks."):
            leaf = name[len("blocks."):]
            for i, x in enumerate(v):
                out[f"blocks.{i}.{leaf}"] = float(x)
        else:
            out[name] = float(v)
    return out


@functools.lru_cache(maxsize=None)
def _train_step_fn(heads: int, eps: float, precision: str, row_block: int,
                   opt_json: str):
    opt = json.loads(opt_json)
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    oeps, wd = opt["epsilon"], opt["weight_decay"]

    def step(params, m, v, t, ids, labels):
        B, S = ids.shape
        nb = B // row_block
        ids_b = ids.reshape(nb, row_block, S)
        lab_b = labels.reshape(nb, row_block, S)
        block = jax.checkpoint(functools.partial(
            _sum_token_loss, heads=heads, eps=eps, precision=precision))

        def body(carry, xs):
            loss, grads = carry
            l, g = jax.value_and_grad(block)(params, xs[0], xs[1])
            return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero), (ids_b, lab_b))
        n = jnp.float32(B * S)
        loss = loss / n
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        gnorm = leaf_norms(grads)

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            return p - lr * (mhat / (jnp.sqrt(vhat) + oeps) + wd * p), m, v

        new = {k: upd(params[k], grads[k], m[k], v[k]) for k in params}
        return (loss, gnorm, {k: x[0] for k, x in new.items()},
                {k: x[1] for k, x in new.items()},
                {k: x[2] for k, x in new.items()})

    return jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _delta_fn():
    return jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))


def train_steps(cfg, seed, batches, opt, *, precision="highest",
                row_block=2, rows=None):
    """Follow the first `len(batches)` AdamW steps from the seed's
    weights. `batches` is a list of (ids, labels) int32 arrays [B, S];
    `rows` (a slice) plants the half-batch fault. Returns the losses,
    the first step's per-leaf gradient norms and the per-leaf norms of
    the parameters' change over all steps, the last two as
    {program leaf name: float}."""
    s = W.sizes(cfg)
    params = W.make(cfg, seed, form="stacked")
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    fn = _train_step_fn(s["heads"], s["eps"], precision, row_block,
                        json.dumps(opt, sort_keys=True))
    losses, g1 = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        loss, gnorm, params, m, v = fn(
            params, m, v, jnp.float32(t), jnp.asarray(ids),
            jnp.asarray(labels))
        losses.append(float(loss))
        if t == 1:
            g1 = flat_norms(gnorm)
    del m, v
    delta = flat_norms(_delta_fn()(params, W.make(cfg, seed,
                                                  form="stacked")))
    return losses, g1, delta
