"""Functionals of a Mamba-2 state-space mixer (the SSD layer of
"Transformers are SSMs", Dao & Gu 2024, with one group of B and C shared
by every head).

    [z | xBC | dt] = x W_in
    xBC            = silu(causal_conv_K(xBC) + b)       depthwise, K taps
    x_t, B_t, C_t  = xBC split                          [H, P], [N], [N]
    D_t            = softplus(dt_t + dt_bias)           [H]
    h_t            = exp(D_t A) h_{t-1} + D_t x_t (x) B_t     [H, P, N]
    y_t            = h_t C_t + D_skip x_t
    out            = W_out RMSNorm_gated(y * silu(z))

A layer carries no rows a token. Its cache is a state a slot
(`SSMState`): the `[H, P, N]` float32 state and the last K - 1 inputs of
the convolution, `[K - 1, C]` in the weights' dtype. Two shapes of call
run the one recurrence:

* ``scan`` — a prompt or a chunk (more than one token a slot): the
  chunked SSD form in chunks of `chunk` tokens (quadratic inside a chunk,
  the state passed from chunk to chunk), starting from the carried state
  and convolution tail. Positions at or past a slot's valid `length`
  neither advance the state nor enter the carried tail: their step size
  and input are zero, and the tail is the last K - 1 valid inputs.
* ``step`` — a decode step (one token a slot): the recurrence once, on
  the slot's state.

Each is one jitted function (`mixer_scan`, `mixer_step`), so the many
mixers of a program trace and lower once; the state, the step sizes, the
decay and the skip are float32, matmuls accumulate in float32.
`observability.metrics.ssm_routes()` counts the calls by shape.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from ...core import autograd as AG

__all__ = ["SSMState", "ssd_scan", "ssd_step", "causal_conv", "mixer_scan",
           "mixer_step", "mixer", "CHUNK"]

#: the cache of one state-space layer: `ssm` [B, H, P, N] float32, `conv`
#: [B, K - 1, C] the convolution's last inputs (C = H * P + 2 * N)
SSMState = collections.namedtuple("SSMState", ["ssm", "conv"])

#: tokens a chunk of the scan (`mamba_chunk_size`)
CHUNK = 256


def causal_conv(xbc, tail, w, b, length):
    """xbc [B, T, C], tail [B, K - 1, C] (the inputs before xbc), w [K, C],
    b [C], length [B] -> (conv(xbc) + b [B, T, C] float32, the new tail:
    the K - 1 inputs that end at position length - 1)."""
    K = w.shape[0]
    T = xbc.shape[1]
    full = jnp.concatenate([tail.astype(jnp.float32),
                            xbc.astype(jnp.float32)], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(full[:, k:k + T] * wf[k] for k in range(K)) \
        + b.astype(jnp.float32)
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, K - 1, axis=0))(full, length.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def ssd_scan(x, dt, A, Bm, Cm, state, chunk=CHUNK):
    """The chunked SSD form. x [B, T, H, P], dt [B, T, H] (zero where a
    position must not advance the state), A [H], Bm, Cm [B, T, N], state
    [B, H, P, N] float32 -> (y [B, T, H, P] without the skip, the state
    after position T - 1). T is padded to whole chunks with dt = x = 0."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, T)
    pad = -T % L
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = widen(x), widen(dt), widen(Bm), widen(Cm)
    c = (T + pad) // L
    x = x.astype(jnp.float32).reshape(B, c, L, H, P)
    dt = dt.astype(jnp.float32).reshape(B, c, L, H)
    Bm = Bm.astype(jnp.float32).reshape(B, c, L, N)
    Cm = Cm.astype(jnp.float32).reshape(B, c, L, N)
    dA = jnp.cumsum(dt * A.astype(jnp.float32), axis=2)       # [B, c, L, H]
    # inside a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(dA_l - dA_s) dt_s x_s
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    seg = dA.transpose(0, 1, 3, 2)[..., :, None] \
        - dA.transpose(0, 1, 3, 2)[..., None, :]              # [B, c, H, L, L]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcln,bcsn->bcls", Cm, Bm,
                    preferred_element_type=jnp.float32)
    w = cb[:, :, None] * decay * dt.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y = jnp.einsum("bchls,bcshp->bclhp", w, x,
                   preferred_element_type=jnp.float32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(dA[:, :, -1:, :] - dA) * dt              # [B, c, L, H]
    own = jnp.einsum("bcln,bclh,bclhp->bchpn", Bm, to_end, x,
                     preferred_element_type=jnp.float32)
    # the states entering each chunk, chunk by chunk from the carried one
    whole = jnp.exp(dA[:, :, -1, :])                          # [B, c, H]

    def carry(h, k):
        g, s = k
        return g[..., None, None] * h + s, h

    last, entering = jax.lax.scan(
        carry, state.astype(jnp.float32),
        (whole.transpose(1, 0, 2), own.transpose(1, 0, 2, 3, 4)))
    y = y + jnp.einsum("bcln,cbhpn,bclh->bclhp", Cm, entering, jnp.exp(dA),
                       preferred_element_type=jnp.float32)
    return y.reshape(B, c * L, H, P)[:, :T], last


def ssd_step(x, dt, A, Bm, Cm, state):
    """One token a slot: x [B, H, P], dt [B, H], A [H], Bm, Cm [B, N],
    state [B, H, P, N] float32 -> (y [B, H, P] without the skip, the new
    state). Elementwise in float32, the readout a sum over N."""
    dt = dt.astype(jnp.float32)
    dx = dt[..., None] * x.astype(jnp.float32)
    new = state * jnp.exp(dt * A.astype(jnp.float32))[..., None, None] \
        + dx[..., None] * Bm.astype(jnp.float32)[:, None, None, :]
    y = (new * Cm.astype(jnp.float32)[:, None, None, :]).sum(-1)
    return y, new


def _valid(length, T):
    """[B, T]: the positions below each row's valid length."""
    return jnp.arange(T)[None, :] < length[:, None]


def _split(zxbcdt, H, P, N):
    d_inner = H * P
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * N:]
    return z, xbc, dt


def _gated_out(y, z, norm_w, w_out, eps, dtype):
    """W_out RMSNorm(y * silu(z)) with a gain over all d_inner values."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps) \
        * norm_w.astype(jnp.float32)
    return jnp.matmul(g.astype(dtype), w_out,
                      preferred_element_type=jnp.float32).astype(dtype)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps",
                                             "chunk"))
def mixer_scan(u, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
               w_out, state, tail, length, *, heads, head_dim, eps, chunk):
    """The mixer over u [B, T, D] from (state, tail), positions at or past
    `length` [B] left out -> (out [B, T, D], state, tail)."""
    H, P = heads, head_dim
    N = (conv_w.shape[1] - H * P) // 2
    B, T, _ = u.shape
    z, xbc, dt = _split(jnp.matmul(u, w_in,
                                   preferred_element_type=jnp.float32),
                        H, P, N)
    with jax.named_scope("ssm_chunk_scan"):
        xbc, new_tail = causal_conv(xbc.astype(u.dtype), tail, conv_w,
                                    conv_b, length)
        xbc = jax.nn.silu(xbc)
        valid = _valid(length, T)
        x = jnp.where(valid[..., None], xbc[..., :H * P], 0.0).reshape(
            B, T, H, P)
        dt = jnp.where(valid[..., None], jax.nn.softplus(
            dt + dt_bias.astype(jnp.float32)), 0.0)
        A = -jnp.exp(a_log.astype(jnp.float32))
        y, new_state = ssd_scan(x, dt, A, xbc[..., H * P:H * P + N],
                                xbc[..., H * P + N:], state, chunk)
        y = y + d_skip.astype(jnp.float32)[:, None] * x
    out = _gated_out(y.reshape(B, T, H * P), z, norm_w, w_out, eps, u.dtype)
    return out, new_state, new_tail


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps"))
def mixer_step(u, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
               w_out, state, tail, *, heads, head_dim, eps):
    """The mixer for one token a slot, u [B, 1, D] -> (out [B, 1, D],
    state, tail)."""
    H, P = heads, head_dim
    N = (conv_w.shape[1] - H * P) // 2
    B = u.shape[0]
    z, xbc, dt = _split(jnp.matmul(u[:, 0], w_in,
                                   preferred_element_type=jnp.float32),
                        H, P, N)
    with jax.named_scope("ssm_step"):
        window = jnp.concatenate([tail, xbc.astype(tail.dtype)[:, None]],
                                 axis=1)                       # [B, K, C]
        xbc = jax.nn.silu((window.astype(jnp.float32)
                           * conv_w.astype(jnp.float32)).sum(1)
                          + conv_b.astype(jnp.float32))
        x = xbc[:, :H * P].reshape(B, H, P)
        dt = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
        A = -jnp.exp(a_log.astype(jnp.float32))
        y, new_state = ssd_step(x, dt, A, xbc[:, H * P:H * P + N],
                                xbc[:, H * P + N:], state)
        y = y + d_skip.astype(jnp.float32)[:, None] * x
    out = _gated_out(y.reshape(B, H * P), z, norm_w, w_out, eps, u.dtype)
    return out[:, None], new_state, window[:, 1:]


def mixer(u, params, cache: SSMState, length, *, heads, head_dim, eps,
          chunk=CHUNK):
    """Tensors: u [B, T, D], params (w_in, conv_w, conv_b, dt_bias, a_log,
    d_skip, norm_w, w_out), cache an `SSMState`, length [B] int32 or None
    (every position valid) -> (out [B, T, D], new `SSMState`). A call of
    one token a slot is the step, any other the scan. Inference-only."""
    from ...observability.metrics import record_ssm_route

    T = int(u.shape[1])
    kw = dict(heads=heads, head_dim=head_dim, eps=eps)
    if T == 1:
        record_ssm_route("step")
        out, s, t = AG.apply_nondiff(
            lambda *a: mixer_step(*a, **kw),
            (u, *params, cache.ssm, cache.conv))
        return out, SSMState(s, t)
    record_ssm_route("scan")
    if length is None:
        from ...core.tensor import Tensor

        length = Tensor._wrap(jnp.full((int(u.shape[0]),), T, jnp.int32))
    out, s, t = AG.apply_nondiff(
        lambda *a: mixer_scan(*a, chunk=chunk, **kw),
        (u, *params, cache.ssm, cache.conv, length))
    return out, SSMState(s, t)
