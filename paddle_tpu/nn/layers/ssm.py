"""Layers of a state-space hybrid decoder: `Mamba2Mixer` (a Mamba-2 SSD
mixer whose cache is one state a slot; the arithmetic is in
`nn.functional.ssm`) and `GroupedAttention` (grouped-query attention with
no position encoding, over the per-head K/V cache seam of
`nn.functional.attention`).
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core import autograd as AG
from ...core.tensor import Tensor
from .. import functional as F
from ..functional import ssm as S
from ..initializer import Constant, Normal
from ..layer import Layer

__all__ = ["Mamba2Mixer", "GroupedAttention"]

#: queries a block of `GroupedAttention`'s prefill: each block reads only
#: the key tiles at or below its last query
QUERY_BLOCK = 512


class Mamba2Mixer(Layer):
    """`heads` SSD heads of `head_dim` over a `state_dim` state, one group
    of B and C, a depthwise causal convolution of `conv_kernel` taps over
    [x | B | C], a gated RMSNorm over all heads * head_dim values, no
    projection biases.

    `in_proj` is [z | xBC | dt] ([D, 2 * H * P + 2 * N + H]); `conv_weight`
    is [K, H * P + 2 * N], tap K - 1 on the current input. `forward(x)`
    runs a whole prompt from a zero state; `forward(x, cache=, length=)`
    continues from the slot's `SSMState` (a chunk: the scan, positions at
    or past `length` left out; one token a slot: the step) and returns the
    new state. `gen_cache` returns a zero `SSMState`: float32 state, the
    convolution's tail in the weights' dtype."""

    def __init__(self, d_model, heads, head_dim, state_dim, conv_kernel=4,
                 *, epsilon=1e-5, chunk=S.CHUNK, weight_attr=None,
                 dtype=None):
        super().__init__()
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.state_dim, self.conv_kernel = int(state_dim), int(conv_kernel)
        self.chunk = int(chunk)
        self._epsilon = float(epsilon)
        H, P, N = self.heads, self.head_dim, self.state_dim
        conv = H * P + 2 * N

        def make(shape, init):
            return self.create_parameter(
                shape=shape, attr=weight_attr, dtype=dtype,
                default_initializer=init)

        self.in_proj = make([d_model, 2 * H * P + 2 * N + H],
                            Normal(0.0, 0.02))
        self.conv_weight = make([self.conv_kernel, conv], Normal(0.0, 0.02))
        self.conv_bias = make([conv], Constant(0.0))
        self.dt_bias = make([H], Constant(0.0))
        self.A_log = make([H], Constant(0.0))
        self.D = make([H], Constant(1.0))
        self.norm_weight = make([H * P], Constant(1.0))
        self.out_proj = make([H * P, d_model], Normal(0.0, 0.02))

    def gen_cache(self, batch_size, max_length=None, dtype=None,
                  block_size=None, pool_blocks=None):
        """A zero `SSMState` of `batch_size` slots (`max_length` is
        nothing to a state); a paged pool is refused by name."""
        from ...serving import paged_kv as pk

        B = int(batch_size)
        H, P, N = self.heads, self.head_dim, self.state_dim
        cache = S.SSMState(
            Tensor._wrap(jnp.zeros((B, H, P, N), jnp.float32)),
            Tensor._wrap(jnp.zeros((B, self.conv_kernel - 1, H * P + 2 * N),
                                   dtype or self.in_proj._data.dtype)))
        if block_size:
            pk.refuse_latent(cache, "a paged pool (block_size > 0)")
        return cache

    def _params(self):
        return (self.in_proj, self.conv_weight, self.conv_bias, self.dt_bias,
                self.A_log, self.D, self.norm_weight, self.out_proj)

    def forward(self, x, cache=None, pos=None, length=None):
        kw = dict(heads=self.heads, head_dim=self.head_dim,
                  eps=self._epsilon, chunk=self.chunk)
        if cache is None:
            out, _ = S.mixer(x, self._params(),
                             self.gen_cache(int(x.shape[0])), None, **kw)
            return out
        return S.mixer(x, self._params(), cache, length, **kw)


class GroupedAttention(Layer):
    """`num_heads` query heads on `kv_heads` K/V heads of `head_dim`
    (query head j reads K/V head j // (num_heads / kv_heads)), causal, no
    position encoding, scores scaled by `scale`, no biases; `qkv_proj` is
    [q | k | v]. The cache is `MultiHeadAttention.Cache` of [B, kv_heads,
    cap, head_dim] K and V, written with `F.attention.cache_update` and
    read with `F.attention.cached_attention` (its grouped form, a tile of
    keys at a time) a block of `QUERY_BLOCK` queries at a time, so that a
    block visits only the key tiles at or below its last query."""

    def __init__(self, d_model, num_heads, kv_heads, head_dim, *, scale,
                 weight_attr=None, dtype=None):
        super().__init__()
        self.num_heads, self.kv_heads, self.head_dim = \
            int(num_heads), int(kv_heads), int(head_dim)
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{kv_heads} K/V heads")
        self.scale = float(scale)
        H, G, Dh = self.num_heads, self.kv_heads, self.head_dim

        def mat(shape):
            return self.create_parameter(
                shape=shape, attr=weight_attr, dtype=dtype,
                default_initializer=Normal(0.0, 0.02))

        self.qkv_proj = mat([d_model, (H + 2 * G) * Dh])
        self.o_proj = mat([H * Dh, d_model])

    def gen_cache(self, batch_size, max_length, dtype=None, block_size=None,
                  pool_blocks=None):
        from .transformer import MultiHeadAttention

        shape = (int(batch_size), self.kv_heads, int(max_length),
                 self.head_dim)
        dt = dtype or self.qkv_proj._data.dtype
        return MultiHeadAttention.Cache(Tensor._wrap(jnp.zeros(shape, dt)),
                                        Tensor._wrap(jnp.zeros(shape, dt)))

    def forward(self, x, cache=None, pos=None, length=None):
        from .transformer import MultiHeadAttention

        B, T = int(x.shape[0]), int(x.shape[1])
        H, G, Dh = self.num_heads, self.kv_heads, self.head_dim
        if cache is None:
            out, _ = self(x, cache=self.gen_cache(B, T),
                          pos=Tensor._wrap(jnp.zeros((B,), jnp.int32)))
            return out

        def split(qkv):
            q = qkv[..., :H * Dh].reshape(B, T, H, Dh)
            k = qkv[..., H * Dh:(H + G) * Dh].reshape(B, T, G, Dh)
            v = qkv[..., (H + G) * Dh:].reshape(B, T, G, Dh)
            return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v))

        q, k, v = AG.apply_nondiff(split, (F.linear(x, self.qkv_proj),))
        kc = F.attention.cache_update(cache.k, k, pos)
        vc = F.attention.cache_update(cache.v, v, pos)
        qb = QUERY_BLOCK
        if T <= qb or T % qb:
            ctx = F.attention.cached_attention(q, kc, vc, pos,
                                               scale=self.scale)
        else:
            from ...ops.manipulation import concat

            ctx = concat([F.attention.cached_attention(
                q[:, :, i:i + qb], kc, vc, pos + i, scale=self.scale)
                for i in range(0, T, qb)], axis=2)
        out = ctx.transpose([0, 2, 1, 3]).reshape([B, T, H * Dh])
        return (F.linear(out, self.o_proj),
                MultiHeadAttention.Cache(kc, vc))
