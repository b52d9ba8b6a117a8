"""`IndexedAttention`: grouped-query attention that reads only the cached
keys a learned indexer selects (DeepSeek-Sparse-Attention on a GQA
decoder). The arithmetic is in `nn.functional.dsa`.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core import autograd as AG
from ...core.tensor import Tensor
from .. import functional as F
from ..functional import dsa as S
from ..functional import latent as L
from ..initializer import Constant, Normal
from ..layer import Layer
from .latent import RMSNorm

__all__ = ["IndexedAttention"]


class IndexedAttention(Layer):
    """`num_heads` query heads on `kv_heads` K/V heads of `head_dim`
    (query head j reads K/V head j // (num_heads / kv_heads)), an RMSNorm
    with a learned gain over each q and k head, the plain rotary rotation
    (`rope_base`, rotate-half) over the whole head. Beside them an
    indexer: `index_heads` queries of `index_dim`, one LayerNorm-ed key
    of `index_dim` shared by them, one weight a head; both rotated over
    their `index_dim` values. A query attends over the `topk` cached
    positions whose index score sum_j w_j relu(q_j . k) is largest, all
    heads over the same set.

    Per token the layer caches a K row and a V row ([kv_heads *
    head_dim]) and the indexer's key row ([index_dim]):
    `gen_cache` returns an `IndexedKVCache`. `forward(x)` attends a whole
    prompt, `forward(x, cache=, pos=)` writes the new rows at per-slot
    `pos` and attends a chunk (masked blockwise form) or one token a slot
    (gather form); `F.dsa.indexed_attend_plan` says which.

    The projections are fused: `qkv_proj` is [q | k | v], `index_proj`
    [index queries | index key | head weights].

    `keys` is a device counter [2, 2, 2] int32: row 0 counts while a
    step runs more than one query a slot (prefill), row 1 a decode step;
    the columns are the (query, key) pairs visible and the pairs selected
    (every query of a step counts, a chunk's padding and a finished
    slot's frozen position too); the last axis is `F.dsa.advance_wide`'s
    two limbs. Only advanced where a cache is carried, and handed on by
    the step program as `nn.RoutedExperts.load` is."""

    def __init__(self, d_model, num_heads, kv_heads, head_dim, *,
                 index_heads, index_dim, topk, rope_base=10000.0,
                 epsilon=1e-6, key_block=L.KEY_BLOCK, weight_attr=None,
                 dtype=None):
        super().__init__()
        self.num_heads, self.kv_heads, self.head_dim = \
            int(num_heads), int(kv_heads), int(head_dim)
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{kv_heads} K/V heads")
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.topk, self.key_block = int(topk), int(key_block)
        self._epsilon = epsilon
        self.scale = self.head_dim ** -0.5
        self.inv_freq = L.yarn_inv_freq(self.head_dim, float(rope_base))
        self.index_inv_freq = L.yarn_inv_freq(self.index_dim,
                                              float(rope_base))
        H, G, Dh = self.num_heads, self.kv_heads, self.head_dim
        Hi, Di = self.index_heads, self.index_dim

        def make(shape, init):
            return self.create_parameter(
                shape=shape, attr=weight_attr, dtype=dtype,
                default_initializer=init)

        self.qkv_proj = make([d_model, (H + 2 * G) * Dh], Normal(0.0, 0.02))
        self.o_proj = make([H * Dh, d_model], Normal(0.0, 0.02))
        self.index_proj = make([d_model, Hi * Di + Di + Hi],
                               Normal(0.0, 0.02))
        self.q_norm = RMSNorm(Dh, epsilon, weight_attr=weight_attr,
                              dtype=dtype)
        self.k_norm = RMSNorm(Dh, epsilon, weight_attr=weight_attr,
                              dtype=dtype)
        self.index_norm_weight = make([Di], Constant(1.0))
        self.index_norm_bias = make([Di], Constant(0.0))
        self.register_buffer("keys", Tensor._wrap(
            jnp.zeros((2, 2, 2), jnp.int32)), persistable=False)

    def gen_cache(self, batch_size, max_length, dtype=None, block_size=None,
                  pool_blocks=None):
        """Zero K rows, V rows and indexer-key rows, [B, cap, .] each."""
        if block_size:
            raise NotImplementedError(
                "an indexed cache (K rows, V rows and the indexer's key "
                "rows side by side) has no paged pool: serving.paged_kv "
                "blocks per-head K and V; build the engine with "
                "block_size=0")
        dt = dtype or self.qkv_proj._data.dtype
        B, cap = int(batch_size), int(max_length)
        kv = self.kv_heads * self.head_dim
        return S.IndexedKVCache(*(
            Tensor._wrap(jnp.zeros((B, cap, w), dt))
            for w in (kv, kv, self.index_dim)))

    def project(self, x, positions):
        """x [B, T, D], positions [B, T] -> (q [B, T, H, Dh], k and v
        rows [B, T, G * Dh], index queries [B, T, Hi, Di], index key rows
        [B, T, Di], head weights [B, T, Hi] float32); q, k and the
        indexer's pair normalised and rotated."""
        B, T = int(x.shape[0]), int(x.shape[1])
        H, G, Dh = self.num_heads, self.kv_heads, self.head_dim
        Hi, Di = self.index_heads, self.index_dim
        eps = self._epsilon
        inv, inv_i = self.inv_freq, self.index_inv_freq

        def split(qkv, ix, qg, kg, ig, ib, p):
            q = qkv[..., :H * Dh].reshape(B, T, H, Dh)
            k = qkv[..., H * Dh:(H + G) * Dh].reshape(B, T, G, Dh)
            v = qkv[..., (H + G) * Dh:]
            q = L._rope(L._rms(q, qg, eps), p, inv, 1.0)
            k = L._rope(L._rms(k, kg, eps), p, inv, 1.0)
            qi = L._rope(ix[..., :Hi * Di].reshape(B, T, Hi, Di).astype(
                qkv.dtype), p, inv_i, 1.0)
            ki = ix[..., Hi * Di:Hi * Di + Di]
            mu = ki.mean(-1, keepdims=True)
            ki = (ki - mu) * jnp.reciprocal(jnp.sqrt(
                jnp.mean((ki - mu) ** 2, -1, keepdims=True) + eps))
            ki = ki * ig.astype(jnp.float32) + ib.astype(jnp.float32)
            ki = L._rope(ki, p, inv_i, 1.0).astype(qkv.dtype)
            return (q, k.reshape(B, T, G * Dh), v, qi, ki,
                    ix[..., Hi * Di + Di:])

        qkv = F.linear(x, self.qkv_proj)
        # the indexer's projection leaves the accumulator as float32: its
        # key's LayerNorm statistics and the head weights are float32
        ix = AG.apply(
            lambda a, w: jnp.matmul(a, w,
                                    preferred_element_type=jnp.float32),
            (x, self.index_proj), name="index_proj")
        return AG.apply_nondiff(split, (
            qkv, ix, self.q_norm.weight, self.k_norm.weight,
            self.index_norm_weight, self.index_norm_bias, positions))

    def forward(self, x, cache=None, pos=None):
        from ... import profiler as _prof
        from ...ops.creation import arange

        B, T = int(x.shape[0]), int(x.shape[1])
        kw = dict(kv_heads=self.kv_heads, topk=self.topk, scale=self.scale,
                  key_block=self.key_block)
        if cache is None:
            start = Tensor._wrap(jnp.zeros((B,), jnp.int32))
            with _prof.device_annotation("dsa.project"):
                q, k, v, qi, ki, w = self.project(
                    x, arange(T, dtype="int32").reshape([1, T]).expand(
                        [B, T]))
            ctx, _ = S.indexed_attention(
                q, qi, w, S.IndexedKVCache(k, v, ki), start, **kw)
            return F.linear(ctx.reshape([B, T, -1]), self.o_proj)
        if pos is None:
            raise ValueError("cache decoding needs `pos` (per-slot write "
                             "positions [B] int32)")
        with _prof.device_annotation("dsa.project"):
            q, k, v, qi, ki, w = self.project(
                x, pos.reshape([-1, 1]) + arange(T, dtype="int32"))
            new_cache = S.indexed_cache_update(cache, k, v, ki, pos)
        ctx, keys = S.indexed_attention(q, qi, w, new_cache, pos, **kw)
        row = 0 if T > 1 else 1
        self.keys._data = self.keys._data.at[row].set(
            S.advance_wide(self.keys._data[row], keys._data))
        return F.linear(ctx.reshape([B, T, -1]), self.o_proj), new_cache
