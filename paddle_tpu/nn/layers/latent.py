"""Layers of a latent-attention, routed-expert decoder: `RMSNorm`,
`GatedMLP`, `LatentAttention` (DeepSeek-V2's MLA without a query
low-rank: one `[c | k_rope]` cache row a token) and `RoutedExperts`
(dropless sigmoid top-k routing over the experts a chip holds, beside a
shared expert). The arithmetic is in `nn.functional.latent`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import autograd as AG
from ...core.tensor import Tensor
from .. import functional as F
from ..functional import latent as L
from ..initializer import Constant, Normal
from ..layer import Layer

__all__ = ["RMSNorm", "GatedMLP", "LatentAttention", "RoutedExperts"]


class RMSNorm(Layer):
    """x / sqrt(mean(x^2) + eps) * weight, statistics in float32."""

    def __init__(self, normalized_shape, epsilon=1e-6, weight_attr=None,
                 dtype=None, name=None):
        super().__init__()
        self._normalized_shape = [int(normalized_shape)]
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=self._normalized_shape, attr=weight_attr, dtype=dtype,
            default_initializer=Constant(1.0))

    def forward(self, input):
        return L.rms_norm(input, self.weight, self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}"


class GatedMLP(Layer):
    """down(silu(gate x) * up x); gate and up are one fused [D, 2F]
    matmul. No biases."""

    def __init__(self, d_model, d_hidden, weight_attr=None, dtype=None):
        super().__init__()
        self.gate_up = self.create_parameter(
            shape=[d_model, 2 * d_hidden], attr=weight_attr, dtype=dtype,
            default_initializer=Normal(0.0, 0.02))
        self.down = self.create_parameter(
            shape=[d_hidden, d_model], attr=weight_attr, dtype=dtype,
            default_initializer=Normal(0.0, 0.02))

    def forward(self, x):
        return F.linear(L.swiglu(F.linear(x, self.gate_up)), self.down)


class LatentAttention(Layer):
    """Multi-head latent attention. Per token the layer caches one row
    `[c | k_rope]`: the RMS-normalised `kv_rank`-wide latent and the one
    rotated `rope_dim`-wide key all heads share; per-head keys and values
    are `w_ukv` of the latent. `forward(x)` attends a whole prompt,
    `forward(x, cache=, pos=)` writes the new rows at per-slot `pos` and
    attends a chunk (expanded form) or one token a slot (absorbed form)
    over the cache; `F.latent.latent_attend_plan` says which.

    `rope` holds the rotary settings: `base`, and for `deepseek_yarn`
    `factor`, `original_max_position`, `beta_fast`, `beta_slow`,
    `mscale`, `mscale_all_dim`. With `qk_norm` each query head's nope +
    rope values pass an RMSNorm with a learned gain before the rotation
    (the latent always passes one: keys are never normalised after the
    up-projection, which the absorbed form could not follow)."""

    def __init__(self, d_model, num_heads, *, nope_dim, rope_dim, v_dim,
                 kv_rank, rope=None, qk_norm=True, epsilon=1e-6,
                 key_block=L.KEY_BLOCK, weight_attr=None, dtype=None):
        super().__init__()
        self.num_heads, self.nope_dim, self.rope_dim = \
            int(num_heads), int(nope_dim), int(rope_dim)
        self.v_dim, self.kv_rank = int(v_dim), int(kv_rank)
        self.key_block = int(key_block)
        self._epsilon = epsilon
        rope = dict(rope or {})
        factor = float(rope.get("factor", 1.0))
        self.inv_freq = L.yarn_inv_freq(
            self.rope_dim, float(rope.get("base", 10000.0)), factor,
            int(rope.get("original_max_position", 4096)),
            rope.get("beta_fast", 32), rope.get("beta_slow", 1))
        m_all = L.yarn_mscale(factor, float(rope.get("mscale_all_dim", 0.0)))
        #: the factor on cos and sin, 1 when mscale == mscale_all_dim
        self.rope_scale = L.yarn_mscale(
            factor, float(rope.get("mscale", 1.0))) / m_all
        self.scale = (self.nope_dim + self.rope_dim) ** -0.5 * m_all * m_all
        H, qd = self.num_heads, self.nope_dim + self.rope_dim

        def mat(shape):
            return self.create_parameter(
                shape=shape, attr=weight_attr, dtype=dtype,
                default_initializer=Normal(0.0, 0.02))

        self.q_proj = mat([d_model, H * qd])
        self.kv_down = mat([d_model, self.kv_rank + self.rope_dim])
        self.kv_up = mat([self.kv_rank, H * (self.nope_dim + self.v_dim)])
        self.o_proj = mat([H * self.v_dim, d_model])
        self.q_norm = RMSNorm(qd, epsilon, weight_attr=weight_attr,
                              dtype=dtype) if qk_norm else None
        self.kv_norm = RMSNorm(self.kv_rank, epsilon,
                               weight_attr=weight_attr, dtype=dtype)

    def gen_cache(self, batch_size, max_length, dtype=None, block_size=None,
                  pool_blocks=None):
        """One zero [B, cap, kv_rank + rope_dim] row store."""
        if block_size:
            raise NotImplementedError(
                "a latent cache (one [c | k_rope] row a token, no head "
                "axis) has no paged pool: serving.paged_kv blocks per-head "
                "K and V; build the engine with block_size=0")
        dt = dtype or self.q_proj._data.dtype
        return L.LatentCache(Tensor._wrap(jnp.zeros(
            (int(batch_size), int(max_length),
             self.kv_rank + self.rope_dim), dt)))

    def project(self, x, positions):
        """x [B, T, D], positions [B, T] -> (q [B, T, H, nope + rope]
        normalised and rotated, rows [B, T, kv_rank + rope])."""
        B, T = int(x.shape[0]), int(x.shape[1])
        H, nope, rd = self.num_heads, self.nope_dim, self.rope_dim
        q = F.linear(x, self.q_proj).reshape([B, T, H, nope + rd])
        if self.q_norm is not None:
            q = self.q_norm(q)
        ckr = F.linear(x, self.kv_down)
        inv, rs = self.inv_freq, self.rope_scale

        def rotate(qr, cr, gain, p):
            c = L._rms(cr[..., :self.kv_rank], gain, self._epsilon)
            kr = L._rope(cr[..., self.kv_rank:], p, inv, rs)
            qrot = L._rope(qr[..., nope:], p, inv, rs)
            return (jnp.concatenate([qr[..., :nope], qrot], -1),
                    jnp.concatenate([c, kr], -1))

        return AG.apply(rotate, (q, ckr, self.kv_norm.weight, positions),
                        name="mla_rotate")

    def forward(self, x, cache=None, pos=None):
        from ... import profiler as _prof
        from ...ops.creation import arange

        B, T = int(x.shape[0]), int(x.shape[1])
        H = self.num_heads
        w_ukv = self.kv_up.reshape(
            [self.kv_rank, H, self.nope_dim + self.v_dim])
        kw = dict(kv_rank=self.kv_rank, nope_dim=self.nope_dim,
                  scale=self.scale, key_block=self.key_block)
        if cache is None:
            start = Tensor._wrap(jnp.zeros((B,), jnp.int32))
            with _prof.device_annotation("mla.project"):
                q, rows = self.project(
                    x, arange(T, dtype="int32").reshape([1, T]).expand(
                        [B, T]))
            ctx = L.latent_attention(q, rows, w_ukv, start,
                                     visit_all=True, **kw)
            return F.linear(ctx.reshape([B, T, H * self.v_dim]), self.o_proj)
        if pos is None:
            raise ValueError("cache decoding needs `pos` (per-slot write "
                             "positions [B] int32)")
        with _prof.device_annotation("mla.project"):
            q, rows = self.project(
                x, pos.reshape([-1, 1]) + arange(T, dtype="int32"))
            new_cache = L.latent_cache_update(cache, rows, pos)
        ctx = L.latent_attention(q, new_cache.rows, w_ukv, pos, **kw)
        return (F.linear(ctx.reshape([B, T, H * self.v_dim]), self.o_proj),
                new_cache)


class RoutedExperts(Layer):
    """An expert layer that is told which experts it holds.

    The router keeps all `num_experts` outputs and its `top_k`; scores
    are `score` of the logits in float32 (``sigmoid``, or ``softmax``
    over the `num_experts` logits), the chosen set is the top-k of score
    + `select_bias` (which selects and does not weigh; a layer built with
    `select_bias=False` has none), the weights are `scaling` * score
    over the chosen scores' sum. No capacity: every
    assignment to a held expert is computed (rows sorted by expert, one
    `jax.lax.ragged_dot` over the held experts' stacked weights). The
    layer returns the sum over the chosen experts held here plus the
    shared expert; what absent experts would add is left out, and no code
    stands in for their chips. `held` = (first, count) of the contiguous
    share.

    `load` is a device counter [2, count + 1] int32: row 0 counts while
    a step runs more than one query a slot (prefill), row 1 a decode
    step; the columns are the assignments that fell on each held expert
    and, last, those routed to experts not held. It is only advanced
    inside a cache-carrying step program, which hands it on (see
    `jit.decode_step`)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k, *, held=None,
                 scaling=1.0, shared_hidden=None, score="sigmoid",
                 select_bias=True, weight_attr=None, dtype=None):
        super().__init__()
        if score not in L.SCORES:
            raise ValueError(f"score={score!r}: one of {sorted(L.SCORES)}")
        self.score = score
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        first, count = held if held is not None else (0, self.num_experts)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={held} is not a share of "
                             f"{self.num_experts} experts")
        self.held = (int(first), int(count))
        self.scaling = float(scaling)

        def mat(shape):
            return self.create_parameter(
                shape=shape, attr=weight_attr, dtype=dtype,
                default_initializer=Normal(0.0, 0.02))

        self.gate = mat([d_model, self.num_experts])
        self.select_bias = self.create_parameter(
            shape=[self.num_experts], attr=weight_attr, dtype="float32",
            default_initializer=Constant(0.0)) if select_bias else None
        self.w_in = mat([count, d_model, 2 * d_hidden])
        self.w_out = mat([count, d_hidden, d_model])
        self.shared = GatedMLP(d_model, shared_hidden, weight_attr, dtype) \
            if shared_hidden else None
        self.register_buffer("load", Tensor._wrap(
            jnp.zeros((2, count + 1), jnp.int32)), persistable=False)

    def forward(self, x, count=False):
        """x [B, T, D] -> [B, T, D]; `count` advances `load`."""
        B, T, D = (int(s) for s in x.shape)
        first = self.held[0]

        def f(xr, wg, wi, wo, b=None):
            return L.routed_experts(xr, wg, b, wi, wo, top_k=self.top_k,
                                    scaling=self.scaling, first_held=first,
                                    score=self.score)

        bias = () if self.select_bias is None else (self.select_bias,)
        y, load = AG.apply_nondiff(f, (
            x.reshape([B * T, D]), self.gate, self.w_in, self.w_out) + bias)
        if count:
            row = 0 if T > 1 else 1
            self.load._data = self.load._data.at[row].add(load._data)
        y = y.reshape([B, T, D])
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(x)
        return y
