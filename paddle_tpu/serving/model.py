"""Reference-shaped causal LM implementing the serving model contract
(ISSUE 9).

`jit.DecodeStep` / `jit.PrefillStep` (and the engine on top of them)
consume any Layer with this surface::

    model(ids)                       -> [B, S, V] logits (full forward)
    model(ids, cache=cs, pos=pos)    -> ([B, Sq, V] logits, new caches)
    model.gen_cache(B, cap[, dtype]) -> per-layer static-capacity caches

Four decoders implement it:

* `TransformerLM` — full multi-head attention, learned positions,
  per-head K and V (below).
* `LatentMoELM` — a pattern of layers (leading dense gated-SiLU layers,
  then routed-expert layers) under latent attention, whose `gen_cache`
  returns one `[B, cap, kv_rank + rope]` row store a layer instead of
  per-head K and V.
* `SparseMoELM` — routed-expert layers (softmax top-k) under grouped-query
  attention that reads only the cached keys a learned indexer selects,
  whose `gen_cache` returns two kinds of state a layer side by side: K
  and V rows of the few K/V heads, and the indexer's key rows.
* `HybridSSMLM` — Mamba-2 state-space mixers beside a few grouped-query
  attention layers with no position encoding, whose `gen_cache` returns
  a layer's state (`SSMState`: one `[H, P, N]` state a slot, no rows) or
  its K/V cache, and whose forward takes each row's valid `length`.

The last three share one skeleton, `_RoutedDecoderLM`: embedding, pre-norm
blocks of a mixer (attention or state space) and an MLP, final RMSNorm,
a float32 head, the routed layers' counters.

`TransformerLM` is the first in-repo implementation: token + learned position
embeddings, a `ParallelGPTBlock` stack (tensor-parallel attention/MLP —
trivial on one chip, sharded over 'mp' on a hybrid mesh, same code
path), final LayerNorm and an untied vocab head. The benchmark's
`gpt2` family trains and serves this class (`benchmarks/families/gpt2/`).
"""
from __future__ import annotations

from .. import nn
from ..distributed import comm
from ..distributed.meta_parallel import ParallelGPTBlock
from ..ops.creation import arange

__all__ = ["TransformerLM", "LatentMoELM", "SparseMoELM", "HybridSSMLM"]


class TransformerLM(nn.Layer):
    def __init__(self, vocab_size, d_model=256, num_heads=8,
                 num_layers=4, max_position=2048, dim_feedforward=None,
                 dropout=0.0, use_flash_attention=None):
        super().__init__()
        if comm.hybrid_mesh() is None:
            comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.max_position = max_position
        self.embed = nn.Embedding(vocab_size, d_model)
        self.pos_embed = nn.Embedding(max_position, d_model)
        self.blocks = nn.LayerList([
            ParallelGPTBlock(
                d_model, num_heads, dim_feedforward, dropout=dropout,
                use_flash_attention=use_flash_attention,
            )
            for _ in range(num_layers)
        ])
        self.ln_f = nn.LayerNorm(d_model)
        self.head = nn.Linear(d_model, vocab_size)

    def forward(self, ids, cache=None, pos=None, adapter=None):
        T = int(ids.shape[1])
        if cache is None:
            h = self.embed(ids) + self.pos_embed(
                arange(T, dtype="int64"))
            for blk in self.blocks:
                h = blk(h)
            return self.head(self.ln_f(h))
        if pos is None:
            raise ValueError("cache decoding needs `pos` ([B] int32)")
        # per-slot absolute positions: slot b's first query sits at
        # pos[b] (traced — one program serves every step of the decode)
        pos_ids = pos.reshape([-1, 1]) + arange(T, dtype="int32")
        h = self.embed(ids) + self.pos_embed(pos_ids)
        new_caches = []
        for blk, c in zip(self.blocks, cache):
            h, nc = blk(h, cache=c, pos=pos, adapter=adapter)
            new_caches.append(nc)
        return self.head(self.ln_f(h)), new_caches

    def load_quantized(self, path):
        """Load an int8/fp8 ``jit.save_quantized`` checkpoint directly
        into this model (ISSUE 19): linear weights arrive as narrow
        payload + per-block scales and STAY narrow — no wide copy is
        materialized, ``F.linear`` routes them through the quantized
        matmul, and the compiled decode step streams the narrow bytes
        from HBM. Returns the checkpoint ledger (+ ``load_ms``)."""
        from ..jit.save_load import load_quantized as _loadq

        return _loadq(self, path)

    def gen_cache(self, batch_size, max_length, dtype=None,
                  block_size=None, pool_blocks=None):
        if int(max_length) > self.max_position:
            raise ValueError(
                f"cache capacity {max_length} exceeds max_position="
                f"{self.max_position} (the position table)"
            )
        return [blk.gen_cache(batch_size, max_length, dtype,
                              block_size=block_size,
                              pool_blocks=pool_blocks)
                for blk in self.blocks]


class _Block(nn.Layer):
    """Pre-norm residual block: an attention layer of the cache contract
    (`attn(x)`, `attn(x, cache=, pos=)`, `attn.gen_cache`), then a dense
    gated MLP or routed experts. `residual` scales both branches (None:
    full weight); a state-space layer in `attn` also takes `length=`."""

    def __init__(self, attn, mlp, d_model, eps, weight_attr, dtype,
                 residual=None):
        super().__init__()
        from ..nn.layers.latent import RMSNorm

        self.norm1 = RMSNorm(d_model, eps, weight_attr=weight_attr,
                           dtype=dtype)
        self.attn = attn
        self.norm2 = RMSNorm(d_model, eps, weight_attr=weight_attr,
                           dtype=dtype)
        self.mlp = mlp
        self.routed = hasattr(mlp, "held")
        self.residual = residual

    def _branch(self, x):
        return x if self.residual is None else x * self.residual

    def forward(self, h, cache=None, pos=None, **length):
        if cache is None:
            h = h + self._branch(self.attn(self.norm1(h)))
            return h + self._branch(self.mlp(self.norm2(h)))
        a, new_cache = self.attn(self.norm1(h), cache=cache, pos=pos,
                                 **length)
        h = h + self._branch(a)
        x = self.norm2(h)
        return h + self._branch(self.mlp(x, count=True) if self.routed
                                else self.mlp(x)), new_cache


class _RoutedDecoderLM(nn.Layer):
    """The skeleton `LatentMoELM`, `SparseMoELM` and `HybridSSMLM` share:
    an embedding, `blocks` (each subclass builds its own pairs of mixer
    and MLP through `_build`), a final RMSNorm, a head whose logits are
    float32, no biases, no position table. Parameters and cache are
    `dtype`; norm and softmax statistics, the router and the logits are
    float32. `_build`'s `embedding_multiplier` and `residual` (None: no
    scaling) and `head=False` (a subclass's own `_logits`) are the hooks
    a hybrid uses; `length` reaches the blocks only where it is given.

    The serving contract of this module: `model(ids)`, `model(ids,
    cache=, pos=)`, `gen_cache(B, cap[, dtype], block_size=,
    pool_blocks=)`; a paged pool is refused (`block_size` > 0 raises).
    `expert_load()` reads the routed layers' device counters."""

    def _build(self, vocab_size, d_model, pairs, *, max_position, epsilon,
               weight_attr, dtype, embedding_multiplier=None, residual=None,
               head=True):
        """`pairs`: one (attention, mlp) a block."""
        from ..nn.initializer import Normal
        from ..nn.layers.latent import RMSNorm

        if comm.hybrid_mesh() is None:
            comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.max_position = int(max_position)
        self.embedding_multiplier = embedding_multiplier
        # nn.Embedding makes its table in the default dtype
        self.embedding = nn.Embedding(vocab_size, d_model,
                                      weight_attr=weight_attr)
        self.embedding.weight._data = \
            self.embedding.weight._data.astype(dtype)
        self.blocks = nn.LayerList([
            _Block(attn, mlp, d_model, epsilon, weight_attr, dtype, residual)
            for attn, mlp in pairs])
        self.norm_f = RMSNorm(d_model, epsilon, weight_attr=weight_attr,
                            dtype=dtype)
        if head:
            self.head = self.create_parameter(
                shape=[d_model, vocab_size], attr=weight_attr, dtype=dtype,
                default_initializer=Normal(0.0, 0.02))

    def _logits(self, h):
        """The head's product leaves the accumulator as float32: logits
        rounded to the parameters' bfloat16 would tie at the top of a
        65,536-way row (the best logit's neighbours lie 2^-6 apart)."""
        import jax.numpy as jnp

        from ..core import autograd as AG

        return AG.apply(
            lambda a, w: jnp.matmul(a, w,
                                    preferred_element_type=jnp.float32),
            (self.norm_f(h), self.head), name="lm_head")

    def forward(self, ids, cache=None, pos=None, adapter=None, length=None):
        h = self.embedding(ids)
        if self.embedding_multiplier is not None:
            h = h * self.embedding_multiplier
        if cache is None:
            for blk in self.blocks:
                h = blk(h)
            return self._logits(h)
        if pos is None:
            raise ValueError("cache decoding needs `pos` ([B] int32)")
        kw = {} if length is None else {"length": length}
        new_caches = []
        for blk, c in zip(self.blocks, cache):
            h, nc = blk(h, cache=c, pos=pos, **kw)
            new_caches.append(nc)
        return self._logits(h), new_caches

    def gen_cache(self, batch_size, max_length, dtype=None,
                  block_size=None, pool_blocks=None):
        if int(max_length) > self.max_position:
            raise ValueError(
                f"cache capacity {max_length} exceeds max_position="
                f"{self.max_position}")
        return [blk.attn.gen_cache(batch_size, max_length, dtype,
                                   block_size=block_size,
                                   pool_blocks=pool_blocks)
                for blk in self.blocks]

    def expert_load(self):
        """{block index: [2, held + 1] int numpy array} of the routed
        layers' `load` counters (rows prefill, decode; the last column is
        the assignments routed to experts not held): one device read."""
        import jax.numpy as jnp
        import numpy as np

        routed = [(i, b.mlp) for i, b in enumerate(self.blocks) if b.routed]
        if not routed:
            return {}
        stacked = np.asarray(jnp.stack([m.load._data for _, m in routed]))
        return {i: stacked[n] for n, (i, _) in enumerate(routed)}


class LatentMoELM(_RoutedDecoderLM):
    """A causal LM of latent-attention blocks (`nn.LatentAttention`): the
    first `dense_layers` blocks carry a dense gated-SiLU MLP, the rest
    `nn.RoutedExperts` over the `held` = (first, count) experts this chip
    holds of `num_experts`, beside a shared expert; sigmoid routing with
    a selection bias. The rest is `_RoutedDecoderLM`'s."""

    def __init__(self, vocab_size, d_model, num_heads, num_layers, *,
                 nope_dim, rope_dim, v_dim, kv_rank, dense_ffn,
                 expert_ffn, num_experts, top_k, held=None,
                 shared_ffn=None, routed_scaling=1.0, dense_layers=1,
                 rope=None, qk_norm=True, max_position=131072,
                 epsilon=1e-6, key_block=None, weight_attr=None,
                 dtype="bfloat16"):
        super().__init__()
        from ..nn.layers.latent import (GatedMLP, LatentAttention,
                                        RoutedExperts)

        kb = {} if key_block is None else {"key_block": key_block}
        pairs = []
        for i in range(int(num_layers)):
            attn = LatentAttention(
                d_model, num_heads, nope_dim=nope_dim, rope_dim=rope_dim,
                v_dim=v_dim, kv_rank=kv_rank, rope=rope, qk_norm=qk_norm,
                epsilon=epsilon, weight_attr=weight_attr, dtype=dtype, **kb)
            if i < int(dense_layers):
                mlp = GatedMLP(d_model, dense_ffn, weight_attr, dtype)
            else:
                mlp = RoutedExperts(
                    d_model, expert_ffn, num_experts, top_k, held=held,
                    scaling=routed_scaling, shared_hidden=shared_ffn,
                    weight_attr=weight_attr, dtype=dtype)
            pairs.append((attn, mlp))
        self._build(vocab_size, d_model, pairs, max_position=max_position,
                    epsilon=epsilon, weight_attr=weight_attr, dtype=dtype)


class SparseMoELM(_RoutedDecoderLM):
    """A causal LM whose every block is `nn.IndexedAttention` (grouped
    query heads that read only the `topk` cached keys a learned indexer
    selects) and `nn.RoutedExperts` with softmax scoring over the `held`
    = (first, count) experts this chip holds of `num_experts`: no shared
    expert, no selection bias, the chosen weights renormalised. `gen_cache`
    returns an `IndexedKVCache` a layer: K rows, V rows and the indexer's
    key rows side by side. `selected_keys()` reads the attention layers'
    device counters. The rest is `_RoutedDecoderLM`'s."""

    def __init__(self, vocab_size, d_model, num_heads, kv_heads, head_dim,
                 num_layers, *, index_heads, index_dim, topk, expert_ffn,
                 num_experts, top_k, held=None, rope_base=10000.0,
                 max_position=262144, epsilon=1e-6, key_block=None,
                 weight_attr=None, dtype="bfloat16"):
        super().__init__()
        from ..nn.layers.dsa import IndexedAttention
        from ..nn.layers.latent import RoutedExperts

        kb = {} if key_block is None else {"key_block": key_block}
        pairs = [(
            IndexedAttention(
                d_model, num_heads, kv_heads, head_dim,
                index_heads=index_heads, index_dim=index_dim, topk=topk,
                rope_base=rope_base, epsilon=epsilon,
                weight_attr=weight_attr, dtype=dtype, **kb),
            RoutedExperts(
                d_model, expert_ffn, num_experts, top_k, held=held,
                score="softmax", select_bias=False,
                weight_attr=weight_attr, dtype=dtype))
            for _ in range(int(num_layers))]
        self._build(vocab_size, d_model, pairs, max_position=max_position,
                    epsilon=epsilon, weight_attr=weight_attr, dtype=dtype)

    def selected_keys(self):
        """{block index: [2, 2] int64 numpy array} of the attention
        layers' `keys` counters (rows prefill, decode; columns the (query,
        key) pairs visible and the pairs selected): one device read."""
        import jax.numpy as jnp

        from ..nn.functional.dsa import read_wide

        wide = read_wide(jnp.stack([b.attn.keys._data
                                    for b in self.blocks]))
        return dict(enumerate(wide))


class HybridSSMLM(_RoutedDecoderLM):
    """A causal LM whose blocks are Mamba-2 mixers (`nn.Mamba2Mixer`) or
    grouped-query attention with no position encoding
    (`nn.GroupedAttention`), as `layer_types` ("mamba" / "attention")
    says, each beside a dense gated-SiLU MLP (granite-4.0-h's
    `granitemoehybrid` with no experts): the embedding times
    `embedding_multiplier`, each residual branch times
    `residual_multiplier`, attention scores times `attention_multiplier`,
    a final RMSNorm and a head tied to the embedding whose float32 logits
    are divided by `logits_scaling`. The rest is `_RoutedDecoderLM`'s.

    The serving contract, with one addition: `model(ids, cache=, pos=,
    length=)` takes each row's valid length (`takes_length`), which
    `jit.PrefillStep` hands it, because a padded position would advance a
    state. `gen_cache` returns a layer's `SSMState` or K/V cache in order;
    a paged pool, the prefix cache, migration and speculative decoding
    refuse the state by name."""

    #: `jit.PrefillStep` passes `length=` to a model that declares it
    takes_length = True

    def __init__(self, vocab_size, d_model, layer_types, *, num_heads,
                 kv_heads, head_dim, ssm_heads, ssm_head_dim, state_dim,
                 conv_kernel, ffn, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=None,
                 logits_scaling=1.0, chunk=256, max_position=131072,
                 epsilon=1e-5, weight_attr=None, dtype="bfloat16"):
        super().__init__()
        from ..nn.layers.latent import GatedMLP
        from ..nn.layers.ssm import GroupedAttention, Mamba2Mixer

        self.layer_types = tuple(layer_types)
        self.logits_scaling = float(logits_scaling)
        scale = (attention_multiplier if attention_multiplier is not None
                 else int(head_dim) ** -0.5)

        def mixer(kind):
            if kind == "mamba":
                return Mamba2Mixer(d_model, ssm_heads, ssm_head_dim,
                                   state_dim, conv_kernel, epsilon=epsilon,
                                   chunk=chunk, weight_attr=weight_attr,
                                   dtype=dtype)
            if kind == "attention":
                return GroupedAttention(d_model, num_heads, kv_heads,
                                        head_dim, scale=scale,
                                        weight_attr=weight_attr, dtype=dtype)
            raise ValueError(f"unknown layer type {kind!r}")

        pairs = [(mixer(kind), GatedMLP(d_model, ffn, weight_attr, dtype))
                 for kind in self.layer_types]
        self._build(vocab_size, d_model, pairs, max_position=max_position,
                    epsilon=epsilon, weight_attr=weight_attr, dtype=dtype,
                    embedding_multiplier=float(embedding_multiplier),
                    residual=float(residual_multiplier), head=False)

    def _logits(self, h):
        """RMSNorm(h) E^T / logits_scaling, leaving the accumulator as
        float32."""
        import jax.numpy as jnp

        from ..core import autograd as AG

        return AG.apply(
            lambda a, w: jnp.matmul(a, w.T,
                                    preferred_element_type=jnp.float32)
            / self.logits_scaling,
            (self.norm_f(h), self.embedding.weight), name="lm_head")
