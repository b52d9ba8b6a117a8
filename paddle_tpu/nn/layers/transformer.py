"""Transformer stack (reference: python/paddle/nn/layer/transformer.py:
MultiHeadAttention, TransformerEncoder/DecoderLayer, Transformer).

TPU-first notes: attention is computed in the standard fused form (XLA fuses
QK^T·scale·softmax·V well); a Pallas flash-attention path and ring-attention
context parallelism plug in at paddle_tpu.nn.functional.scaled_dot_product
via config (SURVEY.md §5 long-context plan).
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ...core import autograd as AG
from ...core.tensor import Tensor
from .. import functional as F
from ..layer import Layer
from .common import Dropout, Linear
from .container import LayerList
from .norm import LayerNorm

__all__ = [
    "MultiHeadAttention", "TransformerEncoderLayer", "TransformerEncoder",
    "TransformerDecoderLayer", "TransformerDecoder", "Transformer",
]


def _convert_attention_mask(attn_mask, dtype):
    """bool mask -> additive float mask (transformer.py _convert_attention_mask)."""
    if attn_mask is None:
        return None
    if attn_mask.dtype == jnp.bool_:
        def f(m):
            return jnp.where(m, 0.0, jnp.asarray(-1e9, dtype))

        return AG.apply_nondiff(f, (attn_mask,))
    return attn_mask


class MultiHeadAttention(Layer):
    """reference: nn/layer/transformer.py MultiHeadAttention.

    Decoder-hot-path form (ISSUE 4): when kdim == vdim == embed_dim the
    Q/K/V projections are ONE fused `[d, 3d]` matmul (`qkv_proj`) —
    one MXU dispatch instead of three under-filled ones. Pre-fusion
    checkpoints (`q_proj.*`/`k_proj.*`/`v_proj.*` keys) still load:
    `_convert_legacy_state_dict` merges them (Layer.set_state_dict calls
    the hook on every sublayer). Causal, mask-free, dropout-free
    attention routes to the Pallas flash kernel by default on TPU
    (functional.attention policy; `PADDLE_FLASH_DEFAULT=0` restores
    dense routing).
    """

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None,
                 attn_impl="dense", causal=False, block_size=512):
        # attn_impl: "dense" (materialized scores, reference semantics),
        # "blockwise" (online-softmax, O(block) memory; Pallas-routed on
        # a single TPU chip), "ring"/"ring_pallas" (sp-axis sequence
        # parallel; _pallas runs each step's local attention as the hand
        # kernel), or "ulysses"
        # (sequence-parallel over the hybrid mesh's sp axis — the
        # long-context path the reference lacks, SURVEY.md §5)
        super().__init__()
        if attn_impl not in ("dense", "blockwise", "ring",
                             "ring_pallas", "ulysses"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        self.attn_impl = attn_impl
        self.causal = causal
        self.block_size = block_size
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        if self.head_dim * num_heads != embed_dim:
            raise ValueError("embed_dim must be divisible by num_heads")
        self._fused_qkv = (self.kdim == embed_dim
                           and self.vdim == embed_dim)
        if self._fused_qkv:
            self.qkv_proj = Linear(embed_dim, 3 * embed_dim, weight_attr,
                                   bias_attr)
        else:
            self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
            self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
            self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    # -- fused-QKV plumbing --------------------------------------------------
    def _proj(self, x, which):
        """Project with the q/k/v slice of the fused weight (0/1/2)."""
        if not self._fused_qkv:
            return (self.q_proj, self.k_proj, self.v_proj)[which](x)
        d = self.embed_dim
        w = self.qkv_proj.weight[:, which * d:(which + 1) * d]
        b = self.qkv_proj.bias
        if b is not None:
            b = b[which * d:(which + 1) * d]
        return F.linear(x, w, b)

    def _convert_legacy_state_dict(self, sd, prefix):
        """Merge pre-fusion q_proj/k_proj/v_proj checkpoint entries into
        the fused qkv_proj keys (state-dict round-trip compatibility)."""
        if not self._fused_qkv:
            return sd
        import numpy as np

        for leaf, axis in (("weight", 1), ("bias", 0)):
            keys = [f"{prefix}{p}_proj.{leaf}" for p in ("q", "k", "v")]
            if not all(k in sd for k in keys):
                continue
            parts = [
                v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                for v in (sd[k] for k in keys)
            ]
            sd = dict(sd)
            for k in keys:
                sd.pop(k)
            sd[f"{prefix}qkv_proj.{leaf}"] = np.concatenate(parts, axis=axis)
        return sd

    def _split_heads(self, x):
        from ...ops.manipulation import reshape, transpose

        B, T = x.shape[0], x.shape[1]
        x = reshape(x, [B, T, self.num_heads, self.head_dim])
        return transpose(x, [0, 2, 1, 3])  # B, H, T, D

    def gen_cache(self, key=None, value=None, type=None, max_length=None,
                  batch_size=None, dtype=None, block_size=None,
                  pool_blocks=None):
        """Paddle-compatible `gen_cache` grown a STATIC-CAPACITY form
        (ISSUE 9): with ``max_length`` the returned ``Cache`` holds
        zero-filled [B, H, max_length, Dh] buffers that decode WRITES
        INTO at per-slot positions (forward's ``pos`` kwarg) — constant
        shapes, so the compiled DecodeStep traces once and the buffers
        are donatable. Without it, the legacy zero-length concat cache
        (shape grows per step — eager-only) is returned.

        Round 13: ``block_size`` (or the ``PADDLE_SERVE_BLOCK_SIZE``
        env default, static-capacity form only) switches the storage to
        the PAGED layout — a [P, H, bs, Dh] block pool + [B, nmax]
        block table (`serving.paged_kv.PagedKV`) behind the same
        ``cache_update``/``cached_attention`` seam. ``pool_blocks``
        sizes the pool explicitly (tables start all-trash; the engine's
        BlockPool assigns per request — HBM scales with actual length);
        the default identity mapping reserves full capacity per slot.
        Composes with the int8/fp8 quantized form."""
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self._proj(key, 1))
            v = self._split_heads(
                self._proj(value if value is not None else key, 2)
            )
            return MultiHeadAttention.StaticCache(k, v)
        if batch_size is not None:
            B = int(batch_size)
        elif key is not None:
            B = int(key.shape[0])
        else:
            raise ValueError("gen_cache needs `key` or `batch_size`")
        cap = 0 if max_length is None else int(max_length)
        shape = (B, self.num_heads, cap, self.head_dim)
        from ...distributed import quantized_comm as qc

        kvq = qc.kv_quant_policy(dtype)
        if kvq is not None and cap == 0 and dtype is None:
            # the env default applies only to the static-capacity
            # serving form — a legacy concat-cache caller in the same
            # process never opted in and keeps its full-width cache
            kvq = None
        from ...serving import paged_kv as pk

        # paged layout (ISSUE 13): explicit block_size wins; the env
        # default applies only to the static-capacity serving form
        bs_pg = (int(block_size) if block_size is not None
                 else (pk.block_size_default() if cap > 0 else 0))
        if bs_pg > 0:
            if cap == 0:
                raise ValueError(
                    "a paged KV cache needs the static-capacity form: "
                    "pass max_length="
                )
            pdt = None if kvq is not None else (dtype or self._dtype)

            def paged_buf():
                raw = pk.paged_zero(
                    B, self.num_heads, cap, self.head_dim, block=bs_pg,
                    pool_blocks=pool_blocks, dtype=pdt, quant=kvq,
                )
                kv = (qc.QuantKV(Tensor._wrap(raw.kv.q),
                                 Tensor._wrap(raw.kv.scale))
                      if kvq is not None else Tensor._wrap(raw.kv))
                return pk.PagedKV(kv, Tensor._wrap(raw.table))

            return MultiHeadAttention.Cache(paged_buf(), paged_buf())
        if kvq is not None:
            # int8/fp8 block-scaled KV cache (ISSUE 10): narrow payload
            # at the cache shape + per-row-block f32 scales, reusing the
            # quantized-comm primitives; decode writes quantize, reads
            # dequantize (cache_update / cached_attention)
            if cap == 0:
                raise ValueError(
                    "a quantized KV cache needs the static-capacity "
                    "form: pass max_length="
                )

            def qkv_buf():
                p, s = qc.kv_zero(shape, kvq)
                return qc.QuantKV(Tensor._wrap(p), Tensor._wrap(s))

            return MultiHeadAttention.Cache(qkv_buf(), qkv_buf())
        dt = dtype or self._dtype
        # _wrap, not Tensor(): the ctor's dtype inference would
        # np.asarray the buffer — a device read per cache allocation
        zk = Tensor._wrap(jnp.zeros(shape, dt))
        zv = Tensor._wrap(jnp.zeros(shape, dt))
        return MultiHeadAttention.Cache(zk, zv)

    def _finish_output(self, out, weights, cache):
        from ...ops.manipulation import reshape, transpose

        out = transpose(out, [0, 2, 1, 3])
        out = reshape(out, [out.shape[0], out.shape[1], self.embed_dim])
        out = self.out_proj(out)
        outs = [out]
        if self.need_weights:
            outs.append(weights)
        if cache is not None and not isinstance(
                cache, MultiHeadAttention.StaticCache):
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None, pos=None):
        key = query if key is None else key
        value = key if value is None else value

        if (self._fused_qkv and key is query and value is query
                and not isinstance(cache, MultiHeadAttention.StaticCache)):
            # self-attention: ONE [B, T, 3d] projection, split afterwards
            from ...ops.manipulation import reshape, transpose

            B, T = query.shape[0], query.shape[1]
            qkv = self.qkv_proj(query)
            qkv = reshape(qkv, [B, T, 3, self.num_heads, self.head_dim])
            qkv = transpose(qkv, [2, 0, 3, 1, 4])  # 3, B, H, T, dh
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            q = self._split_heads(self._proj(query, 0))
            k = v = None
        if isinstance(cache, MultiHeadAttention.StaticCache):
            k, v = cache.k, cache.v
        else:
            if k is None:
                k = self._split_heads(self._proj(key, 1))
                v = self._split_heads(self._proj(value, 2))
            if isinstance(cache, MultiHeadAttention.Cache):
                if pos is not None:
                    # static-capacity decode-append (ISSUE 9): K/V rows
                    # are written IN PLACE at per-slot `pos` and the
                    # position-masked attention runs over the full
                    # capacity — constant shapes, donatable buffers,
                    # one trace for the whole decode (jit.DecodeStep).
                    if attn_mask is not None or self.need_weights:
                        raise NotImplementedError(
                            "static-capacity decode is causal-by-"
                            "position and never materializes weights; "
                            "attn_mask/need_weights need the concat "
                            "cache (pos=None)"
                        )
                    if self.attn_impl != "dense":
                        raise NotImplementedError(
                            "static-capacity decode requires "
                            "attn_impl='dense' (blockwise/ring paths "
                            "have no traced-position masking)"
                        )
                    from ..functional import attention as attn_route

                    out, k, v = attn_route.cached_append_attention(
                        q, cache.k, cache.v, k, v, pos,
                        scale=self.head_dim ** -0.5
                    )
                    cache = MultiHeadAttention.Cache(k, v)
                    return self._finish_output(out, None, cache)
                from ...ops.manipulation import concat

                k = concat([cache.k, k], axis=2)
                v = concat([cache.v, v], axis=2)
                cache = MultiHeadAttention.Cache(k, v)

        mask = _convert_attention_mask(attn_mask, q._data.dtype)

        if self.attn_impl != "dense":
            # flash-style paths never materialize the weights and use
            # LOCAL query positions for causal masking — features that
            # need either are rejected loudly, not silently wrong
            if attn_mask is not None:
                raise NotImplementedError(
                    "blockwise/ring attention support causal=True masking "
                    "only; arbitrary attn_mask needs the dense impl"
                )
            if self.dropout and self.training:
                raise NotImplementedError(
                    "attention-weight dropout requires the dense impl "
                    "(flash-style paths never materialize the weights)"
                )
            if self.need_weights:
                raise NotImplementedError(
                    "need_weights requires the dense impl"
                )
            if cache is not None:
                raise NotImplementedError(
                    "incremental-decode Cache needs query-position offsets "
                    "the blockwise/ring paths do not implement yet; use "
                    "the dense impl for decoding"
                )
            from .ring_attention import (
                blockwise_attention, ring_attention, ulysses_attention,
            )

            if self.attn_impl == "blockwise":
                out = blockwise_attention(
                    q, k, v, causal=self.causal,
                    block_size=self.block_size,
                )
            elif self.attn_impl == "ulysses":
                out = ulysses_attention(q, k, v, causal=self.causal,
                                        block_size=self.block_size)
            else:
                out = ring_attention(
                    q, k, v, causal=self.causal,
                    use_pallas=(self.attn_impl == "ring_pallas"),
                )
            weights = None
        elif not self.need_weights:
            # ONE implementation of routed attention (ISSUE 4): the
            # policy functional sends causal/mask-free/dropout-free
            # attention to the Pallas flash kernel on TPU
            # (PADDLE_FLASH_DEFAULT=0 escape hatch) and computes the
            # dense masked form — including causal masking, which the
            # pre-r06 dense path silently dropped — otherwise
            weights = None
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=self.dropout,
                is_causal=self.causal, training=self.training,
            )
        else:
            out = None

        scale = self.head_dim ** -0.5

        if out is None:
            Sq, Sk = q.shape[2], k.shape[2]
            causal_here = self.causal  # need_weights path masks too

            def score_fn(qr, kr, *m):
                scores = jnp.einsum("bhqd,bhkd->bhqk", qr, kr) * scale
                if m:
                    scores = scores + m[0]
                if causal_here:
                    qpos = jnp.arange(Sq) + (Sk - Sq)
                    kpos = jnp.arange(Sk)
                    scores = jnp.where(
                        kpos[None, :] > qpos[:, None], -1e9, scores
                    )
                return jax.nn.softmax(scores, axis=-1)

            args = (q, k) + ((mask,) if mask is not None else ())
            weights = AG.apply(score_fn, args, name="attention_scores")
            # dropout on the softmax weights, paddle semantics
            # (nn/layer/transformer.py applies F.dropout to `weights`)
            if self.dropout and self.training:
                weights = F.dropout(weights, self.dropout, training=True)
            out = AG.apply(
                lambda w, vr: jnp.einsum("bhqk,bhkd->bhqd", w, vr),
                (weights, v),
                name="attention_context",
            )

        return self._finish_output(out, weights, cache)


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 attn_impl="dense", causal=False):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr,
                                            attn_impl=attn_impl,
                                            causal=causal)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self.linear2(self.dropout(self.activation(self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList(
            [encoder_layer if i == 0 else copy.deepcopy(encoder_layer)
             for i in range(num_layers)]
        )
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, incr_cache = self.self_attn(tgt, tgt, tgt, tgt_mask, cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incr_cache, cache[1]))

    def gen_cache(self, memory):
        incr = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(
            memory, memory, type=MultiHeadAttention.StaticCache
        )
        return incr, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList(
            [decoder_layer if i == 0 else copy.deepcopy(decoder_layer)
             for i in range(num_layers)]
        )
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask, memory_mask,
                                        cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


class Transformer(Layer):
    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        from ...ops.creation import tril, ones

        return Tensor(
            jnp.where(
                jnp.tril(jnp.ones((length, length), bool)), 0.0, -1e9
            ).astype(jnp.float32)
        )
