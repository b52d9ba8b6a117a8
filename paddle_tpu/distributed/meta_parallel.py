"""Tensor (model) parallel layers.

Reference: python/paddle/distributed/collective.py:492 (`_parallel_linear`),
:526 (`_parallel_embedding`), :566 (`split`) — weight-partitioned layers over
a model-parallel NCCL ring with explicit c_allreduce/c_allgather calls.
Tests: column_parallel_linear_api.py / row_parallel_linear_api.py /
parallel_embedding_api.py.

TPU-native: a partitioned weight is ONE logical parameter laid out sharded
over the 'mp' mesh axis (each device stores 1/mp of it in HBM). The forward
is the plain dense computation; XLA's sharding propagation inserts the
all-reduce / all-gather exactly where the reference calls them explicitly,
and fuses them with the matmuls. `gather_output` / `input_is_parallel`
become output/input sharding constraints.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import autograd as AG
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import XavierNormal
from ..nn.layer import Layer
from . import comm


def _constrain(x: Tensor, mesh, spec) -> Tensor:
    """Differentiable sharding constraint, usable eager and in-trace."""
    sh = NamedSharding(mesh, spec)
    return AG.apply(
        lambda r: jax.lax.with_sharding_constraint(r, sh), (x,),
        name="sharding_constraint",
    )


def _shard_param(p, mesh, spec):
    p._data = jax.device_put(p._data, NamedSharding(mesh, spec))
    p._tp_spec = spec  # consumed by fleet.distributed_model layout pass
    return p


def _overlap_plan(mesh, x, weight=None):
    """(mp, row_spec_elem) when PADDLE_TP_OVERLAP routes this layer's
    matmul through the collective-matmul ring (distributed/overlap.py),
    else None (the GSPMD sharding-propagation form). Declines when the
    weight takes a quantized-matmul route (ISSUE 19: pre-quantized
    payload or armed PADDLE_Q_MATMUL/strategy policy) — the narrow form
    goes through the F.linear seam; hand-fusing the dequant into the
    ring chunks is future work."""
    from . import overlap as _ov

    if not _ov.tp_overlap_enabled():
        return None
    if weight is not None:
        from . import quantized_compute as _qcp

        if (getattr(weight, "_q_scale", None) is not None
                or _qcp.matmul_policy() is not None):
            return None
    rows = 1
    for s in x.shape[:-1]:
        rows *= int(s)
    return _ov.row_overlap_plan(mesh, rows)


class ColumnParallelLinear(Layer):
    """Weight column-partitioned linear (collective.py:492, axis=1 path).

    W: [in, out] sharded P(None, 'mp'); per-device block [in, out/mp].
    gather_output=True replicates the output (reference: c_concat-style
    allgather); False leaves it sharded on the feature axis for a following
    RowParallelLinear.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, bias_attr=None,
                 name=None):
        super().__init__()
        self.mesh = comm.mp_mesh()
        mp = self.mesh.shape["mp"]
        if out_features % mp != 0:
            raise ValueError(
                f"out_features={out_features} not divisible by mp={mp}"
            )
        self._in = in_features
        self._out = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard_param(self.weight, self.mesh, P(None, "mp"))
        if has_bias and bias_attr is not False:
            self.bias = self.create_parameter(
                shape=[out_features], attr=bias_attr, is_bias=True
            )
            _shard_param(self.bias, self.mesh, P("mp"))
        else:
            self.bias = None

    def forward(self, x):
        if self.gather_output:
            plan = _overlap_plan(self.mesh, x, self.weight)
            if plan is not None:
                # pipelined output gather: per-row-chunk local matmuls,
                # each chunk's all-gather issued while the next computes
                from . import overlap as _ov

                mp, row_ax = plan
                args = (x, self.weight) + (
                    (self.bias,) if self.bias is not None else ()
                )
                return AG.apply(
                    lambda xr, wr, *br: _ov.column_gather_overlap(
                        xr, wr, br[0] if br else None, self.mesh, mp,
                        row_ax,
                    ),
                    args, name="column_gather_overlap",
                )
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return _constrain(out, self.mesh, P())
        return _constrain(out, self.mesh, P(*([None] * (out.ndim - 1) + ["mp"])))


class RowParallelLinear(Layer):
    """Weight row-partitioned linear (collective.py:492, axis=0 path).

    W: [in, out] sharded P('mp', None). With input_is_parallel the incoming
    activation is already sharded on its feature axis (from a
    gather_output=False column layer); the matmul's contraction produces
    the partial sums whose all-reduce (reference: explicit c_allreduce_sum)
    XLA inserts via propagation. Output replicated.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, bias_attr=None,
                 name=None):
        super().__init__()
        self.mesh = comm.mp_mesh()
        mp = self.mesh.shape["mp"]
        if in_features % mp != 0:
            raise ValueError(
                f"in_features={in_features} not divisible by mp={mp}"
            )
        self._in = in_features
        self._out = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard_param(self.weight, self.mesh, P("mp", None))
        if has_bias and bias_attr is not False:
            self.bias = self.create_parameter(
                shape=[out_features], attr=bias_attr, is_bias=True
            )
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(
                x, self.mesh, P(*([None] * (x.ndim - 1) + ["mp"]))
            )
        plan = _overlap_plan(self.mesh, x, self.weight)
        if plan is not None:
            # the contraction's psum decomposed into per-chunk ppermute
            # ring steps interleaved with the matmul chunks (collective
            # matmul): each ppermute overlaps the next chunk's MXU work
            from . import overlap as _ov

            mp, row_ax = plan
            args = (x, self.weight) + (
                (self.bias,) if self.bias is not None else ()
            )
            return AG.apply(
                lambda xr, wr, *br: _ov.row_parallel_overlap(
                    xr, wr, br[0] if br else None, self.mesh, mp, row_ax
                ),
                args, name="row_parallel_overlap",
            )
        out = F.linear(x, self.weight, self.bias)
        return _constrain(out, self.mesh, P())


class VocabParallelEmbedding(Layer):
    """Vocab-partitioned embedding (collective.py:526 _parallel_embedding).

    Weight [vocab, dim] sharded P('mp', None): each device stores a vocab
    slice; the gather of looked-up rows (reference: masked local lookup +
    c_allreduce_sum) is XLA's gather over the sharded operand.
    """

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 name=None):
        super().__init__()
        self.mesh = comm.mp_mesh()
        mp = self.mesh.shape["mp"]
        if num_embeddings % mp != 0:
            raise ValueError(
                f"num_embeddings={num_embeddings} not divisible by mp={mp}"
            )
        self._num = num_embeddings
        self._dim = embedding_dim
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard_param(self.weight, self.mesh, P("mp", None))

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, self.mesh, P())


class ParallelMultiHeadAttention(Layer):
    """Megatron-style tensor-parallel self-attention.

    Reference lineage: the fused qkv + head-partitioned attention the
    reference reaches via `paddle.distributed.split` compositions
    (collective.py:492) and its Megatron ERNIE/GPT configs — heads are
    split over the 'mp' axis: the qkv projection is column-parallel
    (gather_output=False keeps [B, T, 3D] feature-sharded), each mp shard
    computes attention for its own heads locally (zero comm in the
    softmax), and the output projection is row-parallel, whose contraction
    all-reduce XLA inserts from sharding propagation.
    """

    def __init__(self, embed_dim, num_heads, dropout=0.0, causal=True,
                 weight_attr=None, bias_attr=None,
                 use_flash_attention=None):
        super().__init__()
        self.mesh = comm.mp_mesh()
        mp = self.mesh.shape["mp"]
        if embed_dim % num_heads != 0:
            raise ValueError("embed_dim must divide into num_heads")
        if num_heads % mp != 0:
            raise ValueError(
                f"num_heads={num_heads} not divisible by mp={mp}"
            )
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.dropout = dropout
        # softmax(QK^T)V core routing (ISSUE 4 flash-by-default):
        #   None  -> AUTO: the Pallas flash kernel whenever the
        #            functional.attention policy allows (causal,
        #            dropout-free, TPU; PADDLE_FLASH_DEFAULT=0 escape
        #            hatch) — dense fallback otherwise;
        #   True  -> force the kernel (requires dropout == 0: flash
        #            never materializes the attention probabilities);
        #   False -> force the dense materialized-score path.
        if use_flash_attention and dropout:
            raise ValueError(
                "use_flash_attention requires dropout=0.0: the flash "
                "kernel never materializes the attention probabilities"
            )
        self.use_flash_attention = use_flash_attention
        self.qkv = ColumnParallelLinear(
            embed_dim, 3 * embed_dim, weight_attr=weight_attr,
            bias_attr=bias_attr, gather_output=False,
        )
        self.out_proj = RowParallelLinear(
            embed_dim, embed_dim, weight_attr=weight_attr,
            bias_attr=bias_attr, input_is_parallel=True,
        )

    def gen_cache(self, batch_size, max_length, dtype=None,
                  block_size=None, pool_blocks=None):
        """Static-capacity decode cache (ISSUE 9): zero [B, H, cap, Dh]
        K/V buffers in the same MultiHeadAttention.Cache namedtuple the
        single-chip layer uses, laid out with heads sharded over 'mp'
        (matching the attention compute) when the mesh is real — the
        compiled DecodeStep then updates each shard's slice in place.

        Round 13: ``block_size`` / ``PADDLE_SERVE_BLOCK_SIZE`` switches
        to the PAGED layout (`serving.paged_kv.PagedKV`): the
        [P, H, bs, Dh] block pool shards its heads over 'mp' exactly
        like the contiguous buffer; the pool dim is slot-agnostic (any
        block can belong to any slot), so it does NOT shard over the
        dp axes — a dp job replicates the pool and dp slots index it
        through their (replicated) tables, which is correct, just not
        dp-elastic in HBM (the multi-host router scales hosts, not
        per-host pools)."""
        import jax.numpy as jnp

        from ..nn.layers.transformer import MultiHeadAttention
        from ..serving import paged_kv as pk

        H, dh = self.num_heads, self.head_dim
        from . import quantized_comm as qc

        kvq = qc.kv_quant_policy(dtype)
        dt = dtype or self._dtype  # follow the layer dtype (bf16 models
        #                            get bf16 caches, like the 1-chip MHA)
        shape = (int(batch_size), H, int(max_length), dh)
        mp = int(self.mesh.shape["mp"])
        # batch shards over the data-parallel axes when divisible (dp
        # slots each store/decode only their shard — dp actually scales
        # serving memory + throughput), heads over mp; indivisible dims
        # stay replicated, which is correct but redundant
        bax = comm.dp_axes(self.mesh)
        baxes = (bax,) if isinstance(bax, str) else tuple(bax)
        bdeg = 1
        for a in baxes:
            if a in self.mesh.shape:
                bdeg *= int(self.mesh.shape[a])
        bspec = None
        if bdeg > 1 and int(batch_size) % bdeg == 0:
            bspec = baxes[0] if len(baxes) == 1 else tuple(baxes)
        spec = P(bspec, "mp" if (mp > 1 and H % mp == 0) else None,
                 None, None)

        def place(z, s=None):
            if self.mesh.size > 1:
                # the scale buffer's leading dims match the payload's,
                # so one spec lays out both; a constraint places eagerly
                # as device_put does, and also lays out the output of a
                # compiled program (the engine's SlotCache), where a
                # device_put does not
                z = jax.lax.with_sharding_constraint(
                    z, NamedSharding(self.mesh, spec if s is None else s))
            # _wrap, not Tensor(): the ctor's dtype inference would
            # np.asarray the buffer — a device read per cache allocation
            return Tensor._wrap(z)

        bs_pg = (int(block_size) if block_size is not None
                 else pk.block_size_default())
        if bs_pg > 0:
            # paged pool [P, H, bs, Dh]: heads over 'mp' (axis 1, like
            # the contiguous buffer); pool dim + tables replicated
            pspec = P(None, "mp" if (mp > 1 and H % mp == 0) else None,
                      None, None)
            pdt = None if kvq is not None else dt

            def paged_buf():
                raw = pk.paged_zero(
                    int(batch_size), H, int(max_length), dh,
                    block=bs_pg, pool_blocks=pool_blocks, dtype=pdt,
                    quant=kvq,
                )
                kv = (qc.QuantKV(place(raw.kv.q, pspec),
                                 place(raw.kv.scale, pspec))
                      if kvq is not None else place(raw.kv, pspec))
                return pk.PagedKV(kv, place(raw.table, P()))

            return MultiHeadAttention.Cache(paged_buf(), paged_buf())

        if kvq is not None:
            # int8/fp8 block-scaled KV cache (ISSUE 10): payload +
            # per-row-block scales shard identically (batch over dp,
            # heads over mp); decode writes quantize, reads dequantize
            def qkv_buf():
                p, s = qc.kv_zero(shape, kvq)
                return qc.QuantKV(place(p), place(s))

            return MultiHeadAttention.Cache(qkv_buf(), qkv_buf())
        out = [place(jnp.zeros(shape, dt)) for _ in range(2)]
        return MultiHeadAttention.Cache(out[0], out[1])

    def forward(self, x, cache=None, pos=None):
        from .. import ops

        B, T = x.shape[0], x.shape[1]
        H, dh = self.num_heads, self.head_dim
        qkv = self.qkv(x)  # [B, T, 3D] sharded on the feature axis
        # heads axis inherits the mp sharding (3D = 3*H*dh, H-major)
        qkv = qkv.reshape([B, T, 3, H, dh]).transpose([2, 0, 3, 1, 4])
        qkv = _constrain(qkv, self.mesh, P(None, None, "mp", None, None))
        q, k, v = qkv[0], qkv[1], qkv[2]  # [B, H, T, dh]
        from ..nn.functional import attention as attn_route

        if cache is not None:
            # static-capacity decode-append: write this step's K/V rows
            # at per-slot `pos`, attend position-masked over what the
            # slot holds. On one chip one Pallas kernel a layer does
            # both; on a real mesh plain XLA ops, which GSPMD partitions
            # over (dp -> batch, mp -> heads) exactly like the training
            # path (a traced pos cannot feed the flash kernel's static
            # q_offset seam).
            if pos is None:
                raise ValueError(
                    "cache decoding needs `pos` (per-slot write "
                    "positions [B] int32)"
                )
            from ..nn.layers.transformer import MultiHeadAttention

            ctx, k, v = attn_route.cached_append_attention(
                q, cache.k, cache.v, k, v, pos, scale=dh ** -0.5
            )
            new_cache = MultiHeadAttention.Cache(k, v)
            ctx = ctx.transpose([0, 2, 1, 3]).reshape([B, T, H * dh])
            ctx = _constrain(ctx, self.mesh, P(None, None, "mp"))
            return self.out_proj(ctx), new_cache

        route_flash = self.use_flash_attention
        plan = None
        if route_flash is None:  # AUTO: the flash-by-default policy
            # self.mesh is the job-wide hybrid mesh — or, inside a
            # pipeline stage, the rebound pp-free submesh — so the
            # policy routes on the axes that partition THIS program
            plan = attn_route.flash_plan(
                T, T, causal=self.causal,
                dropout_active=bool(self.dropout) and self.training,
                mesh=self.mesh, batch=B, heads=H,
            )
            route_flash = plan is not None
        elif route_flash:
            # FORCED flash still needs the shard plan: when the seam
            # declines (PADDLE_FLASH_SHARD=0, a mesh the seam cannot
            # cover, the async-dcn manual region) the dense form below
            # composes — a bare pallas_call inside a multi-device GSPMD
            # program has no partition rule and would fail to compile
            p = attn_route._shard_plan(self.mesh, int(B), int(H))
            if p is False:
                route_flash = False
            else:
                plan = ("plain",) if p is None else ("sharded",) + p
        if route_flash:
            ctx = attn_route.flash_core_routed(
                q, k, v, mesh=self.mesh, causal=self.causal, plan=plan
            )
            ctx = ctx.transpose([0, 2, 1, 3]).reshape([B, T, H * dh])
            ctx = _constrain(ctx, self.mesh, P(None, None, "mp"))
            return self.out_proj(ctx)
        scores = ops.matmul(q, k, transpose_y=True) * (dh ** -0.5)
        if self.causal:
            import numpy as np

            mask = np.triu(
                np.full((T, T), -1e9, dtype=np.float32), k=1
            )
            scores = scores + Tensor._wrap(
                jax.numpy.asarray(mask), stop_gradient=True
            )
        attn = F.softmax(scores, axis=-1)
        if self.dropout:
            attn = F.dropout(attn, p=self.dropout, training=self.training)
        ctx = ops.matmul(attn, v)  # [B, H, T, dh], heads sharded
        ctx = ctx.transpose([0, 2, 1, 3]).reshape([B, T, H * dh])
        ctx = _constrain(ctx, self.mesh, P(None, None, "mp"))
        return self.out_proj(ctx)


class ParallelGPTBlock(Layer):
    """Pre-LN GPT decoder block with tensor-parallel attention + MLP —
    the unit the BASELINE GPT-3 configs stack inside pipeline stages."""

    def __init__(self, d_model, num_heads, dim_feedforward=None,
                 dropout=0.0, causal=True, use_flash_attention=None):
        super().__init__()
        from ..nn.layers.norm import LayerNorm

        ffn = dim_feedforward or 4 * d_model
        self._d_model = d_model
        self.ln1 = LayerNorm(d_model)
        self.attn = ParallelMultiHeadAttention(
            d_model, num_heads, dropout=dropout, causal=causal,
            use_flash_attention=use_flash_attention,
        )
        self.ln2 = LayerNorm(d_model)
        # the block's program mesh, shared with its LN layers so the
        # fused-LN routing targets the same device set as the attention
        # routing — pipeline _Stage rebinds every Mesh-valued `.mesh`
        # (this one, the LNs', the TP layers') to its pp-free submesh
        self.mesh = self.attn.mesh
        self.ln1.mesh = self.mesh
        self.ln2.mesh = self.mesh
        self.fc1 = ColumnParallelLinear(d_model, ffn, gather_output=False)
        self.fc2 = RowParallelLinear(ffn, d_model, input_is_parallel=True)
        self.dropout = dropout

    def forward(self, x, cache=None, pos=None, adapter=None):
        if cache is not None:
            a, new_cache = self.attn(self.ln1(x), cache=cache, pos=pos)
        else:
            a, new_cache = self.attn(self.ln1(x)), None
        # residual-add + LN fused in one Pallas pass on TPU (the sum is
        # formed once; both the residual stream and its normalization
        # come back) — dense x+LN fallback elsewhere
        h, n2 = F.fused_residual_layer_norm(
            x, a, [self._d_model],
            self.ln2.weight, self.ln2.bias, self.ln2._epsilon,
            mesh=self.mesh,
        )
        m_in = self.fc1(n2)
        if adapter is not None and "adapter_A" in self._buffers:
            # per-slot LoRA delta on the fc1 projection (ISSUE 18
            # adapter fleets): rows gathered from the resident stacks
            # by the traced [B] id vector — one program serves every
            # adapter mix; row 0 is zeros, so id 0 adds exact zeros
            m_in = m_in + self._adapter_delta(n2, adapter)
        m = F.gelu(m_in)
        if self.dropout:
            m = F.dropout(m, p=self.dropout, training=self.training)
        out = h + self.fc2(m)
        return out if new_cache is None else (out, new_cache)

    def _adapter_delta(self, x, ids):
        """``scale * B[a] @ (A[a] @ x)`` with ``a`` the per-row adapter
        id: two batched low-rank einsums over rows gathered in-graph
        from the stacked buffers. ``B`` is sharded on the ffn axis like
        the ``fc1`` weight, so the delta lands feature-sharded exactly
        where ``fc1``'s output does."""
        scale = self._adapter_scale

        def d(xr, ar, br, ir):
            import jax.numpy as jnp

            xf = xr.astype(jnp.float32)
            a = ar[ir].astype(jnp.float32)   # [B, r, d]
            b = br[ir].astype(jnp.float32)   # [B, ffn, r]
            u = jnp.einsum("btd,brd->btr", xf, a)
            out = jnp.einsum("btr,bfr->btf", u, b)
            return (scale * out).astype(xr.dtype)

        out = AG.apply(
            d, (x, self.adapter_A, self.adapter_B, ids),
            name="adapter_delta")
        return _constrain(out, self.mesh, P(None, None, "mp"))

    def gen_cache(self, batch_size, max_length, dtype=None,
                  block_size=None, pool_blocks=None):
        return self.attn.gen_cache(batch_size, max_length, dtype,
                                   block_size=block_size,
                                   pool_blocks=pool_blocks)


def split(x, size, operation: str, axis: int = 0, num_partitions: Optional[int] = None,
          gather_out: bool = True, weight_attr=None, bias_attr=None,
          name=None):
    """paddle.distributed.split (collective.py:566): build-and-apply a
    model-parallel layer. size=(in,out) for 'linear' (axis=0 row-, axis=1
    column-parallel), (vocab,dim) for 'embedding'. Creates fresh parameters
    per call — construct the *ParallelLinear layers directly inside models.
    """
    if operation == "linear":
        if axis == 1:
            layer = ColumnParallelLinear(
                size[0], size[1], weight_attr=weight_attr,
                bias_attr=bias_attr, gather_output=gather_out,
            )
        elif axis == 0:
            layer = RowParallelLinear(
                size[0], size[1], weight_attr=weight_attr,
                bias_attr=bias_attr, input_is_parallel=not gather_out,
            )
        else:
            raise ValueError("split(linear) axis must be 0 or 1")
    elif operation == "embedding":
        layer = VocabParallelEmbedding(
            size[0], size[1], weight_attr=weight_attr
        )
    else:
        raise ValueError(f"unknown split operation {operation!r}")
    return layer(x)
