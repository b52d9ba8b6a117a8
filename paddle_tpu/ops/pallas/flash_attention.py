"""Flash attention as Pallas TPU kernels — forward AND backward.

The MXU-tiled counterpart of `nn/layers/ring_attention.py`'s XLA blockwise
path (reference gap: the CUDA side fuses attention via
operators/fused/fused_attention pieces and math/bert_encoder_functor.cu —
here the fusion is an explicit VMEM-resident online-softmax kernel).

Design:
  - K/V STREAM through the grid: grid = (batch*heads, q blocks, k blocks)
    with the online-softmax state (acc, m, l) in VMEM scratch carried
    across the innermost k iterations. Per-program VMEM is
    O(block_q*D + 2*block_k*D) — sequence length is bounded by HBM, not
    by VMEM. K/V of one head that fit `_RESIDENT_KV_BYTES` take the
    resident forward instead (one grid step a q block, a fori_loop over
    the key blocks).
  - the forward also emits the per-row logsumexp; backward is TWO Pallas
    kernels (FlashAttention-2 recompute form): a dq kernel streaming K/V
    per q block, and a dk/dv kernel streaming Q/dO per k block, both
    using p = exp(s - lse) and delta = rowsum(dO * O); the dk/dv kernel
    computes its tiles transposed (`_dkv_kernel`).
  - causal masking by global positions; fully-future blocks are skipped
    arithmetically (guarded compute) in fwd and bwd.

Precision follows the input: every `dot_general` takes its stored tiles
(q, k, v, dO) in the dtype the refs hold and accumulates in float32, so
bf16 inputs are single-pass bf16 MXU matmuls and float32 inputs are the
float32 matmuls they always were. The computed operands of the other
four matmuls (p for P·V and Pᵀ·dO, ds for dS·K and dSᵀ·Q) are rounded to
the stored dtype of the tile they meet just before the dot — a no-op for
float32. Everything else is float32 whatever comes in: the scores s, the
softmax state m, l and lse, delta, p, dp and ds as computed, the o carry
and the acc / dk / dv accumulators; outputs are cast to the input dtype
once, on the way out.

`q_offset` / `kv_offset` shift the global positions — the seam ring
attention uses to run this kernel on a rotated KV shard (its causal mask
must compare GLOBAL positions; fully-masked rows produce lse=-inf and a
zero partial, which the ring's partial-merge handles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -1e30



_DIM_SEMANTICS = ("parallel", "arbitrary")


def _tpu_params(*sem):
    """dimension_semantics hint: q/batch grid axes are parallel, the
    online-softmax k axis is sequential — lets Mosaic pipeline block
    fetches across grid steps (interpret mode ignores it)."""
    from jax.experimental.pallas import tpu as pltpu

    bad = [s for s in sem if s not in _DIM_SEMANTICS]
    if bad:
        raise ValueError(
            f"dimension_semantics {bad} not in {_DIM_SEMANTICS}")
    return pltpu.CompilerParams(dimension_semantics=tuple(sem))


def _dot(a, b, contract_a, contract_b):
    """One MXU matmul of two tiles as they are stored, float32 out.
    float32 tiles keep the ambient matmul precision (today's kernel);
    for anything narrower a precision says nothing, and Mosaic refuses a
    bf16 operand under an ambient `highest`, so those ask for the
    default."""
    both_f32 = a.dtype == b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        precision=None if both_f32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                         causal, scale, seq_k, q_offset, kv_offset):
    """Fast path for K/V that fit VMEM (`_RESIDENT_KV_BYTES`): this
    head's FULL K/V are resident and a fori_loop runs the online softmax
    (no per-grid-step scratch round-trips); the streaming kernel takes
    over beyond the VMEM budget.
    """
    from jax.experimental import pallas as pl

    q = q_ref[0]                                  # [block_q, D]
    block_q, d = q.shape
    qi = pl.program_id(1)
    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    n_k = seq_k // block_k

    def body(j, carry):
        o, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            k_pos = kv_offset + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(k_pos > q_pos, _NEG, s)
        m_new = jnp.maximum(m, s.max(axis=1))
        alive = m_new > _NEG / 2
        p = jnp.where(alive[:, None], jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.where(alive, jnp.exp(m - m_new), 1.0)
        l_new = l * corr + p.sum(axis=1)
        o_new = o * corr[:, None] + _dot(p.astype(v.dtype), v, 1, 0)
        return o_new, m_new, l_new

    o = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q,), _NEG, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    if causal and q_offset == 0 and kv_offset == 0:
        # aligned diagonal: skip fully-future key blocks
        hi = jnp.minimum(
            (qi * block_q + block_q + block_k - 1) // block_k, n_k
        )
    else:
        hi = n_k
    o, m, l = jax.lax.fori_loop(0, hi, body, (o, m, l))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (o / safe_l[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, _NEG, m + jnp.log(safe_l))
    lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


#: K+V bytes of one head the resident forward admits. Mosaic double-
#: buffers both blocks: compiled for v5e the kernel's scoped VMEM is
#: 2 x (K+V) + ~0.75 MiB against the 16 MiB default limit, so 8 MiB was
#: refused at D=128 in bf16 and f32 (16.5 / 16.75 MiB); 4 MiB compiles
#: at D in {64, 128} in both dtypes and leaves room for the Q/O/lse
#: blocks of a 256-row tile.
_RESIDENT_KV_BYTES = 4 << 20


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, block_q, block_k, n_k, causal, scale, q_offset,
                kv_offset):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: key block kj is (partially) visible to query block qi iff
    # kv_offset + kj*block_k <= q_offset + qi*block_q + block_q - 1
    visible = True
    if causal:
        visible = (kv_offset + kj * block_k
                   <= q_offset + qi * block_q + block_q - 1)

    @pl.when(visible)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, 1, 1) * scale                # [bq, bk]
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_offset + kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos > q_pos, _NEG, s)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        # fully-masked rows keep m == _NEG; their p must stay 0
        alive = m_new > _NEG / 2
        p = jnp.where(alive[:, None], jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        l_new = l_prev * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + _dot(p.astype(v.dtype), v, 1, 0))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)
        m = m_ref[:, 0]
        lse = jnp.where(l == 0.0, _NEG, m + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, block_q, block_k, n_k, causal, scale, q_offset,
               kv_offset):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    visible = True
    if causal:
        visible = (kv_offset + kj * block_k
                   <= q_offset + qi * block_q + block_q - 1)

    @pl.when(visible)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_offset + kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos > q_pos, _NEG, s)
        # masked entries must stay 0 even for fully-masked rows where
        # lse == _NEG too (exp(_NEG - _NEG) would be 1)
        p = jnp.where(s <= _NEG / 2, 0.0, jnp.exp(s - lse[:, None]))
        dp = _dot(do, v, 1, 1)
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += _dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k, n_q,
                causal, scale, q_offset, kv_offset):
    """Works on the TRANSPOSED tile [bk, bq] (sᵀ = K·Qᵀ), so pᵀ and dsᵀ
    come out as computed and all four dots are plain: contracting dim 0
    of both operands makes Mosaic transpose a whole [bq, bk] tile for
    each of Pᵀ·dO and dSᵀ·Q. What is transposed instead is lse and
    delta, [bq, 128] into rows [1, bq]: the same arrays the dq kernel
    reads, so the program around the kernels is the one it was."""
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    visible = True
    if causal:
        # query block qi sees key block kj iff its LAST query position is
        # at or past the key block's first position
        visible = (q_offset + qi * block_q + block_q - 1
                   >= kv_offset + kj * block_k)

    @pl.when(visible)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0].T[:1]                      # [1, bq]
        delta = delta_ref[0].T[:1]
        st = _dot(k, q, 1, 1) * scale               # [bk, bq]
        if causal:
            k_pos = kv_offset + kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(k_pos > q_pos, _NEG, st)
        pt = jnp.where(st <= _NEG / 2, 0.0, jnp.exp(st - lse))
        dv_acc[...] += _dot(pt.astype(do.dtype), do, 1, 0)    # [bk, D]
        dpt = _dot(v, do, 1, 1)
        dst = pt * (dpt - delta) * scale
        dk_acc[...] += _dot(dst.astype(q.dtype), q, 1, 0)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _forward(q, k, v, *, causal, block_q, block_k, scale, interpret,
             q_offset=0, kv_offset=0, return_lse=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"flash_attention: S={S}/Sk={Sk} must be divisible by "
            f"block_q={block_q}/block_k={block_k}"
        )
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    if Sk * D * k.dtype.itemsize * 2 <= _RESIDENT_KV_BYTES:
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel_resident, block_k=block_k, causal=causal,
                scale=scale, seq_k=Sk, q_offset=q_offset,
                kv_offset=kv_offset,
            ),
            out_shape=(
                jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, S, 128), jnp.float32),
            ),
            grid=(B * H, S // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 128), lambda b, i: (b, i, 0)),
            ),
            compiler_params=_tpu_params("parallel", "parallel"),
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    else:
        n_k = Sk // block_k
        grid = (B * H, S // block_q, n_k)
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
                causal=causal, scale=scale, q_offset=q_offset,
                kv_offset=kv_offset,
            ),
            out_shape=(
                jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, S, 128), jnp.float32),
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            compiler_params=_tpu_params(
                "parallel", "parallel", "arbitrary"),
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    out = out.reshape(B, H, S, D)
    lse = lse[:, :, 0].reshape(B, H, S)
    if return_lse:
        return out, lse
    return out


def _backward(q, k, v, out, lse, g, *, causal, block_q, block_k, scale,
              interpret, q_offset=0, kv_offset=0):
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )                                                # rowsum(dO * O)
    return _backward_with_delta(
        q, k, v, g, lse, delta, causal=causal, block_q=block_q,
        block_k=block_k, scale=scale, interpret=interpret,
        q_offset=q_offset, kv_offset=kv_offset,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=False, block_q=256, block_k=256,
                    scale=None, interpret=False, q_offset=0, kv_offset=0):
    """Exact softmax attention, Pallas-tiled on TPU. [B, H, S, D] in/out.
    `interpret=True` runs the kernels in the Pallas interpreter (CPU
    testing). Both forward and backward are hand kernels; K/V stream
    through the grid, so S is HBM-bound (tested at 32k), not VMEM-bound.

    `q_offset`/`kv_offset` (static ints) shift the GLOBAL positions the
    causal mask compares — the decode-append seam (ISSUE 9): a cached
    Sq != Sk suffix attends end-aligned by passing `q_offset = Sk - Sq`,
    computing the same function as the dense end-aligned fallback
    (`qpos = arange(Sq) + (Sk - Sq)`) without materializing scores.
    """
    return _forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, interpret=interpret, q_offset=q_offset,
        kv_offset=kv_offset,
    )


def _fa_fwd(q, k, v, causal, block_q, block_k, scale, interpret,
            q_offset, kv_offset):
    out, lse = _forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, interpret=interpret, q_offset=q_offset,
        kv_offset=kv_offset, return_lse=True,
    )
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, scale, interpret, q_offset,
            kv_offset, res, g):
    q, k, v, out, lse = res
    return _backward(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, scale=scale, interpret=interpret,
        q_offset=q_offset, kv_offset=kv_offset,
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_partial(q, k, v, causal, block_q, block_k, scale,
                            interpret, q_offset, kv_offset):
    """Ring-attention building block: same kernels with GLOBAL position
    offsets, returning the UNMERGED partial (out, lse) for this KV shard.
    Fully-masked rows return (0, -inf) — the ring's partial-merge is the
    normalizer."""
    return _forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, interpret=interpret, q_offset=q_offset,
        kv_offset=kv_offset, return_lse=True,
    )


def _fap_fwd(q, k, v, causal, block_q, block_k, scale, interpret,
             q_offset, kv_offset):
    out, lse = _forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, interpret=interpret, q_offset=q_offset,
        kv_offset=kv_offset, return_lse=True,
    )
    return (out, lse), (q, k, v, out, lse)


def _fap_bwd(causal, block_q, block_k, scale, interpret, q_offset,
             kv_offset, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    # the partial's consumers differentiate through the merge, which
    # rescales g_out; the lse cotangent folds into delta:
    #   d/ds [out, lse] -> ds = p*(dp - delta) + p * g_lse
    # implemented by shifting delta with -g_lse per row
    delta = jnp.sum(
        g_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ) - g_lse.astype(jnp.float32)
    # reuse the standard backward with the adjusted delta by inlining:
    B, H, S, D = q.shape
    lse_adj = lse
    # _backward recomputes delta internally; call a variant that accepts
    # the adjusted delta instead
    return _backward_with_delta(
        q, k, v, g_out, lse_adj, delta, causal=causal, block_q=block_q,
        block_k=block_k, scale=scale, interpret=interpret,
        q_offset=q_offset, kv_offset=kv_offset,
    )


def _backward_with_delta(q, k, v, g, lse, delta, *, causal, block_q,
                         block_k, scale, interpret, q_offset, kv_offset):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    n_q, n_k = S // block_q, Sk // block_k
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    dor = g.reshape(B * H, S, D).astype(q.dtype)
    lse128 = jnp.broadcast_to(
        lse.reshape(B * H, S)[..., None], (B * H, S, 128))
    delta128 = jnp.broadcast_to(
        delta.reshape(B * H, S)[..., None], (B * H, S, 128))
    common = dict(
        block_q=block_q, block_k=block_k, causal=causal, scale=scale,
        q_offset=q_offset, kv_offset=kv_offset,
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **common),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_dq",
    )(qr, kr, vr, dor, lse128, delta128)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **common),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype),
        ),
        grid=(B * H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_dkv",
    )(kr, vr, qr, dor, lse128, delta128)
    return (
        dq.reshape(B, H, S, D),
        dk.reshape(B, H, Sk, D),
        dv.reshape(B, H, Sk, D),
    )


flash_attention_partial.defvjp(_fap_fwd, _fap_bwd)
