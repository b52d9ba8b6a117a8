"""Fused LayerNorm — forward AND backward — as Pallas TPU kernels, plus
the fused residual-add+LayerNorm the pre-LN decoder block wants.

Why a hand kernel: under bf16 amp the dense
`layer_norm` functional sits on the AMP black list, so every decoder LN
round-trips its activation through f32 HBM (cast up, two reduction
passes, cast down) — 2 LNs x 24 layers x [B*S, 1024] per step. The
kernel keeps the activation in its input dtype end to end, computes the
row statistics once in f32 VMEM registers, and applies the normalization
as one fused pass; backward recomputes x_hat from the saved (mean, rstd)
instead of storing it (FlashAttention-style recompute form — the same
trade the reference's fused_layer_norm CUDA op makes in
operators/fused/fused_layernorm_*).

Layout contract: x is [R, D] (callers flatten leading dims), D is the
normalized axis, weight/bias are [1, D]. Row statistics travel in the
(block_r, 128) lane-broadcast form (same trick as the flash kernel's
lse output — TPU outputs want a 128-wide lane dim).

The residual-add variant computes s = x + y ONCE and emits both s (the
residual stream the block carries forward) and LN(s) — the dense path
writes s to HBM, re-reads it for the mean pass, re-reads for the var
pass; here it is read once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _tpu_params


#: f32 working-set budget of one (block_r, D) tile. The add-LN forward
#: holds four (block_r, D) blocks in the I/O dtype, double-buffered, plus
#: a handful of f32 temporaries of the same extent; at 1 MiB per f32
#: tile that is <= 8 + ~5 MiB, inside the 16 MiB scoped-VMEM default
#: (a 512 x 1024 f32 tile measured 17.01 MiB and was refused).
_TILE_F32_BYTES = 1 << 20


def _pick_block_r(R: int, D: int, dtype) -> int:
    """Largest power-of-two row tile dividing R whose f32 (tile, D)
    working copy fits `_TILE_F32_BYTES` (at most 512 rows); bf16
    sublanes pack 16 rows, so bf16 tiles stay multiples of 16. The
    kernels require the tile to DIVIDE R (the grid would silently drop
    tail rows otherwise) — callers that can't guarantee rows % floor == 0
    must use the dense path (`nn.functional.layer_norm` gates on exactly
    this)."""
    floor = 16 if dtype == jnp.bfloat16 else 8
    b = 512
    while b > floor and b * D * 4 > _TILE_F32_BYTES:
        b //= 2
    while b >= floor and R % b:
        b //= 2
    if b < floor or R % b:
        raise ValueError(
            f"fused_layer_norm: rows={R} must be a multiple of {floor} "
            f"for {jnp.dtype(dtype).name} tiling; use the dense "
            "layer_norm path for this shape"
        )
    return b


def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1)
    xc = x - mu[:, None]
    var = jnp.mean(xc * xc, axis=1)
    rs = jax.lax.rsqrt(var + eps)
    y = xc * rs[:, None]
    o_ref[...] = (
        y * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    ).astype(o_ref.dtype)
    mu_ref[...] = jnp.broadcast_to(mu[:, None], mu_ref.shape)
    rs_ref[...] = jnp.broadcast_to(rs[:, None], rs_ref.shape)


def _add_ln_fwd_kernel(x_ref, y_ref, w_ref, b_ref, s_ref, o_ref, mu_ref,
                       rs_ref, *, eps):
    s32 = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    s_ref[...] = s32.astype(s_ref.dtype)
    # normalize what downstream actually sees: the stored-dtype sum (bf16
    # residual streams must match the dense x+y; stats still run f32)
    s = s_ref[...].astype(jnp.float32)
    mu = jnp.mean(s, axis=1)
    sc = s - mu[:, None]
    var = jnp.mean(sc * sc, axis=1)
    rs = jax.lax.rsqrt(var + eps)
    o_ref[...] = (
        sc * rs[:, None] * w_ref[0].astype(jnp.float32)
        + b_ref[0].astype(jnp.float32)
    ).astype(o_ref.dtype)
    mu_ref[...] = jnp.broadcast_to(mu[:, None], mu_ref.shape)
    rs_ref[...] = jnp.broadcast_to(rs[:, None], rs_ref.shape)


def _ln_bwd_kernel(x_ref, w_ref, mu_ref, rs_ref, g_ref, dx_ref, dw_ref,
                   db_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[:, 0]
    rs = rs_ref[:, 0]
    xhat = (x - mu[:, None]) * rs[:, None]
    w = w_ref[0].astype(jnp.float32)
    dxhat = g * w
    m1 = jnp.mean(dxhat, axis=1)
    m2 = jnp.mean(dxhat * xhat, axis=1)
    dx_ref[...] = (
        rs[:, None] * (dxhat - m1[:, None] - xhat * m2[:, None])
    ).astype(dx_ref.dtype)
    # per-row-block partial dgamma/dbeta, folded to ONE (8, D) sublane
    # tile per block (an output block's second-minor dim must be a
    # multiple of 8 — a (1, D) block does not lower); the cross-block sum
    # is one tiny [8 * n_blocks, D] reduce outside the kernel
    br, d = x.shape
    dw_ref[...] = jnp.sum((g * xhat).reshape(br // 8, 8, d), axis=0)
    db_ref[...] = jnp.sum(g.reshape(br // 8, 8, d), axis=0)


def _ln_forward(x2d, w2d, b2d, eps, interpret):
    from jax.experimental import pallas as pl

    R, D = x2d.shape
    br = _pick_block_r(R, D, x2d.dtype)
    out, mu, rs = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), x2d.dtype),
            jax.ShapeDtypeStruct((R, 128), jnp.float32),
            jax.ShapeDtypeStruct((R, 128), jnp.float32),
        ),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
        ),
        compiler_params=_tpu_params("parallel"),
        interpret=interpret,
        name="ln_fwd",
    )(x2d, w2d, b2d)
    return out, mu[:, 0], rs[:, 0]


def _ln_backward(x2d, w2d, mu, rs, g2d, interpret):
    from jax.experimental import pallas as pl

    R, D = x2d.shape
    br = _pick_block_r(R, D, x2d.dtype)
    n = R // br
    mu128 = jnp.broadcast_to(mu[:, None], (R, 128))
    rs128 = jnp.broadcast_to(rs[:, None], (R, 128))
    dx, dwp, dbp = pl.pallas_call(
        _ln_bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((R, D), x2d.dtype),
            jax.ShapeDtypeStruct((8 * n, D), jnp.float32),
            jax.ShapeDtypeStruct((8 * n, D), jnp.float32),
        ),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (i, 0)),
        ),
        compiler_params=_tpu_params("parallel"),
        interpret=interpret,
        name="ln_bwd",
    )(x2d, w2d, mu128, rs128, g2d)
    return dx, dwp.sum(axis=0), dbp.sum(axis=0)


def _flatten(x):
    D = x.shape[-1]
    return x.reshape(-1, D), x.shape


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, weight, bias, eps=1e-5, interpret=False):
    """LayerNorm over the last axis of x ([..., D]); weight/bias [D].
    Input-dtype in/out, f32 statistics. Hand fwd+bwd Pallas kernels."""
    x2d, shape = _flatten(x)
    out, _, _ = _ln_forward(
        x2d, weight.reshape(1, -1), bias.reshape(1, -1), eps, interpret
    )
    return out.reshape(shape)


def _fln_fwd(x, weight, bias, eps, interpret):
    x2d, shape = _flatten(x)
    out, mu, rs = _ln_forward(
        x2d, weight.reshape(1, -1), bias.reshape(1, -1), eps, interpret
    )
    return out.reshape(shape), (x2d, weight, mu, rs, shape)


def _fln_bwd(eps, interpret, res, g):
    x2d, weight, mu, rs, shape = res
    dx, dw, db = _ln_backward(
        x2d, weight.reshape(1, -1), mu, rs,
        g.reshape(x2d.shape).astype(x2d.dtype), interpret,
    )
    return (dx.reshape(shape), dw.astype(weight.dtype).reshape(weight.shape),
            db.astype(weight.dtype).reshape(weight.shape))


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_add_layer_norm(x, y, weight, bias, eps=1e-5, interpret=False):
    """(x + y, LayerNorm(x + y)) in one pass — the pre-LN decoder block's
    residual seam (s feeds the next residual add, LN(s) feeds the MLP)."""
    s, out, _, _ = _add_ln_forward(x, y, weight, bias, eps, interpret)
    return s, out


def _add_ln_forward(x, y, weight, bias, eps, interpret):
    from jax.experimental import pallas as pl

    x2d, shape = _flatten(x)
    y2d = y.reshape(x2d.shape)
    R, D = x2d.shape
    br = _pick_block_r(R, D, x2d.dtype)
    s, out, mu, rs = pl.pallas_call(
        functools.partial(_add_ln_fwd_kernel, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), x2d.dtype),
            jax.ShapeDtypeStruct((R, D), x2d.dtype),
            jax.ShapeDtypeStruct((R, 128), jnp.float32),
            jax.ShapeDtypeStruct((R, 128), jnp.float32),
        ),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
            pl.BlockSpec((br, 128), lambda i: (i, 0)),
        ),
        compiler_params=_tpu_params("parallel"),
        interpret=interpret,
        name="ln_residual_fwd",
    )(x2d, y2d, weight.reshape(1, -1), bias.reshape(1, -1))
    return (s.reshape(shape), out.reshape(shape), mu[:, 0], rs[:, 0])


def _fadd_ln_fwd(x, y, weight, bias, eps, interpret):
    s, out, mu, rs = _add_ln_forward(x, y, weight, bias, eps, interpret)
    s2d = s.reshape(-1, s.shape[-1])
    return (s, out), (s2d, weight, mu, rs, x.shape)


def _fadd_ln_bwd(eps, interpret, res, g):
    s2d, weight, mu, rs, shape = res
    gs, go = g
    ds, dw, db = _ln_backward(
        s2d, weight.reshape(1, -1), mu, rs,
        go.reshape(s2d.shape).astype(s2d.dtype), interpret,
    )
    # both addends receive d(s) = dLN/ds + the direct s cotangent
    dsum = (ds.reshape(shape) + gs.astype(ds.dtype)).astype(ds.dtype)
    return (dsum, dsum, dw.astype(weight.dtype).reshape(weight.shape),
            db.astype(weight.dtype).reshape(weight.shape))


fused_add_layer_norm.defvjp(_fadd_ln_fwd, _fadd_ln_bwd)
