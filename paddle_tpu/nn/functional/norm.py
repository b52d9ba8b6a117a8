"""Normalization functionals (reference: python/paddle/nn/functional/norm.py
over operators/batch_norm_op.*, layer_norm_op.*, group_norm_op.cc).

batch_norm returns the updated running stats alongside the output instead of
mutating them inside the kernel (functional form — the Layer wrappers own the
buffer update so the same code paths trace cleanly under jit)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import autograd as AG
from ...core.tensor import Tensor

__all__ = ["batch_norm", "layer_norm", "fused_residual_layer_norm",
           "group_norm", "instance_norm", "normalize", "local_response_norm"]


def _stat_axes(ndim, data_format):
    ch = 1 if data_format.startswith("NC") else ndim - 1
    return tuple(i for i in range(ndim) if i != ch), ch


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    """Returns out; in training mode also refreshes running stats in-place on
    the provided buffer Tensors (eager) — under trace the Layer handles stats
    functionally via batch_norm_stats."""
    ndim = x._data.ndim
    axes, ch = _stat_axes(ndim, data_format)
    use_batch_stats = training and not use_global_stats

    bshape = [1] * ndim
    bshape[ch] = x._data.shape[ch]

    if use_batch_stats:
        # TPU-first formulation (not measured on the chip: no cell runs
        # a batch norm):
        #  - stats accumulate in f32 but the normalization APPLIES in the
        #    input dtype, so bf16 activations are never round-tripped
        #    through f32 HBM writes (the reference's CUDA kernel does the
        #    same internally: batch_norm_op.cu accumulates in float);
        #  - one fused stat pass (mean, mean-of-squares) instead of
        #    mean-then-var, and the apply is folded to out = x*scale+bias
        #    with per-channel [C] vectors — 2 fusable elementwise ops whose
        #    VJP reductions XLA fuses into a single variadic reduce.
        def f(a, *wb):
            af = a.astype(jnp.float32) if a.dtype != jnp.float32 else a
            mean = jnp.mean(af, axis=axes)
            meansq = jnp.mean(jnp.square(af), axis=axes)
            var = jnp.maximum(meansq - jnp.square(mean), 0.0)
            r = jax.lax.rsqrt(var + epsilon)
            i = 0
            if weight is not None:
                scale = wb[i].astype(jnp.float32) * r
                i += 1
            else:
                scale = r
            if bias is not None:
                shift = wb[i].astype(jnp.float32) - mean * scale
            else:
                shift = -mean * scale
            out = a * scale.astype(a.dtype).reshape(bshape) + shift.astype(
                a.dtype
            ).reshape(bshape)
            return out, mean, var

        args = (x,) + tuple(p for p in (weight, bias) if p is not None)
        out, mean_t, var_t = AG.apply(f, args, name="batch_norm")
        mean_t.stop_gradient = True
        var_t.stop_gradient = True
        # EMA update (paddle: mean = mean*momentum + batch_mean*(1-m)).
        if getattr(mean_t, "_static_var", None) is not None:
            # static-graph recording: the EMA is recorded as ops and the
            # buffers registered as persistable-state writes the Executor
            # writes back after each run (the scope-variable update of
            # batch_norm_op's MeanOut/VarianceOut)
            from ...static.program import default_main_program

            ema = AG.apply(
                lambda rm, rv, mt, vt: (
                    rm * momentum + mt * (1 - momentum),
                    rv * momentum + vt * (1 - momentum),
                ),
                (running_mean, running_var, mean_t, var_t),
                name="bn_stat_ema",
            )
            prog = default_main_program()
            prog.record_state_write(running_mean, ema[0])
            prog.record_state_write(running_var, ema[1])
            return out
        # eager / jit trace: set_value is trace-safe (under to_static
        # capture the buffer holds a traced value which the program
        # wrapper threads out as extra state)
        running_mean.set_value(
            running_mean._data * momentum + mean_t._data * (1 - momentum)
        )
        running_var.set_value(
            running_var._data * momentum + var_t._data * (1 - momentum)
        )
        return out

    rm, rv = running_mean._data, running_var._data

    def f(a, *wb):
        out = (a - rm.reshape(bshape)) / jnp.sqrt(rv.reshape(bshape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = (x,) + tuple(p for p in (weight, bias) if p is not None)
    return AG.apply(f, args, name="batch_norm")


def _ln_row_factoring(mesh, rows, row_floor):
    """Shard the flattened LN row dim over the mesh axes that partition
    the program (a row op shards over any product of batch/model axes).
    Returns the axis tuple, () for an all-trivial mesh, or None when
    rows don't tile per shard — or when a size>1 axis is outside the
    shared dp/dcn/ici/mp allowlist (comm.DP_AXES, the same policy as
    attention.shard_factoring): 'pp' stages run stage-LOCAL programs on
    pp-free submeshes (their activations differ per stage, so a
    shard_map over the job-wide mesh would be both unsound and the
    wrong device set — layers that thread a rebound submesh via the
    `mesh=` kwarg route through it), and 'sp' sequence sharding belongs
    to ring attention's schedule."""
    from ...distributed import comm as _comm

    if mesh is None:
        return None
    axes = _comm.partitioning_axes(mesh)
    if any(a not in _comm.DP_AXES + ("mp",) for a in axes):
        return None
    deg = 1
    for a in axes:
        deg *= int(mesh.shape[a])
    if rows % deg or (rows // deg) % row_floor:
        return None
    return axes


def _fused_ln_route(raw, normalized_shape, weight, bias, mesh=None):
    """Route LayerNorm to the Pallas fused kernel? Returns None for the
    dense XLA path, or (interpret, mesh, row_axes) — mesh is None for the
    single-device kernel, a Mesh for the shard_map seam
    (ops/pallas/sharded.py) with rows sharded over `row_axes`.

    Eligibility: last-axis-only normalization with both affine params, a
    lane-tileable layout (D % 128 == 0, rows % 8 — the MXU/VPU tiling
    floor), a float dtype, and a TPU backend. Multi-device programs
    (round 7) route through the shard_map seam when the rows tile per
    shard and `PADDLE_FLASH_SHARD` != 0 (the shared sharded-hot-path
    escape hatch). `PADDLE_FUSED_LN=0` disables the kernel entirely
    (dense escape hatch); `=interpret` forces the routed path through
    the Pallas interpreter off-TPU (CPU CI).

    `mesh` is the caller's program mesh when it knows one — a pipeline
    stage's rebound pp-free submesh (ParallelGPTBlock threads it via
    F.layer_norm/fused_residual_layer_norm's `mesh=` kwarg, mirroring
    ParallelMultiHeadAttention's flash_plan(mesh=...)); mesh-less
    callers resolve the hybrid/default-group mesh like attention does.
    """
    import os

    mode = os.environ.get("PADDLE_FUSED_LN", "1").strip().lower()
    if mode in ("0", "false", "off"):
        return None
    if weight is None or bias is None or len(normalized_shape) != 1:
        return None
    if raw.ndim < 2 or raw.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    D = raw.shape[-1]
    rows = raw.size // D if D else 0
    row_floor = 16 if raw.dtype == jnp.bfloat16 else 8
    if D % 128 != 0 or rows == 0 or rows % row_floor != 0:
        return None
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and mode != "interpret":
        return None
    interp = not on_tpu
    if on_tpu and len(jax.devices()) == 1:
        return (False, None, ())
    from ...distributed import overlap as _ov
    from .attention import _routing_mesh, flash_shard_enabled

    if _ov.in_manual_dcn():
        # inside the async-dcn manual region a nested shard_map over
        # the already-manual 'dcn' axis is ill-formed — dense composes
        return None
    # multi-device program (or interpret-mode CI standing in for one): a
    # bare pallas_call has no partitioning rule — route through the
    # shard_map seam, rows sharded over the axes that partition the
    # program. _routing_mesh is the SAME mesh resolution the attention
    # policy uses (hybrid/default-group on TPU, declared-hybrid-only in
    # interpret mode) so CPU CI exercises the seam the pod runs.
    if mesh is None:
        mesh = _routing_mesh()
    if mesh is None or mesh.size <= 1:
        if on_tpu:
            # mesh-less multi-device TPU program: no axes to map — keep
            # the dense form GSPMD can shard (the r6 decline); a trivial
            # mesh runs the plain single-device kernel
            return None if mesh is None else (False, None, ())
        return (interp, None, ())
    if not flash_shard_enabled():
        return None
    axes = _ln_row_factoring(mesh, rows, row_floor)
    if axes is None:
        return None
    return (interp, mesh if axes else None, axes)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None, mesh=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)
    nd = len(normalized_shape)
    axes = tuple(range(x._data.ndim - nd, x._data.ndim))

    route = _fused_ln_route(x._data, normalized_shape, weight, bias,
                            mesh=mesh)
    if route is not None:
        from ... import profiler as _prof

        interp, mesh, row_axes = route
        # dispatched OFF the amp black list on purpose: the kernel keeps
        # bf16 activations bf16 (f32 stats internally) instead of the
        # dense path's f32 HBM round trip (same move as r5 batch_norm)
        if mesh is not None:
            from ...ops.pallas.sharded import sharded_layer_norm

            with _prof.device_annotation("layer_norm::sharded_fused"):
                return AG.apply(
                    lambda a, w, b: sharded_layer_norm(
                        a, w, b, epsilon, interp, mesh, row_axes
                    ),
                    (x, weight, bias), name="sharded_layer_norm",
                )
        from ...ops.pallas.layer_norm import fused_layer_norm

        with _prof.device_annotation("layer_norm::fused"):
            return AG.apply(
                lambda a, w, b: fused_layer_norm(a, w, b, epsilon, interp),
                (x, weight, bias), name="fused_layer_norm",
            )

    def f(a, *wb):
        mean = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mean) / jnp.sqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = (x,) + tuple(p for p in (weight, bias) if p is not None)
    return AG.apply(f, args, name="layer_norm")


def fused_residual_layer_norm(x, residual, normalized_shape, weight=None,
                              bias=None, epsilon=1e-5, name=None,
                              mesh=None):
    """(x + residual, LayerNorm(x + residual)) — the pre-LN block seam.

    On TPU this is ONE Pallas kernel (ops/pallas/layer_norm.py
    fused_add_layer_norm): the sum is formed once in VMEM and both the
    residual stream and its normalization come back without the dense
    path's extra HBM write+2 reads of the sum. Dense fallback elsewhere.
    Returns (sum, normalized) Tensors.
    """
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)

    route = _fused_ln_route(x._data, normalized_shape, weight, bias,
                            mesh=mesh)
    if route is not None and x._data.shape == residual._data.shape:
        from ... import profiler as _prof

        interp, mesh, row_axes = route
        if mesh is not None:
            from ...ops.pallas.sharded import sharded_add_layer_norm

            with _prof.device_annotation("layer_norm::sharded_residual"):
                return AG.apply(
                    lambda a, r, w, b: sharded_add_layer_norm(
                        a, r, w, b, epsilon, interp, mesh, row_axes
                    ),
                    (x, residual, weight, bias),
                    name="sharded_residual_layer_norm",
                )
        from ...ops.pallas.layer_norm import fused_add_layer_norm

        with _prof.device_annotation("layer_norm::fused_residual"):
            return AG.apply(
                lambda a, r, w, b: fused_add_layer_norm(
                    a, r, w, b, epsilon, interp
                ),
                (x, residual, weight, bias),
                name="fused_residual_layer_norm",
            )
    s = x + residual
    return s, layer_norm(s, normalized_shape, weight, bias, epsilon,
                         mesh=mesh)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    ndim = x._data.ndim
    ch = 1 if data_format.startswith("NC") else ndim - 1
    C = x._data.shape[ch]
    if C % num_groups != 0:
        raise ValueError("channels not divisible by num_groups")

    def f(a, *wb):
        if ch != 1:
            a = jnp.moveaxis(a, ch, 1)
        n = a.shape[0]
        grouped = a.reshape((n, num_groups, -1))
        mean = jnp.mean(grouped, axis=-1, keepdims=True)
        var = jnp.var(grouped, axis=-1, keepdims=True)
        out = ((grouped - mean) / jnp.sqrt(var + epsilon)).reshape(a.shape)
        bshape = [1] * out.ndim
        bshape[1] = C
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        if ch != 1:
            out = jnp.moveaxis(out, 1, ch)
        return out

    args = (x,) + tuple(p for p in (weight, bias) if p is not None)
    return AG.apply(f, args, name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    ndim = x._data.ndim
    ch = 1 if data_format.startswith("NC") else ndim - 1
    axes = tuple(i for i in range(ndim) if i not in (0, ch))
    bshape = [1] * ndim
    bshape[ch] = x._data.shape[ch]

    def f(a, *wb):
        mean = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mean) / jnp.sqrt(var + eps)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = (x,) + tuple(p for p in (weight, bias) if p is not None)
    return AG.apply(f, args, name="instance_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(a):
        n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)

    return AG.apply(f, (x,), name="normalize")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    ndim = x._data.ndim
    ch = 1 if data_format.startswith("NC") else ndim - 1

    def f(a):
        sq = a * a
        if ch != 1:
            sq = jnp.moveaxis(sq, ch, 1)
        half = size // 2
        pad = [(0, 0)] * sq.ndim
        pad[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad)
        acc = sum(
            jnp.take(padded, jnp.arange(i, i + sq.shape[1]), axis=1)
            for i in range(size)
        )
        if ch != 1:
            acc = jnp.moveaxis(acc, 1, ch)
        return a / (k + alpha * acc) ** beta

    return AG.apply(f, (x,), name="local_response_norm")
