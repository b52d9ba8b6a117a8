"""Comm/compute overlap for the multi-device hot path (ISSUE 6):

1. Collective-matmul decomposition for the tensor-parallel linear layers
   ("Overlap communication with computation in collective matmuls" /
   MLPerf-on-TPU-pods lineage). The row-parallel contraction's psum is
   split into per-chunk `ppermute` ring steps interleaved with the
   matmul chunks: at ring step s each device computes its partial for
   one output-row chunk and adds the accumulator arriving from its ring
   neighbor — the partial matmul for step s+1 has no data dependency on
   the incoming accumulator, so XLA's async collectives overlap each
   ppermute with the next chunk's MXU work instead of serializing one
   monolithic all-reduce after the full matmul. The column-parallel
   gather is pipelined the same way: per-row-chunk local matmuls with
   each chunk's all-gather issued while the next chunk computes.
   Enabled by `PADDLE_TP_OVERLAP=1` (default off: the r6 GSPMD
   sharding-propagation form stays the default until the overlap win is
   measured across chips: no cell of the benchmark runs a mesh yet).

2. Async DCN-hop gradient reduction ("EQuARX" motivation: the dcn hop
   is the slow, overlappable piece). The r6 hierarchical mesh leaves the
   WHOLE grad reduction to GSPMD, which (via the all-reduce combiner)
   tends to batch it after the full backward. Here the step's
   value_and_grad runs inside a `shard_map` that is MANUAL over 'dcn'
   and auto over every other axis: within a dcn group, GSPMD still owns
   the fast ici/mp collectives, while the inter-group (cross-pod) hop is
   an EXPLICIT per-gradient `lax.pmean` placed at each grad's definition
   point in the backward dataflow — so the slow collective for layer N's
   grads can start the moment layer N's backward finishes, behind the
   remaining layers' compute, and the combiner cannot sink it to the
   end. Enabled by `DistributedStrategy.async_dcn_allreduce` (requires
   `hierarchical_allreduce`). Numerically identical to the implicit
   form WHEN the loss is a fixed-divisor batch mean (the default
   `cross_entropy`/`mse_loss` reduction): an equal-sized-group mean of
   means IS the global mean (parity gated in
   tests/test_sharded_hot_path.py). A loss that is NOT such a mean —
   `reduction='sum'`, or a masked mean whose denominator (e.g. live
   token count) varies per dcn group — composes differently: the
   per-group losses are pmean'd, so a sum-reduced loss comes out
   scaled by 1/dcn and a variable-denominator mean is biased toward
   small-denominator groups. Keep the default batch-mean reduction (or
   any per-element loss whose divisor is the same on every dcn shard)
   under this flag.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import comm

__all__ = [
    "tp_overlap_enabled", "in_manual_dcn", "row_parallel_overlap",
    "column_gather_overlap", "dcn_value_and_grad",
]


def tp_overlap_enabled() -> bool:
    v = os.environ.get("PADDLE_TP_OVERLAP", "0").strip().lower()
    return v not in ("", "0", "false", "off")


# True while dcn_value_and_grad traces its manual-over-'dcn' body. The
# hot-path routers (attention._shard_plan, norm._fused_ln_route,
# row_overlap_plan) consult it and decline: opening a NESTED shard_map
# whose specs mention the already-manual 'dcn' axis is ill-formed, so
# inside the async-dcn region the model composes through its dense /
# implicit-GSPMD forms (routing is a trace-time Python decision, which
# is exactly when this flag is set).
_MANUAL_DCN = False


def in_manual_dcn() -> bool:
    return _MANUAL_DCN


def _dp_row_axes(mesh, rows, chunks):
    """Row-shard spec element for the overlap region: the dp axes when
    the flattened row count tiles (rows % dp == 0 and the local rows
    still split into `chunks`); None when the mesh has no size>1 dp axis
    (rows replicated is exact — there is no dp redundancy); False when
    dp axes exist but the rows don't tile over them — the caller must
    DECLINE, because a shard_map with rows unsharded would all-gather
    the dp-sharded activation onto every dp replica and recompute the
    full matmul dp times, regressing below the un-overlapped form."""
    axes = tuple(
        a for a in comm.DP_AXES
        if a in mesh.shape and int(mesh.shape[a]) > 1
    )
    if not axes:
        return None
    deg = 1
    for a in axes:
        deg *= int(mesh.shape[a])
    if rows % deg or (rows // deg) % chunks:
        return False
    return axes[0] if len(axes) == 1 else axes


def row_overlap_plan(mesh, rows):
    """Eligibility for the overlapped TP matmuls: returns
    (mp, row_spec_elem) or None when the shapes don't chunk (mp must be
    >1 and the per-device rows must split into mp ring chunks)."""
    if in_manual_dcn():
        return None  # no nested shard_map inside the async-dcn region
    if mesh is None or "mp" not in mesh.shape:
        return None
    mp = int(mesh.shape["mp"])
    if mp <= 1:
        return None
    for ax in comm.partitioning_axes(mesh):
        # pp/sp carry stage-/sequence-LOCAL activations: a shard_map
        # over the job-wide mesh would assert replication that does not
        # hold (pipeline stages that rebind a pp-free submesh pass it)
        if ax not in comm.DP_AXES + ("mp",):
            return None
    row_ax = _dp_row_axes(mesh, rows, mp)
    if row_ax is False:
        return None  # dp-sharded rows that don't tile: decline
    local_rows = rows
    if row_ax is not None:
        for a in (row_ax if isinstance(row_ax, tuple) else (row_ax,)):
            local_rows //= int(mesh.shape[a])
    if local_rows % mp:
        return None
    return mp, row_ax


def _row_ring_body(xl, wl, bl, *, n, axis):
    """Per-device body: xl [Rl, in/mp], wl [in/mp, out], bl [out]|None.
    Reduce-scatter ring over row chunks + chunk all-gather:

    step s: device d computes its partial for chunk c = (d - s) mod n,
    adds the accumulator ppermuted in from d-1 (which carries the
    partials of devices d-s..d-1 for the same chunk), and passes it on.
    After n-1 steps device d owns the fully-reduced chunk (d+1) mod n;
    the all-gather + roll reassembles row order. The partial matmul of
    step s+1 does not read the incoming accumulator, so the ppermute
    overlaps with it.
    """
    Rl, _ = xl.shape
    chunk = Rl // n
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    xr = xl.reshape(n, chunk, xl.shape[1])
    acc = None
    for s in range(n):
        c = (idx - s) % n
        xs = jnp.take(xr, c, axis=0)  # [chunk, in/mp]
        part = jax.lax.dot_general(
            xs, wl, (((1,), (0,)), ((), ())),
            preferred_element_type=xs.dtype,
        )
        acc = part if acc is None else acc + part
        if s < n - 1:
            acc = jax.lax.ppermute(acc, axis, perm)
    g = jax.lax.all_gather(acc, axis)       # [n, chunk, out]
    g = jnp.roll(g, 1, axis=0)              # slot c now holds chunk c
    out = g.reshape(Rl, -1)
    if bl is not None:
        out = out + bl
    return out


def row_parallel_overlap(x, w, b, mesh, mp, row_ax, axis="mp"):
    """RowParallelLinear forward with the psum decomposed into the
    overlap ring: x [..., in] (feature axis sharded over mp — or
    replicated, shard_map slices it), w [in, out] row-sharded, b [out]
    replicated (added once after the reduction). Output replicated over
    mp, rows sharded over `row_ax` when the shapes tile."""
    from .. import profiler as _prof

    shape = x.shape[:-1] + (w.shape[-1],)
    x2d = x.reshape(-1, x.shape[-1])
    with _prof.device_annotation("tp_overlap::row_ring"):
        if b is None:
            body = functools.partial(
                lambda xl, wl, **kw: _row_ring_body(xl, wl, None, **kw),
                n=mp, axis=axis,
            )
            out = comm.shard_map(
                body, mesh,
                in_specs=(P(row_ax, axis), P(axis, None)),
                out_specs=P(row_ax, None),
            )(x2d, w)
        else:
            body = functools.partial(_row_ring_body, n=mp, axis=axis)
            out = comm.shard_map(
                body, mesh,
                in_specs=(P(row_ax, axis), P(axis, None), P()),
                out_specs=P(row_ax, None),
            )(x2d, w, b)
    return out.reshape(shape)


def _col_pipeline_body(xl, wl, bl, *, n, axis):
    """Per-device body: xl [Rl, in] (full features), wl [in, out/mp],
    bl [out/mp]|None. The output gather is pipelined per row chunk:
    chunk c's all-gather is issued as soon as its local matmul is done,
    while chunk c+1 computes."""
    Rl, _ = xl.shape
    chunk = Rl // n
    outs = []
    for c in range(n):
        xs = jax.lax.dynamic_slice_in_dim(xl, c * chunk, chunk, 0)
        part = jax.lax.dot_general(
            xs, wl, (((1,), (0,)), ((), ())),
            preferred_element_type=xs.dtype,
        )
        if bl is not None:
            part = part + bl
        g = jax.lax.all_gather(part, axis)  # [n, chunk, out/mp]
        outs.append(jnp.moveaxis(g, 0, 1).reshape(chunk, -1))
    return jnp.concatenate(outs, axis=0)


def column_gather_overlap(x, w, b, mesh, mp, row_ax, axis="mp"):
    """ColumnParallelLinear (gather_output=True) forward with the output
    all-gather pipelined behind per-chunk matmuls. w [in, out]
    column-sharded, b [out] sharded over mp."""
    from .. import profiler as _prof

    shape = x.shape[:-1] + (w.shape[-1],)
    x2d = x.reshape(-1, x.shape[-1])
    with _prof.device_annotation("tp_overlap::column_gather"):
        if b is None:
            body = functools.partial(
                lambda xl, wl, **kw: _col_pipeline_body(xl, wl, None, **kw),
                n=mp, axis=axis,
            )
            out = comm.shard_map(
                body, mesh,
                in_specs=(P(row_ax, None), P(None, axis)),
                out_specs=P(row_ax, None),
            )(x2d, w)
        else:
            body = functools.partial(_col_pipeline_body, n=mp, axis=axis)
            out = comm.shard_map(
                body, mesh,
                in_specs=(P(row_ax, None), P(None, axis), P(axis)),
                out_specs=P(row_ax, None),
            )(x2d, w, b)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# async DCN-hop gradient reduction
# ---------------------------------------------------------------------------


def dcn_value_and_grad(loss_of, mesh, p_raws, key, in_raws, label_raws,
                       quant=None):
    """value_and_grad of the training loss with the inter-node ('dcn')
    gradient reduction explicit and per-grad (manual over 'dcn', GSPMD
    auto over every other axis). `loss_of(p_tuple, b_raws, key, in_raws,
    label_raws) -> (loss, aux)` is TrainStep._loss_of; buffers must be
    empty (batch-statistic layers would change numerics per dcn group).

    Returns (loss, grads): loss is the global mean (a mean of the
    equal-sized per-group means), grads are the globally-reduced grads —
    numerically the implicit-GSPMD values PROVIDED the loss is a
    fixed-divisor batch mean (see module docstring: sum-reduced or
    variable-denominator losses scale/bias under the per-group pmean),
    with each grad's dcn pmean placed at its definition point in the
    backward dataflow.

    ``quant`` — a quantized_comm.resolve_policy pair ("int8"|"fp8",
    block) — swaps each grad's dcn pmean for the block-scaled
    ``quantized_pmean`` (ISSUE 10): the ici hop inside each dcn group
    stays full-width under GSPMD; each group's contribution to the slow
    inter-node exchange passes the symmetric per-block quantizer before
    the f32-master reduction (the EQuARX error model; see
    quantized_comm.quantized_pmean for why the narrow-payload
    ``quantized_allreduce`` form cannot lower in this partial-manual
    region). The per-grad placement is unchanged, so the quantized hop
    inherits the same overlap-behind-backward schedule. The loss scalar
    stays full-width.
    """
    dcn = int(mesh.shape["dcn"])
    for r in tuple(in_raws) + tuple(label_raws):
        if r.ndim == 0 or r.shape[0] % dcn:
            raise ValueError(
                "async_dcn_allreduce: every input/label needs a leading "
                f"batch dim divisible by the dcn degree {dcn}; got shape "
                f"{tuple(r.shape)}"
            )
    auto = frozenset(a for a in mesh.axis_names if a != "dcn")
    if quant is None:
        reduce_grad = lambda g: jax.lax.pmean(g, "dcn")
    else:
        # quantized_pmean, not quantized_allreduce: this region is
        # PARTIAL-manual (GSPMD auto over ici/mp) and this XLA admits
        # only all-reduce collectives in manual subgroups — see the
        # quantized_comm.quantized_pmean docstring for the trade
        from . import quantized_comm as _qc

        q_dtype, q_block = quant
        reduce_grad = lambda g: _qc.quantized_pmean(
            g, "dcn", dtype=q_dtype, block=q_block
        )

    def body(p, k, ins, lbls):
        global _MANUAL_DCN
        if k is not None:
            # decorrelate dropout/noise across dcn groups (the implicit
            # form draws one global mask; parity holds when no RNG is
            # consumed, i.e. the deterministic training step — an
            # RNG-consuming model gets per-group masks: a valid but
            # DIFFERENT sample, documented in README/strategy)
            k = jax.random.fold_in(k, jax.lax.axis_index("dcn"))
        _MANUAL_DCN = True  # routers decline nested shard_map seams
        try:
            (loss, _aux), grads = jax.value_and_grad(
                lambda pt: loss_of(pt, (), k, ins, lbls), has_aux=True
            )(p)
        finally:
            _MANUAL_DCN = False
        # the explicit dcn hop, one collective PER GRAD at the grad's
        # own position in the dataflow — schedulable behind the rest of
        # backward, un-combinable into a tail collective (full-width
        # pmean, or the block-quantized exchange under the policy)
        grads = tuple(
            g if g is None else reduce_grad(g) for g in grads
        )
        return jax.lax.pmean(loss, "dcn"), grads

    from .. import profiler as _prof

    p_specs = jax.tree_util.tree_map(lambda _: P(), tuple(p_raws))
    in_specs_ins = tuple(P("dcn") for _ in in_raws)
    in_specs_lbls = tuple(P("dcn") for _ in label_raws)
    with _prof.device_annotation("TrainStep::async_dcn"):
        if key is None:
            f = comm.shard_map(
                lambda p, ins, lbls: body(p, None, ins, lbls), mesh,
                in_specs=(p_specs, in_specs_ins, in_specs_lbls),
                out_specs=(P(), p_specs),
                auto=auto,
            )
            return f(tuple(p_raws), tuple(in_raws), tuple(label_raws))
        f = comm.shard_map(
            body, mesh,
            in_specs=(p_specs, P(), in_specs_ins, in_specs_lbls),
            out_specs=(P(), p_specs),
            auto=auto,
        )
        loss, grads = f(tuple(p_raws), key, tuple(in_raws),
                        tuple(label_raws))
        return loss, grads
