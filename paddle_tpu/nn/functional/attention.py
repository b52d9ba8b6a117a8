"""Attention routing policy — flash attention by DEFAULT on the causal
decoder hot path (ISSUE 4 tentpole).

The Pallas flash kernel (ops/pallas/flash_attention.py) never writes
the [S, S] scores to HBM; in the `gpt2-medium.train` cell its three
kernels are 24.9 % of the step at 14.25 / 10.72 % of their rooflines
(PERF.md §5; ledger, PR 31). This module centralizes the routing
decision so `nn.MultiHeadAttention` and
`distributed.ParallelMultiHeadAttention` pick the kernel automatically
whenever it computes the same function as the dense path:

  * causal self/cross attention with NO arbitrary mask (the kernel masks
    by global position; an additive mask would need materialized scores),
  * no attention-probability dropout while training (flash never
    materializes the probabilities),
  * no need_weights / incremental-decode cache,
  * sequence lengths tileable to >= 8 (the kernel requires S % block == 0;
    degenerate tiles are slower than dense),
  * a TPU backend — compiled Pallas is TPU-only; every other backend
    falls back to the dense XLA path (the interpreter is for tests only).

Escape hatch: `PADDLE_FLASH_DEFAULT=0` restores dense routing everywhere
(set it when bisecting a numerics question back to the materialized-score
path). `PADDLE_FLASH_DEFAULT=interpret` forces routing through the Pallas
interpreter off-TPU — CPU CI uses it to exercise the routed code path.

Round 7 (ISSUE 6): multi-device programs route too. The r6 policy
declined ANY `device_count() > 1` process because a pallas_call inside a
GSPMD program has no partition rule — even when the operands were fully
replicated or every model axis had size 1. The router is now mesh-aware:
`shard_factoring` maps the mesh axes that actually partition the
operands onto the attention dims (dp/dcn/ici -> batch, mp -> heads), and
eligible shapes run the kernel through the `shard_map` seam
(ops/pallas/sharded.py) — each device executes the single-chip kernel on
its shard. `PADDLE_FLASH_SHARD=0` is the loud escape hatch back to the
r6 dense fallback for every multi-device program (it also gates the
sharded fused-LN routing in functional.norm).

Round 10 (ISSUE 9): decode-append Sq != Sk causal shapes route too. The
queries are the end-aligned suffix of the key sequence, so the kernel's
`q_offset = Sk - Sq` seam computes the same triangle the dense fallback
masks explicitly (`qpos = arange(Sq) + (Sk - Sq)`).
`PADDLE_FLASH_APPEND=0` restores the r4 dense-only Sq != Sk policy.
Traced (per-slot) positions cannot use a static offset: the serving
KV-cache path uses `cached_attention`/`cache_update` below instead.
"""
from __future__ import annotations

import os

import jax

from ...core import autograd as AG

__all__ = [
    "flash_default_enabled", "flash_shard_enabled", "flash_append_enabled",
    "shard_factoring", "flash_plan", "flash_routable", "flash_core",
    "flash_core_sharded", "flash_core_routed",
    "scaled_dot_product_attention", "cache_update", "cached_attention",
    "cached_append_attention",
]


def flash_default_enabled() -> bool:
    v = os.environ.get("PADDLE_FLASH_DEFAULT", "1").strip().lower()
    return v not in ("0", "false", "off")


def flash_append_enabled() -> bool:
    """May causal decode-append (Sq != Sk, queries end-aligned) shapes
    route through the offset-aware flash kernel? `PADDLE_FLASH_APPEND=0`
    restores the round-4 policy: every Sq != Sk shape takes the dense
    end-aligned fallback (ISSUE 9)."""
    v = os.environ.get("PADDLE_FLASH_APPEND", "1").strip().lower()
    return v not in ("0", "false", "off")


def flash_shard_enabled() -> bool:
    """May multi-device programs route Pallas kernels through the
    shard_map seam? `PADDLE_FLASH_SHARD=0` restores the r6 policy
    (dense fallback whenever the program spans >1 device)."""
    v = os.environ.get("PADDLE_FLASH_SHARD", "1").strip().lower()
    return v not in ("0", "false", "off")


def _routing_mesh():
    """The mesh a mesh-less caller's multi-device program runs on.

    On TPU: the hybrid mesh when fleet/init_hybrid_mesh declared one,
    else the default data-parallel group's mesh (plain DataParallel
    jobs). Off-TPU (interpret-mode CI): ONLY an explicitly declared
    hybrid mesh counts — the default group always spans every virtual
    device of the test harness, and consulting it would veto the plain
    single-device interpret tests that never shard anything."""
    from ...distributed import comm

    mesh = comm.hybrid_mesh()
    if mesh is not None:
        return mesh
    if jax.default_backend() != "tpu":
        return None
    g = comm.get_group(0)
    return g.mesh if g is not None else None


def shard_factoring(mesh, batch, heads):
    """Map the mesh axes that partition a multi-device program onto the
    [B, H, S, D] attention operands: data-parallel axes ('dp', or the
    hierarchical 'dcn' x 'ici' pair) shard the batch, 'mp' shards heads.

    Returns (batch_axes, head_axes) — possibly empty tuples, meaning the
    mesh partitions nothing (all axes size 1: the kernel runs as-is) —
    or None when the operands cannot be covered: a dim not divisible by
    its axes' product, or a size>1 axis this seam cannot map ('sp'
    belongs to ring attention, 'pp' to the pipeline schedule; inside a
    pipeline stage the rebound submesh has no pp axis).
    """
    from ...distributed import comm as _comm

    if mesh is None:
        return None
    batch_axes, head_axes = [], []
    for ax in _comm.partitioning_axes(mesh):
        if ax in _comm.DP_AXES:
            batch_axes.append(ax)
        elif ax == "mp":
            head_axes.append(ax)
        else:
            return None
    bdeg = 1
    for ax in batch_axes:
        bdeg *= int(mesh.shape[ax])
    hdeg = 1
    for ax in head_axes:
        hdeg *= int(mesh.shape[ax])
    if bdeg > 1 and (batch is None or int(batch) % bdeg):
        return None
    if hdeg > 1 and (heads is None or int(heads) % hdeg):
        return None
    return tuple(batch_axes), tuple(head_axes)


def _shard_plan(mesh, batch, heads):
    """The multi-device routing decision, shared by `flash_routable` and
    the kernel dispatchers so policy and execution cannot drift.

    Returns one of:
      None         — the program is single-device (or the mesh partitions
                     nothing): run the plain kernel;
      (mesh, fac)  — multi-device: run through the shard_map seam with
                     `fac = (batch_axes, head_axes)`;
      False        — decline (dense fallback): PADDLE_FLASH_SHARD=0, a
                     mesh this seam cannot cover, a mesh-less
                     multi-device TPU program (no axes to map), or a
                     trace inside the async-dcn manual region (a nested
                     shard_map over the already-manual 'dcn' axis would
                     be ill-formed — the dense forms compose there).
    """
    from ...distributed import overlap as _ov

    if _ov.in_manual_dcn():
        return False
    if mesh is None:
        if jax.default_backend() == "tpu" and len(jax.devices()) == 1:
            return None
        mesh = _routing_mesh()
        if mesh is None:
            # off-TPU with no declared hybrid mesh: a plain interpret
            # test, nothing is sharded — the single-device kernel is
            # exact. On TPU this is a mesh-less multi-device program:
            # decline below via shard_factoring(None).
            if jax.default_backend() != "tpu":
                return None
    if mesh is not None and mesh.size <= 1:
        return None
    if not flash_shard_enabled():
        return False
    fac = shard_factoring(mesh, batch, heads)
    if fac is None:
        return False
    if not (fac[0] or fac[1]):
        return None  # every mapped axis has size 1: plain kernel
    return mesh, fac


def _interpret_forced() -> bool:
    return os.environ.get(
        "PADDLE_FLASH_DEFAULT", ""
    ).strip().lower() == "interpret"


def _flash_block(s: int) -> int:
    """Largest power-of-two tile <= 512 dividing s (kernel contract:
    S % block == 0)."""
    b = 512
    while b > 1 and s % b:
        b //= 2
    return b


def flash_plan(seq_q, seq_k, *, causal, has_mask=False,
               dropout_active=False, need_weights=False,
               has_cache=False, mesh=None, batch=None, heads=None):
    """The full routing decision, made ONCE: None = dense fallback,
    `("plain",)` = single-device kernel, `("sharded", mesh, fac)` = the
    shard_map seam. Callers thread the plan into `flash_core_routed` so
    the route decision and the dispatch cannot drift (env vars and the
    global mesh are read a single time).

    `mesh`/`batch`/`heads` feed the multi-device decision: a program
    spanning several devices routes iff the mesh axes that partition the
    operands factor onto (batch, heads) — see `shard_factoring` — and
    `PADDLE_FLASH_SHARD` is not 0. Callers that know their mesh (the
    tensor-parallel layers) pass it; mesh-less callers fall back to the
    hybrid/default-group mesh on TPU.
    """
    if not flash_default_enabled():
        return None
    if not causal or has_mask or dropout_active or need_weights \
            or has_cache:
        return None
    # Sq != Sk is the decode-append shape: queries are the END-ALIGNED
    # suffix of the key sequence (qpos = arange(Sq) + (Sk - Sq), the same
    # alignment as the dense fallback). Since round 10 it routes through
    # the kernel's q_offset seam (PADDLE_FLASH_APPEND=0 hatch restores
    # the r4 dense-only policy); Sq > Sk has no causal interpretation
    # here and a too-small Sq tile (single-token decode) falls through
    # to dense below via the block check — a 1-row matvec beats a
    # degenerate Pallas tile anyway.
    if int(seq_q) != int(seq_k):
        if int(seq_q) > int(seq_k) or not flash_append_enabled():
            return None
    if jax.default_backend() != "tpu" and not _interpret_forced():
        return None
    if _flash_block(int(seq_q)) < 8 or _flash_block(int(seq_k)) < 8:
        return None
    # multi-device: route on the axes that ACTUALLY partition the
    # operands (r6 declined everything here) — the kernel runs per shard
    # through the shard_map seam; `False` is the seam's decline
    plan = _shard_plan(mesh, batch, heads)
    if plan is False:
        return None
    return ("plain",) if plan is None else ("sharded",) + plan


def flash_routable(seq_q, seq_k, *, causal, has_mask=False,
                   dropout_active=False, need_weights=False,
                   has_cache=False, mesh=None, batch=None,
                   heads=None) -> bool:
    """Would the default router send this attention to the flash kernel?
    (The bool view of `flash_plan`.)"""
    return flash_plan(
        seq_q, seq_k, causal=causal, has_mask=has_mask,
        dropout_active=dropout_active, need_weights=need_weights,
        has_cache=has_cache, mesh=mesh, batch=batch, heads=heads,
    ) is not None


def flash_core(q, k, v, *, causal=True, scale=None, q_offset=0):
    """Run the Pallas flash kernel on [B, H, S, D] Tensors (tape-recorded;
    block sizes derived from the sequence lengths). `q_offset` is the
    static global position of the first query row — `Sk - Sq` for the
    end-aligned decode-append shape."""
    from ...ops.pallas import flash_attention

    bq = _flash_block(int(q.shape[2]))
    bk = _flash_block(int(k.shape[2]))
    interpret = jax.default_backend() != "tpu"
    from ... import profiler as _prof

    with _prof.device_annotation("attention::flash"):
        return AG.apply(
            lambda a, b, c: flash_attention(
                a, b, c, causal, bq, bk, scale, interpret, q_offset, 0
            ),
            (q, k, v), name="flash_attention",
        )


def flash_core_sharded(q, k, v, *, mesh, batch_axes, head_axes,
                       causal=True, scale=None, q_offset=0):
    """Run the flash kernel through the shard_map seam
    (ops/pallas/sharded.py) on [B, H, S, D] Tensors: B shards over
    `batch_axes`, H over `head_axes`, each device executes the
    single-chip kernel on its shard (tape-recorded)."""
    from ...ops.pallas.sharded import sharded_flash_attention

    bq = _flash_block(int(q.shape[2]))
    bk = _flash_block(int(k.shape[2]))
    interpret = jax.default_backend() != "tpu"
    from ... import profiler as _prof

    with _prof.device_annotation("attention::sharded_flash"):
        return AG.apply(
            lambda a, b, c: sharded_flash_attention(
                a, b, c, mesh, batch_axes, head_axes, causal, bq, bk,
                scale, interpret, q_offset, 0
            ),
            (q, k, v), name="sharded_flash_attention",
        )


def flash_core_routed(q, k, v, *, mesh=None, causal=True, scale=None,
                      plan=None, q_offset=0):
    """Dispatch the flash kernel per the shard plan: through the
    shard_map seam when the mesh partitions the [B, H, S, D] operands,
    the plain single-device kernel otherwise. Callers that already hold
    a `flash_plan` result pass it so the decision is not re-derived;
    otherwise it is computed here once — and a seam DECLINE raises
    loudly (the caller must fall back to its dense form: a bare
    pallas_call inside a multi-device GSPMD program has no partition
    rule, and letting it through would surface as an opaque XLA
    partitioning error instead)."""
    if plan is None:
        p = _shard_plan(mesh, int(q.shape[0]), int(q.shape[1]))
        if p is False:
            raise RuntimeError(
                "flash_core_routed: the shard_map seam declined this "
                "multi-device program (PADDLE_FLASH_SHARD=0, an "
                "uncoverable mesh, or the async-dcn manual region) — "
                "route through the dense attention form instead"
            )
        plan = ("plain",) if p is None else ("sharded",) + p
    if plan[0] == "sharded":
        _, m, (batch_axes, head_axes) = plan
        return flash_core_sharded(
            q, k, v, mesh=m, batch_axes=batch_axes, head_axes=head_axes,
            causal=causal, scale=scale, q_offset=q_offset,
        )
    return flash_core(q, k, v, causal=causal, scale=scale,
                      q_offset=q_offset)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """Routed softmax attention over [B, H, S, D] Tensors.

    The flash kernel handles the causal/mask-free/dropout-free case (on
    TPU); everything else runs the dense XLA form with materialized
    scores. Dense+causal applies the triangular mask explicitly, so the
    two routes compute the same function.
    """
    import jax.numpy as jnp

    dropout_active = bool(dropout_p) and training
    B, H = int(query.shape[0]), int(query.shape[1])
    plan = flash_plan(query.shape[2], key.shape[2], causal=is_causal,
                      has_mask=attn_mask is not None,
                      dropout_active=dropout_active, batch=B, heads=H)
    if plan is not None:
        # multi-device programs run the kernel per shard through the
        # shard_map seam (the plan carries the vetted factoring); a
        # decode-append shape (Sq < Sk) rides the kernel's q_offset so
        # its causal mask compares the SAME end-aligned positions as the
        # dense fallback below
        return flash_core_routed(
            query, key, value, causal=is_causal, scale=scale, plan=plan,
            q_offset=int(key.shape[2]) - int(query.shape[2]),
        )

    sc = scale if scale is not None else int(query.shape[-1]) ** -0.5
    Sq, Sk = int(query.shape[2]), int(key.shape[2])

    def score_fn(qr, kr, *m):
        s = jnp.einsum("bhqd,bhkd->bhqk", qr, kr) * sc
        if m:
            s = s + m[0]
        if is_causal:
            qpos = jnp.arange(Sq) + (Sk - Sq)  # aligned last positions
            kpos = jnp.arange(Sk)
            s = jnp.where(kpos[None, :] > qpos[:, None], -1e9, s)
        return jax.nn.softmax(s, axis=-1)

    from ... import profiler as _prof

    args = (query, key) + ((attn_mask,) if attn_mask is not None else ())
    with _prof.device_annotation("attention::dense"):
        weights = AG.apply(score_fn, args, name="attention_scores")
        if dropout_active:
            from .common import dropout as _dropout

            weights = _dropout(weights, dropout_p, training=True)
        return AG.apply(
            lambda w, vr: jnp.einsum("bhqk,bhkd->bhqd", w, vr),
            (weights, value), name="attention_context",
        )


# ---------------------------------------------------------------------------
# static-capacity KV cache (ISSUE 9 serving seam)
# ---------------------------------------------------------------------------


def _lane_cache_route(c, u):
    """Does a decode step's work on this cache take the Pallas kernels on
    the cache as the chip stores it: the in-place append `kv_append`
    (ops/pallas/kv_append.py) for a write alone, `decode_attention`
    (ops/pallas/decode_attention.py) for a read alone, and
    `decode_append_attention` (the same module) where the step's two
    writes and its read come together (`cached_append_attention`)? None =
    XLA's own form (the slot-by-slot scatter, the dense attention over
    the capacity), else the kernels' `interpret` flag. Every one of them
    asks this one question, because they rest on one fact, and it is
    decided from what the call is handed and nothing else: one row a slot
    (`u`, the new K or V row or the query, is [B, H, 1, D]: the decode
    step) and a float32 or bfloat16 [B, H, cap, D] array whose capacity
    is whole 128-lane tiles and whose head_dim is under a tile's 128
    lanes — only then does the chip store capacity in the lanes, so that
    the kernels' `[B, H, D, cap]` view is a bitcast; at D >= 128 the view
    would be a copy of the whole cache tensor. A non-trivial mesh keeps
    XLA's form (GSPMD partitions it; a bare pallas_call it would not).
    Off the TPU the kernels run only in the interpreter, for tests, by
    `PADDLE_FLASH_DEFAULT=interpret`."""
    import jax.numpy as jnp

    from ...ops.pallas.kv_append import LANES

    if c.ndim != 4 or c.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    B, H, cap, D = c.shape
    if u.shape != (B, H, 1, D) or cap % LANES or D >= LANES:
        return None
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not _interpret_forced():
        return None
    mesh = _routing_mesh()
    if mesh is not None and mesh.size > 1:
        return None
    return not on_tpu


def cache_update(cache, new, pos):
    """Write the [B, H, Sq, D] new K or V rows into the static-capacity
    [B, H, cap, D] `cache` Tensor at per-slot write positions ``pos``
    ([B] int32 Tensor): one vmapped dynamic_update_slice — no concat, no
    shape change, so the compiled decode program is traced ONCE and the
    cache buffer can be donated. Inference-only (no VJP).

    The decode step's write (one row a slot into a plain float cache, on
    the chip) is the same write as one in-place Pallas kernel instead
    (`_lane_cache_route`, ops/pallas/kv_append.py): XLA:TPU expands the
    vmapped slice into a serial loop of B iterations a cache tensor. A
    decode step that writes K and V and then attends goes through
    `cached_append_attention`, which folds the write into the read.
    `observability.metrics.kv_append_routes()` counts every way.

    A block-quantized cache (``quantized_comm.QuantKV`` — int8/fp8
    payload at the full cache shape + per-row-block f32 scales, ISSUE
    10) quantizes the new rows along the head dim and writes payload and
    scales with the same per-slot slice — the HBM-resident buffer the
    decode streams every step stays narrow.

    A PAGED cache (``serving.paged_kv.PagedKV`` — fixed-size block pool
    + per-slot block table, ISSUE 13) routes the same append through
    the table as one scatter (``paged_write``): position ``p`` lands in
    physical block ``table[b, p // bs]``. Same constant shapes, same
    single trace, same donatable buffers — only the storage layout
    changes, so DecodeStep/PrefillStep and the engine splice are
    untouched. The quantized form composes (a QuantKV pool inside the
    PagedKV carries payload and scales in the same block layout)."""
    import jax.numpy as jnp

    from ...distributed import quantized_comm as qc
    from ...observability.metrics import (
        record_kv_append_route as _count_route)
    from ...serving import paged_kv as pk

    if isinstance(cache, pk.PagedKV):
        if isinstance(cache.kv, qc.QuantKV):
            def fpq(kq, ks, tab, u, p):
                _count_route("scatter")
                out = pk.paged_write(qc.QuantKV(kq, ks), tab, u,
                                     jnp.asarray(p, jnp.int32))
                return out.q, out.scale

            oq, osc = AG.apply_nondiff(
                fpq, (cache.kv.q, cache.kv.scale, cache.table, new, pos)
            )
            return pk.PagedKV(qc.QuantKV(oq, osc), cache.table)

        def fp(kv, tab, u, p):
            _count_route("scatter")
            return pk.paged_write(kv, tab, u, jnp.asarray(p, jnp.int32))

        out = AG.apply_nondiff(fp, (cache.kv, cache.table, new, pos))
        return pk.PagedKV(out, cache.table)

    def scatter(c, u, p):
        return jax.vmap(
            lambda cb, ub, pb: jax.lax.dynamic_update_slice_in_dim(
                cb, ub.astype(cb.dtype), pb, axis=1
            )
        )(c, u, jnp.asarray(p, jnp.int32))

    def write(c, u, p):
        interpret = _lane_cache_route(c, u)
        if interpret is None:
            _count_route("scatter")
            return scatter(c, u, p)
        from ...ops.pallas.kv_append import kv_append

        _count_route("kernel")
        return kv_append(c, u, p, interpret)

    if isinstance(cache, qc.QuantKV):
        bs = int(cache.q.shape[-1]) // int(cache.scale.shape[-1])
        qdtype = "int8" if cache.q.dtype == jnp.int8 else "fp8"

        def fq(cq, cs, u, p):
            _count_route("scatter")
            uq, us = qc.quantize_lastaxis(u, dtype=qdtype, block=bs)
            return scatter(cq, uq, p), scatter(cs, us, p)

        out = AG.apply_nondiff(fq, (cache.q, cache.scale, new, pos))
        return qc.QuantKV(out[0], out[1])

    return AG.apply_nondiff(write, (cache, new, pos))


def cached_attention(query, key, value, pos, *, scale=None):
    """Decode attention over a static-capacity cache: [B, H, Sq, D]
    queries whose first token sits at per-slot position ``pos`` ([B]
    int32 Tensor) against [B, H, cap, D] cache K/V. The causal mask
    compares TRACED per-slot positions (qpos = pos[b] + i vs kpos = j),
    which also masks every not-yet-written cache slot (kpos > qpos by
    construction — the engine only writes at monotonically growing pos).

    The decode step (one query row a slot over a plain float cache, on
    the chip) is one Pallas kernel a layer that reads only what the slot
    holds (`_lane_cache_route`, ops/pallas/decode_attention.py): lane
    tiles of 128 positions up to the one that holds ``pos[b]``, on the
    cache as the chip stores it. Every other call keeps the dense form
    over the whole capacity under the position mask: prefill, chunked
    prefill and speculative steps (Sq > 1; a TRACED offset cannot feed
    the flash kernel's static q_offset seam), a quantized or paged cache,
    a head_dim of 128 or more, a sharded cache, the CPU.
    `observability.metrics.cached_attention_routes()` counts both ways.
    Grouped queries (query heads a multiple of the cache's K/V heads:
    query head j reads K/V head j // (H / G)) always take XLA's form, a
    tile of keys at a time up to the furthest query, with float32
    scores. Static end-aligned Sq != Sk shapes (prefill-with-history)
    route through the flash kernel via `flash_plan` instead.
    Inference-only (no VJP).

    A PAGED cache (``PagedKV``, ISSUE 13) gathers the slot's view
    [B, H, nmax*bs, D] from the block pool through the table first (one
    gather; a quantized pool gathers narrow payload + scales and
    dequantizes the view) — unwritten or trash-mapped rows carry
    garbage, but they all sit at kpos > qpos so the SAME position mask
    that hides not-yet-written contiguous rows hides them."""
    import jax.numpy as jnp

    from ...distributed import quantized_comm as qc
    from ...observability.metrics import (
        record_cached_attention_route as _count_route)
    from ...serving import paged_kv as pk

    sc = scale if scale is not None else int(query.shape[-1]) ** -0.5
    paged = isinstance(key, pk.PagedKV)
    quantized = isinstance(key.kv if paged else key, qc.QuantKV)
    Sq = int(query.shape[2])
    if paged:
        pool = key.kv.q if quantized else key.kv
        Sk = int(key.table.shape[1]) * int(pool.shape[2])
    else:
        Sk = int((key.q if quantized else key).shape[2])

    def core(qr, kr, vr, pr):
        _count_route("dense")
        if qr.shape[1] != kr.shape[1]:
            return grouped(qr, kr, vr, pr)
        s = jnp.einsum("bhqd,bhkd->bhqk", qr, kr) * sc
        qpos = pr[:, None].astype(jnp.int32) + jnp.arange(Sq)[None, :]
        kpos = jnp.arange(Sk)
        masked = kpos[None, None, None, :] > qpos[:, None, :, None]
        s = jnp.where(masked, -1e9, s)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w, vr)

    def grouped(qr, kr, vr, pr):
        # query head j reads K/V head j // (heads / kv heads); scores, the
        # running max and sum, and the product in float32, a tile of
        # KEY_BLOCK keys at a time (an online softmax), visiting only the
        # tiles at or below the furthest query: a softmax over the whole
        # capacity at once lowers to a reduce-window as wide as the
        # capacity, quadratic in it on the chip
        from .latent import _NEG, KEY_BLOCK

        B, H = int(qr.shape[0]), int(qr.shape[1])
        G, D = int(kr.shape[1]), int(qr.shape[-1])
        bk = KEY_BLOCK if (Sk > KEY_BLOCK and Sk % KEY_BLOCK == 0) else Sk
        qg = qr.reshape(B, G, H // G, Sq, D)
        qpos = pr[:, None].astype(jnp.int32) + jnp.arange(Sq)[None, :]

        def tile(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kr, j * bk, bk, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vr, j * bk, bk, axis=2)
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kb,
                           preferred_element_type=jnp.float32) * sc
            kpos = j * bk + jnp.arange(bk)
            s = jnp.where(kpos[None, None, None, None, :]
                          > qpos[:, None, None, :, None], _NEG, s)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(-1), acc * alpha[..., None] + pv

        init = (jnp.full((B, G, H // G, Sq), _NEG, jnp.float32),
                jnp.zeros((B, G, H // G, Sq), jnp.float32),
                jnp.zeros((B, G, H // G, Sq, D), jnp.float32))
        if Sk == bk:
            _, l, acc = tile(0, init)
        else:
            n = jnp.minimum((jnp.max(pr).astype(jnp.int32) + Sq + bk - 1)
                            // bk, Sk // bk)
            _, l, acc = jax.lax.fori_loop(0, n, tile, init)
        o = acc / l[..., None]
        return o.reshape(B, H, Sq, D).astype(qr.dtype)

    from ... import profiler as _prof

    with _prof.device_annotation("attention::cached"):
        if paged:
            # block-table gather first: the pool stays the HBM-resident
            # form, the [B, H, nmax*bs, D] view is a transient of this
            # step only (quantized pools gather narrow then dequantize)
            if quantized:
                def fpq(qr, kq, ks, kt, vq, vs, vt, pr):
                    kr = pk.paged_gather(qc.QuantKV(kq, ks), kt,
                                         qr.dtype)
                    vr = pk.paged_gather(qc.QuantKV(vq, vs), vt,
                                         qr.dtype)
                    return core(qr, kr, vr, pr)

                return AG.apply_nondiff(fpq, (
                    query, key.kv.q, key.kv.scale, key.table,
                    value.kv.q, value.kv.scale, value.table, pos))

            def fpg(qr, kk, kt, vk, vt, pr):
                return core(qr, pk.paged_gather(kk, kt),
                            pk.paged_gather(vk, vt), pr)

            return AG.apply_nondiff(
                fpg, (query, key.kv, key.table, value.kv, value.table,
                      pos))
        if quantized:
            # dequantize-on-read: the score math runs at the query
            # dtype, but the buffer the step streams from HBM (the
            # decode bottleneck) is the narrow payload + scales
            def fq(qr, kq, ks, vq, vs, pr):
                kr = qc.dequantize_lastaxis(kq, ks, qr.dtype)
                vr = qc.dequantize_lastaxis(vq, vs, qr.dtype)
                return core(qr, kr, vr, pr)

            return AG.apply_nondiff(
                fq, (query, key.q, key.scale, value.q, value.scale, pos)
            )

        def attend(qr, kr, vr, pr):
            from ...ops.pallas.decode_attention import (LANES,
                                                        decode_attention)

            interpret = _lane_cache_route(kr, qr)
            # the read's own two facts: V laid out as K is, and the heads
            # of a slot's query fit the one lane tile the kernel turns
            if (interpret is None or kr.shape[1] > LANES
                    or (vr.shape, vr.dtype) != (kr.shape, kr.dtype)):
                return core(qr, kr, vr, pr)
            _count_route("kernel")
            return decode_attention(qr, kr, vr, pr, scale=sc,
                                    interpret=interpret)

        return AG.apply_nondiff(attend, (query, key, value, pos))


def cached_append_attention(query, k_cache, v_cache, k_new, v_new, pos, *,
                            scale=None):
    """A decode layer's cache work in one call: the [B, H, Sq, D] new K
    and V rows written into the caches at per-slot ``pos`` (as
    `cache_update` writes them), then ``query`` attended over the
    written caches (as `cached_attention` reads them). Returns
    ``(out, k_cache', v_cache')``.

    The decode step over a plain float cache on the chip (one row a
    slot: `_lane_cache_route`, with the read's own checks that
    `cached_attention` makes, and at most `APPEND_HEADS` (40) heads, so
    that the query and the two new rows turn in one tile) is one Pallas
    kernel a layer, `decode_append_attention`
    (ops/pallas/decode_attention.py): the tile that holds ``pos[b]`` is the last one the read fetches, so
    the rows go into it there and it is written back once, where
    `kv_append` would read and write it again in a launch of its own.
    The result is the two kernels' bit for bit. Every other call is
    exactly `cache_update` for K, for V, then `cached_attention`:
    prefill and speculative steps (Sq > 1), a quantized or paged cache,
    a head_dim of 128 or more, more heads than the turn holds, a sharded
    cache, the CPU. `observability.metrics.kv_append_routes()["fused"]`
    counts the rows written inside the kernel (two a call), and
    `cached_attention_routes()["kernel"]` its read. Inference-only (no
    VJP)."""
    from ...core.tensor import Tensor
    from ...observability.metrics import (record_cached_attention_route,
                                          record_kv_append_route)
    from ...ops.pallas.decode_attention import (APPEND_HEADS,
                                                decode_append_attention)

    sc = scale if scale is not None else int(query.shape[-1]) ** -0.5
    interpret = None
    if isinstance(k_cache, Tensor) and isinstance(v_cache, Tensor):
        kc, vc = k_cache._data, v_cache._data
        interpret = _lane_cache_route(kc, k_new._data)
        if (interpret is not None
                and (_lane_cache_route(kc, v_new._data) is None
                     or _lane_cache_route(kc, query._data) is None
                     or kc.shape[1] > APPEND_HEADS
                     or (vc.shape, vc.dtype) != (kc.shape, kc.dtype))):
            interpret = None
    if interpret is None:
        k = cache_update(k_cache, k_new, pos)
        v = cache_update(v_cache, v_new, pos)
        return cached_attention(query, k, v, pos, scale=scale), k, v

    def fused(qr, kr, vr, kn, vn, pr):
        record_kv_append_route("fused")
        record_kv_append_route("fused")
        record_cached_attention_route("kernel")
        return decode_append_attention(qr, kr, vr, kn, vn, pr, scale=sc,
                                       interpret=interpret)

    from ... import profiler as _prof

    with _prof.device_annotation("attention::cached"):
        out, k, v = AG.apply_nondiff(
            fused, (query, k_cache, v_cache, k_new, v_new, pos))
    return out, k, v
