"""The decode step's attention as one Pallas TPU kernel that stops at
each slot's live length.

The chip stores a float `[B, H, cap, D]` cache with D under 128 with the
capacity in the lanes (ops/pallas/kv_append.py says how and why), so
`swapaxes(cache, 2, 3)`, the `[B, H, D, cap]` view, is a bitcast and a
kernel can fetch lane tiles of `TILE` positions from it. Slot `b`'s one
query row sits at `pos[b]` and sees keys `0 … pos[b]`: the first
`pos[b] // TILE + 1` tiles and no other. XLA's dense form reads the whole
capacity of every slot every step, at bandwidth, whatever is live.

One grid step a slot. The slot's first tile of K and of V comes through a
`BlockSpec` (every slot has one, and the pipeline fetches slot `b + 1`'s
while slot `b` computes); the live tiles after it are copied by the
kernel itself, two buffers each, with a trip count read from the
scalar-prefetched `pos`, so a tile past the live length costs neither a
DMA nor a grid step.

Inside a tile everything stays where the chip stores it: scores are
`q[h, d] * K[h, d, t]` summed over the sublane axis `d` (vector work, no
MXU: one query row a head), each of the 128 lanes keeps a running maximum
and sum of its own across tiles (float32 whatever the cache holds), and
`p[h, t] * V[h, d, t]` is accumulated per lane; the lanes are reduced once
a slot, at the end. Lanes past `pos[b]` in the last live tile weigh
exactly 0, as the dense form's masked keys do.

`decode_append_attention` is the decode step's whole cache work in one
call: it also writes the step's new K and V rows. The tile that holds
`pos[b]` is the last live one the attention fetches, so the rows go into
its copy in fast memory and that copy goes back to the cache, aliased in
to out: one whole-tile write a slot a tensor, where `kv_append` first
reads the same tile again in a launch of its own. There each cache tensor
enters once, in HBM: a slot's first tiles are the kernel's own copies,
started while the slot before it computes, and a slot's write is waited
for two slots later, when its buffer is next filled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _tpu_params
from .kv_append import LANES

#: positions a fetched tile holds: one lane tile of the stored cache. Every
#: slot reads at least one tile, so slots x TILE positions is the floor of
#: a step's read, and the chip keeps 128 positions of 8 head_dim rows
#: contiguous (4 KB), so nothing smaller would move fewer bytes. Alone at
#: the cells' shapes 256 was slower everywhere (PERF.md, PR 36)
TILE = LANES

# below every score a float32 product of finite operands can reach, and
# finite itself: exp(_NEG - _NEG) is 1, never nan
_NEG = -1e30


def _stride(heads):
    """Sublanes between the query's rows and each further row set in one
    turn: whole 8-sublane tiles, so that every store into the turn is
    aligned."""
    return -(-heads // 8) * 8


#: the most heads `decode_append_attention` takes: the query's rows and
#: the two new rows, each from a whole number of 8 sublanes, in one turn
APPEND_HEADS = LANES // 3 // 8 * 8


def _attend_tile(k_ref, v_ref, base, last, q_scr, m_scr, l_scr, acc_scr):
    """Fold the `TILE` positions from `base` on, held by `k_ref` / `v_ref`
    ([H, D, TILE]), into the per-lane running maximum, sum and weighted
    values. A head at a time, whose working set is a few vregs; two heads
    a loop body, so that one's loads stand behind the other's arithmetic."""
    heads = k_ref.shape[0]
    at = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
    dead = at > last

    def head(h):
        k = k_ref[h].astype(jnp.float32)
        s = jnp.sum(q_scr[h] * k, axis=0, keepdims=True)
        s = jnp.where(dead, _NEG, s)
        m_old = m_scr[h]
        m_new = jnp.maximum(m_old, s)
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(dead, 0.0, jnp.exp(s - m_new))
        m_scr[h] = m_new
        l_scr[h] = alpha * l_scr[h] + p
        acc_scr[h] = alpha * acc_scr[h] + p * v_ref[h].astype(jnp.float32)

    pair = 2 if heads % 2 == 0 else 1

    def group(g, carry):
        for i in range(pair):
            head(g * pair + i)
        return carry

    jax.lax.fori_loop(0, heads // pair, group, None)


def _begin(q_ref, scale, turn_scr, q_scr, m_scr, l_scr, acc_scr,
           rows=()):
    """A slot's start. `q_ref` holds its query rows as the program has
    them, [H, D]: a head's row lies along the lanes, and the scores want
    it down the sublanes. One 128 x 128 turn in the kernel (XLA's own
    transpose of so small an array is a launch of its own, dearer than
    the attention of a short slot), then each head's column is scaled
    and spread over the lanes once. `rows` are further [1, H, D] refs
    (the new K and V rows) turned with the query, each from its own
    multiple of 8 sublanes (`_stride`); the turned tile then stays in
    `turn_scr`, where `_insert` finds their columns."""
    heads, depth = q_ref.shape[1:]
    stride = _stride(heads)
    turn_scr[:heads, :depth] = q_ref[0].astype(jnp.float32) * scale
    for i, r in enumerate(rows, 1):
        turn_scr[i * stride:i * stride + heads, :depth] = (
            r[0].astype(jnp.float32))
    q = turn_scr[...].T
    if rows:
        turn_scr[...] = q
    for h in range(heads):
        q_scr[h] = jnp.broadcast_to(q[:depth, h:h + 1], q_scr.shape[1:])
    m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _finish(o_ref, turn_scr, m_scr, l_scr, acc_scr):
    """A slot's end: the lanes' softmaxes, each over its own keys, into
    one a head; the heads' columns are turned back into the [H, D] rows
    the program reads."""
    heads, depth = o_ref.shape[1:]
    for h in range(heads):
        m = m_scr[h]
        w = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))
        total = jnp.sum(l_scr[h] * w, axis=1, keepdims=True)
        turn_scr[:depth, h:h + 1] = (
            jnp.sum(acc_scr[h] * w, axis=1, keepdims=True) / total)
    o_ref[0] = turn_scr[...].T[:heads, :depth].astype(o_ref.dtype)


def _decode_attention_kernel(pos_ref, q_ref, k0_ref, v0_ref, k_hbm, v_hbm,
                             o_ref, turn_scr, q_scr, m_scr, l_scr, acc_scr,
                             k_buf, v_buf, sem, *, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    last = pos_ref[b]
    tiles = last // TILE + 1

    def fetch(t):
        """The copies of live tile `t` of K and of V into buffer t % 2."""
        at = pl.ds(pl.multiple_of(t * TILE, TILE), TILE)
        return [pltpu.make_async_copy(src.at[b, :, :, at], dst.at[t % 2],
                                      sem.at[i, t % 2])
                for i, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    @pl.when(tiles > 1)
    def _():
        for copy in fetch(1):
            copy.start()

    state = (q_scr, m_scr, l_scr, acc_scr)
    _begin(q_ref, scale, turn_scr, *state)
    _attend_tile(k0_ref.at[0], v0_ref.at[0], 0, last, *state)

    def later(t, carry):
        @pl.when(t + 1 < tiles)
        def _():
            for copy in fetch(t + 1):
                copy.start()

        for copy in fetch(t):
            copy.wait()
        _attend_tile(k_buf.at[t % 2], v_buf.at[t % 2], t * TILE, last,
                     *state)
        return carry

    jax.lax.fori_loop(1, tiles, later, None)
    _finish(o_ref, turn_scr, m_scr, l_scr, acc_scr)


# jitted here so that a step program's 24 or 36 layers, which call it on
# the same shapes, trace and lower the kernel once and share one function:
# unjitted, every call site lowered its own copy (a third of a second each)
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention(query, key, value, pos, *, scale=None, interpret=False):
    """softmax(q K^T * scale) V of one query row a slot over the keys
    `0 … pos[b]` of a static-capacity cache: `query` [B, H, 1, D], `key`
    and `value` [B, H, cap, D] (float32 or bfloat16, `cap` a multiple of
    128, D under 128), `pos` [B] int32. The result is [B, H, 1, D] in the
    dtype the dense form gives (`result_type` of the three). Keys past
    `pos[b]` are never read beyond the tile that holds `pos[b]` and never
    weighed. A `pos[b]` outside `[0, cap)` is clamped into it: past the
    capacity that is the dense form's answer too, and a negative one the
    engine never hands over. `interpret=True` runs the Pallas interpreter
    (CPU tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, cap, D = key.shape
    if (cap % TILE or H > LANES or D > LANES
            or query.shape != (B, H, 1, D)
            or value.shape != key.shape or value.dtype != key.dtype):
        raise ValueError(
            f"decode_attention: key {key.shape} {key.dtype} wants a "
            f"capacity that is a multiple of {TILE}, at most {LANES} "
            f"heads of at most {LANES} (the query is turned in one "
            f"{LANES} x {LANES} tile), a value like it and a query "
            f"[B, H, 1, D], got value {value.shape} {value.dtype} and "
            f"query {query.shape}")
    sc = scale if scale is not None else D ** -0.5
    out_dtype = jnp.result_type(query.dtype, key.dtype, value.dtype)
    pos = jnp.clip(jnp.asarray(pos, jnp.int32), 0, cap - 1)
    first = pl.BlockSpec((1, H, D, TILE), lambda b, pos: (b, 0, 0, 0))
    row = pl.BlockSpec((1, H, D), lambda b, pos: (b, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    kt, vt = jnp.swapaxes(key, 2, 3), jnp.swapaxes(value, 2, 3)
    out = pl.pallas_call(
        functools.partial(_decode_attention_kernel, scale=sc),
        out_shape=jax.ShapeDtypeStruct((B, H, D), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row, first, first, whole, whole],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((LANES, LANES), jnp.float32),     # the turns
                pltpu.VMEM((H, D, TILE), jnp.float32),       # q, spread
                pltpu.VMEM((H, 1, TILE), jnp.float32),       # maximum
                pltpu.VMEM((H, 1, TILE), jnp.float32),       # sum
                pltpu.VMEM((H, D, TILE), jnp.float32),       # values
                pltpu.VMEM((2, H, D, TILE), key.dtype),
                pltpu.VMEM((2, H, D, TILE), value.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        # a slot's buffers and semaphores are its own step's: nothing is
        # carried from one slot to the next
        compiler_params=_tpu_params("parallel"),
        interpret=interpret,
        name="decode_attention",
    )(pos, query[:, :, 0, :], kt, vt, kt, vt)
    return out[:, :, None, :]


def _insert(k_tile, v_tile, k_dst, v_dst, turn_scr, lane):
    """The fetched tiles `k_tile` / `v_tile` ([H, D, TILE]) into `k_dst`
    / `v_dst` with the slot's new K and V rows, whose columns `_begin`
    left in `turn_scr`, in lane `lane`, as the cache's dtype has them:
    the value `kv_append` would have written and this kernel would then
    have read back."""
    heads, depth = k_tile.shape[:2]
    stride = _stride(heads)
    turned = turn_scr[:depth, :]
    here = jax.lax.broadcasted_iota(jnp.int32, (depth, TILE), 1) == lane
    for i, (src, dst) in enumerate(((k_tile, k_dst), (v_tile, v_dst)), 1):
        for h in range(heads):
            c = i * stride + h
            row = turned[:, c:c + 1].astype(dst.dtype).astype(jnp.float32)
            dst[h] = jnp.where(here, row,
                               src[h].astype(jnp.float32)).astype(dst.dtype)


#: buffers of a cache tensor's tiles in fast memory, by first index: a
#: slot's first tile (by the slot's parity), its later live tiles (by the
#: tile's), and the tile with the new row in it that goes back to the
#: cache (by the slot's parity: its write is waited for two slots later)
_FIRST, _LATER, _WRITTEN = 0, 2, 4


def _append_attention_kernel(pos_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm,
                             o_ref, k_out, v_out, turn_scr, q_scr, m_scr,
                             l_scr, acc_scr, k_buf, v_buf, sem, *, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    slots = pl.num_programs(0)
    last = pos_ref[b]
    tiles = last // TILE + 1
    bufs = (k_buf, v_buf)

    def copies(kind, s, t):
        """K's and V's copy of slot `s`'s tile `t`: fetched into the
        buffer of `kind` (`_FIRST`, `_LATER`), or written back from
        `_WRITTEN`. The semaphores are laid out as the buffers are."""
        at = pl.ds(pl.multiple_of(t * TILE, TILE), TILE)
        i = kind + (t % 2 if kind == _LATER else s % 2)
        out = []
        for j, (src, dst) in enumerate(((k_hbm, k_out), (v_hbm, v_out))):
            hbm, vmem = src.at[s, :, :, at], bufs[j].at[i]
            if kind == _WRITTEN:
                hbm, vmem = vmem, dst.at[s, :, :, at]
            out.append(pltpu.make_async_copy(hbm, vmem, sem.at[j, i]))
        return out

    def written(s):
        """Slot `s`'s write, to be waited for (the tile is the last
        live one of that slot, whose `pos` is at hand)."""
        return copies(_WRITTEN, s, pos_ref[s] // TILE)

    @pl.when(b == 0)
    def _():
        for copy in copies(_FIRST, b, 0):
            copy.start()

    @pl.when(b + 1 < slots)
    def _():
        for copy in copies(_FIRST, b + 1, 0):
            copy.start()

    @pl.when(tiles > 1)
    def _():
        for copy in copies(_LATER, b, 1):
            copy.start()

    state = (q_scr, m_scr, l_scr, acc_scr)
    _begin(q_ref, scale, turn_scr, *state, rows=(kn_ref, vn_ref))

    def attend(i, t):
        """Fold tile `t`, fetched into buffer `i`; the last live one
        takes the new rows first, into the buffer that goes back."""
        is_last = t == tiles - 1
        out = _WRITTEN + b % 2

        @pl.when(is_last)
        def _():
            @pl.when(b >= 2)
            def _():
                for copy in written(b - 2):
                    copy.wait()

            _insert(k_buf.at[i], v_buf.at[i], k_buf.at[out], v_buf.at[out],
                    turn_scr, last % TILE)
            for copy in copies(_WRITTEN, b, t):
                copy.start()

        at = jnp.where(is_last, out, i)
        _attend_tile(k_buf.at[at], v_buf.at[at], t * TILE, last, *state)

    for copy in copies(_FIRST, b, 0):
        copy.wait()
    attend(_FIRST + b % 2, 0)

    def later(t, carry):
        @pl.when(t + 1 < tiles)
        def _():
            for copy in copies(_LATER, b, t + 1):
                copy.start()

        for copy in copies(_LATER, b, t):
            copy.wait()
        attend(_LATER + t % 2, t)
        return carry

    jax.lax.fori_loop(1, tiles, later, None)
    _finish(o_ref, turn_scr, m_scr, l_scr, acc_scr)

    # the writes still in flight when the grid ends
    @pl.when(b == slots - 1)
    def _():
        @pl.when(b >= 1)
        def _():
            for copy in written(b - 1):
                copy.wait()

        for copy in written(b):
            copy.wait()


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_append_attention(query, key, value, k_new, v_new, pos, *,
                            scale=None, interpret=False):
    """`kv_append(key, k_new, pos)`, `kv_append(value, v_new, pos)` and
    `decode_attention` over the two in one kernel: returns the attention
    [B, H, 1, D] and the caches with `k_new` / `v_new` ([B, H, 1, D])
    cast to the cache dtype and written at `[b, :, pos[b], :]`, bit for
    bit what the three calls give. The cache's tile that holds `pos[b]`
    is the last one the attention fetches anyway, so the rows go into its
    copy in fast memory before it is attended and that copy goes back in
    place (the caches are aliased in to out): one whole-tile write a slot
    a tensor, where `kv_append` reads the tile again. Each cache tensor
    enters once, in HBM; a slot's first tiles are fetched while the slot
    before it computes, so the grid runs in order. `pos` as `kv_append`
    takes it: a negative one counts from the end, then it is clamped into
    `[0, cap)`. At most `APPEND_HEADS` (40) heads: a slot's query and
    two new rows are turned in one 128 x 128 tile. `interpret=True` runs
    the Pallas interpreter (CPU tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, cap, D = key.shape
    row_shape = (B, H, 1, D)
    if (cap % TILE or H > APPEND_HEADS or D > LANES
            or query.shape != row_shape or k_new.shape != row_shape
            or v_new.shape != row_shape
            or value.shape != key.shape or value.dtype != key.dtype):
        raise ValueError(
            f"decode_append_attention: key {key.shape} {key.dtype} wants "
            f"a capacity that is a multiple of {TILE}, at most "
            f"{APPEND_HEADS} heads of at most {LANES} (the query and "
            f"the new rows are turned in one {LANES} x {LANES} tile), a "
            f"value like it and a query and new rows [B, H, 1, D], got "
            f"value {value.shape} {value.dtype}, query {query.shape}, "
            f"new rows {k_new.shape} and {v_new.shape}")
    sc = scale if scale is not None else D ** -0.5
    out_dtype = jnp.result_type(query.dtype, key.dtype, value.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    pos = jnp.clip(jnp.where(pos < 0, pos + cap, pos), 0, cap - 1)
    row = pl.BlockSpec((1, H, D), lambda b, pos: (b, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    kt, vt = jnp.swapaxes(key, 2, 3), jnp.swapaxes(value, 2, 3)
    out, kt, vt = pl.pallas_call(
        functools.partial(_append_attention_kernel, scale=sc),
        out_shape=(jax.ShapeDtypeStruct((B, H, D), out_dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row, row, row, whole, whole],
            out_specs=[row, whole, whole],
            scratch_shapes=[
                pltpu.VMEM((LANES, LANES), jnp.float32),     # the turns
                pltpu.VMEM((H, D, TILE), jnp.float32),       # q, spread
                pltpu.VMEM((H, 1, TILE), jnp.float32),       # maximum
                pltpu.VMEM((H, 1, TILE), jnp.float32),       # sum
                pltpu.VMEM((H, D, TILE), jnp.float32),       # values
                pltpu.VMEM((6, H, D, TILE), key.dtype),      # the tiles
                pltpu.VMEM((6, H, D, TILE), value.dtype),
                pltpu.SemaphoreType.DMA((2, 6)),
            ],
        ),
        # operand 0 is the prefetched `pos`, 1-3 the query and new rows
        input_output_aliases={4: 1, 5: 2},
        # slot b + 1's first tiles and slot b's write are carried from
        # slot b's step into later ones: the grid runs in order
        compiler_params=_tpu_params("arbitrary"),
        interpret=interpret,
        name="decode_append_attention",
    )(pos, query[:, :, 0, :], k_new[:, :, 0, :], v_new[:, :, 0, :], kt, vt)
    return out[:, :, None, :], jnp.swapaxes(kt, 2, 3), jnp.swapaxes(vt, 2, 3)
