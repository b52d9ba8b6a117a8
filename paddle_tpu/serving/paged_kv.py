"""Paged KV cache — fixed-size blocks + per-slot block tables (ISSUE 13
tentpole a).

The round-10 serving cache is a contiguous ``[B, H, cap, Dh]`` buffer
per layer: every slot reserves WORST-CASE HBM for its whole lifetime,
so capacity — not actual context length — prices the pool. This module
replaces the storage layout, not the seam: a paged cache is a pool of
``[P, H, bs, Dh]`` fixed-size blocks plus a ``[B, nmax]`` int32 block
table mapping each slot's logical block ``j`` (positions
``j*bs .. (j+1)*bs-1``) to a physical pool block. A slot consumes
blocks proportional to the tokens it will actually write
(``prompt + max_new_tokens``), appending is defrag-free (any free block
serves any slot, no compaction ever moves a row), and freeing a
finished request returns its blocks to the pool immediately.

Contract with the rest of the serving tier:

- :data:`PagedKV` is a namedtuple pytree, so ``jit.DecodeStep``
  donation / out-sharding pinning and the engine's compiled
  ``CacheInsert`` splice work leaf-wise exactly like the contiguous
  ``Cache`` buffers (the ISSUE 13 "unchanged mechanics" requirement);
- ``kv`` is either a raw payload array or the int8/fp8
  ``quantized_comm.QuantKV`` pair — the round-11 quantized form
  composes by carrying the same block layout in payload AND scales;
- every function here is a pure traced-safe raw-array op (no host
  reads, no python loops over traced values): the per-token write is
  ONE scatter through the table, the read is ONE gather — the
  tpulint ``*Step`` rules stay quiet over the decode path.

Physical block 0 is the TRASH block by convention in engine pools: a
retired slot's table rows are redirected there, so its frozen-position
keep-alive writes (the DecodeStep done-slot idiom) can never corrupt a
block that has been reallocated to a new request. Identity-mapped
caches built by ``gen_cache`` (the whole-batch ``generate()`` shape)
also reserve block 0 so the convention holds everywhere.

Env knob (documented in README): ``PADDLE_SERVE_BLOCK_SIZE`` — KV
block size in tokens; ``0`` (default) keeps the contiguous cache.
"""
from __future__ import annotations

import os
from collections import namedtuple
from typing import List, Optional

__all__ = [
    "PagedKV", "block_size_default", "is_paged", "num_blocks",
    "blocks_for", "paged_zero", "paged_write", "paged_gather",
    "paged_splice", "paged_splice_tail", "paged_fetch", "paged_adopt",
    "retire_tables", "pool_bytes", "worst_case_bytes",
    "BlockPool",
]

_BLOCK_ENV = "PADDLE_SERVE_BLOCK_SIZE"

#: paged K or V cache: ``kv`` holds the block pool — a raw
#: [P, H, bs, Dh] payload array, or a QuantKV(payload, scale) pair with
#: the per-row-block scales at [P, H, bs, Dh/qb] — and ``table`` the
#: [B, nmax] int32 slot -> physical-block map. A namedtuple, so the
#: whole thing is a pytree: DecodeStep donates/pins it leaf-wise and
#: the engine splice tree_maps over payload/scale pairs unchanged.
PagedKV = namedtuple("PagedKV", ["kv", "table"])


def block_size_default() -> int:
    """``PADDLE_SERVE_BLOCK_SIZE`` (tokens per KV block); 0 = contiguous
    cache (the round-10 layout stays the default)."""
    try:
        return max(int(os.environ.get(_BLOCK_ENV, "0")), 0)
    except ValueError:
        return 0


def refuse_latent(cache_tree, what: str) -> None:
    """Blocks, block tables, shared prefixes and migration bundles are
    all made of per-head K and V `[.., H, rows, Dh]`; a cache made of
    rows with no head axis (`nn.functional.dsa.ROW_CACHES`: a latent
    cache's one `[c | k_rope]` row a token, an indexed cache's K rows, V
    rows and indexer-key rows side by side) would be mis-spliced by them,
    so `what` refuses it by name."""
    from ..nn.functional.dsa import is_row_cache

    kind = is_row_cache(cache_tree)
    if kind is not None:
        raise TypeError(
            f"{what} handles per-head K and V only and refuses {kind}; "
            "serve it from the contiguous pool (block_size=0, no prefix "
            "cache, no migration)")


def is_paged(cache) -> bool:
    return isinstance(cache, PagedKV)


def num_blocks(capacity: int, block: int) -> int:
    """Logical blocks a slot of ``capacity`` tokens spans (table width)."""
    return -(-int(capacity) // int(block))


def blocks_for(tokens: int, block: int) -> int:
    """Physical blocks a request writing ``tokens`` rows consumes."""
    return -(-max(int(tokens), 1) // int(block))


def _payload(kv):
    """The payload array of a pool (QuantKV-aware)."""
    return kv.q if hasattr(kv, "q") else kv


def paged_zero(batch, heads, capacity, head_dim, *, block,
               pool_blocks=None, dtype=None, quant=None):
    """Fresh paged (k-or-v) cache raw arrays.

    Returns ``PagedKV(kv, table)``. With ``pool_blocks=None`` the table
    is IDENTITY-mapped (slot ``b``'s logical block ``j`` owns physical
    block ``1 + b*nmax + j``; pool = ``B*nmax + 1`` blocks incl. trash)
    — full capacity per slot, the whole-batch ``generate()`` shape.
    With an explicit ``pool_blocks`` the table starts ALL-TRASH (every
    entry 0) and the caller (the engine's :class:`BlockPool`) assigns
    blocks per request — that is where HBM starts scaling with actual
    length instead of capacity. ``quant`` is an ISSUE-10 policy name
    ("int8"/"fp8") for the block-scaled form."""
    import jax.numpy as jnp

    B = int(batch)
    nmax = num_blocks(capacity, block)
    if pool_blocks is None:
        P = B * nmax + 1
        table = (jnp.arange(B * nmax, dtype=jnp.int32).reshape(B, nmax)
                 + 1)
    else:
        P = int(pool_blocks)
        if P < 2:
            raise ValueError(
                f"pool_blocks={P}: a paged pool needs the trash block "
                f"(0) plus at least one allocatable block")
        table = jnp.zeros((B, nmax), jnp.int32)
    shape = (P, int(heads), int(block), int(head_dim))
    if quant is not None:
        from ..distributed import quantized_comm as qc

        p, s = qc.kv_zero(shape, quant)
        return PagedKV(qc.QuantKV(p, s), table)
    return PagedKV(jnp.zeros(shape, dtype), table)


def _scatter_rows(pool, rows, phys, off):
    """Write [N, H, *rest] rows into ``pool`` [P, H, bs, *rest] at
    (physical block, in-block offset) index pairs — one XLA scatter.
    Colliding destinations only arise on the trash block (retired or
    padded writes), where any winner is fine."""
    return pool.at[phys, :, off, :].set(rows.astype(pool.dtype))


def paged_write(kv, table, new, pos):
    """Append [B, H, Sq, D] ``new`` K-or-V rows at per-slot positions
    ``pos`` ([B] int32) through the block table: position ``p`` lands in
    physical block ``table[b, p // bs]`` at offset ``p % bs``. Pure
    gather/scatter — no host loop over blocks (the tpulint fixture
    pair's quiet side). The caller guarantees ``pos + Sq`` stays within
    the slot's tabled capacity (the engine reserves blocks for
    ``prompt + max_new [+ spec_k]`` up front, so append NEVER allocates
    — that is the defrag-free contract)."""
    import jax.numpy as jnp

    B, H, Sq, _ = new.shape
    bs = int(_payload(kv).shape[2])
    idx = pos[:, None].astype(jnp.int32) + jnp.arange(Sq,
                                                     dtype=jnp.int32)
    phys = jnp.take_along_axis(table, idx // bs, axis=1).reshape(-1)
    off = (idx % bs).reshape(-1)

    def rows_of(u):
        return u.transpose(0, 2, 1, 3).reshape(B * Sq, H, u.shape[-1])

    if hasattr(kv, "q"):  # QuantKV pool: quantize rows, write both
        from ..distributed import quantized_comm as qc

        qb = int(kv.q.shape[-1]) // int(kv.scale.shape[-1])
        qdtype = "int8" if str(kv.q.dtype) == "int8" else "fp8"
        uq, us = qc.quantize_lastaxis(new, dtype=qdtype, block=qb)
        return type(kv)(
            _scatter_rows(kv.q, rows_of(uq), phys, off),
            _scatter_rows(kv.scale, rows_of(us), phys, off),
        )
    return _scatter_rows(kv, rows_of(new), phys, off)


def paged_gather(kv, table, out_dtype=None):
    """Materialize the per-slot K-or-V view [B, H, nmax*bs, D] from the
    pool through the table (ONE gather; a quantized pool gathers the
    narrow payload + scales first and dequantizes the gathered view, so
    the HBM-resident pool stays narrow). Rows in unallocated /
    trash-mapped blocks are garbage — the caller's position mask
    (``cached_attention``: kpos > qpos) blinds every position a slot
    has not written."""

    def gather(pool):
        g = pool[table]  # [B, nmax, H, bs, *rest]
        B, nmax, H, bs = g.shape[:4]
        return g.transpose(0, 2, 1, 3, 4).reshape(
            B, H, nmax * bs, g.shape[-1])

    if hasattr(kv, "q"):
        from ..distributed import quantized_comm as qc

        return qc.dequantize_lastaxis(
            gather(kv.q), gather(kv.scale),
            out_dtype if out_dtype is not None else "float32")
    out = gather(kv)
    return out if out_dtype is None else out.astype(out_dtype)


def paged_splice(paged, slot_kv, slot, table_row):
    """The CacheInsert splice, paged form: write a CONTIGUOUS batch-1
    prefilled cache ``slot_kv`` ([1, H, cap', *] raw array or QuantKV —
    ``cap'`` a multiple of the pool block size, zero-padded) into the
    pool blocks named by ``table_row`` ([nmax] int32, trash-padded past
    the slot's allocation) and point slot ``slot``'s table row at them.
    One scatter per leaf; ``slot`` and ``table_row`` ride as traced
    values so every slot/allocation shares one compile."""
    refuse_latent(slot_kv, "paged_kv.paged_splice")
    import jax

    def leaf(pool, contiguous):
        bs = int(pool.shape[2])
        H = int(pool.shape[1])
        nmax = int(contiguous.shape[2]) // bs
        # [H, nmax*bs, rest] -> [nmax, H, bs, rest]; trash-padded
        # entries collide on block 0, which nothing live attends to
        rows = contiguous[0].reshape(
            H, nmax, bs, contiguous.shape[-1]).transpose(1, 0, 2, 3)
        return pool.at[table_row[:nmax]].set(rows.astype(pool.dtype))

    new_kv = jax.tree_util.tree_map(leaf, paged.kv, slot_kv)
    return PagedKV(new_kv, paged.table.at[slot].set(table_row))


def paged_fetch(paged, slot_kv, table_row):
    """Inverse of :func:`paged_splice` (ISSUE 18 prefix cache):
    materialize the pool blocks named by ``table_row`` ([nmax] int32,
    trash-padded) into a CONTIGUOUS batch-1 cache shaped like
    ``slot_kv`` and return that contiguous tree. The engine runs this
    once per shared-prefix admission so the tail prefill's attention
    sees the cached prefix K/V at positions ``0..start-1`` — rows from
    trash-mapped entries are garbage, which the position mask
    (``kpos > qpos``) blinds. One gather per leaf; ``table_row`` rides
    traced so every admission shares one compile."""
    refuse_latent(slot_kv, "paged_kv.paged_fetch")
    import jax

    def leaf(pool, contiguous):
        bs = int(pool.shape[2])
        H = int(pool.shape[1])
        nmax = int(contiguous.shape[2]) // bs
        g = pool[table_row[:nmax]]  # [nmax, H, bs, rest]
        out = g.transpose(1, 0, 2, 3).reshape(
            1, H, nmax * bs, g.shape[-1])
        return out.astype(contiguous.dtype)

    return jax.tree_util.tree_map(leaf, paged.kv, slot_kv)


def paged_splice_tail(paged, slot_kv, slot, table_row, start, length,
                      cow_src, cow_dst):
    """The CacheInsert splice, SHARED-PREFIX form (ISSUE 18): adopt a
    prefilled contiguous batch-1 cache into the pool writing ONLY
    positions ``start <= p < length`` — positions below ``start`` live
    in refcounted prefix-cache blocks referenced (not copied) by
    ``table_row``, and writing them would corrupt every other reader.
    When the tail's first write lands inside a shared block (the
    full-prefix-match case) the caller passes ``cow_src``/``cow_dst``:
    the shared block is copied into the request's private ``cow_dst``
    FIRST, then the tail scatter overlays the new rows — copy-on-write
    in two fused device ops. ``cow_src = cow_dst = 0`` (trash
    self-copy) is the no-CoW case. Dead positions collide on the trash
    block. All scalars ride traced — one compile covers every
    admission."""
    refuse_latent(slot_kv, "paged_kv.paged_splice_tail")
    import jax
    import jax.numpy as jnp

    def leaf(pool, contiguous):
        bs = int(pool.shape[2])
        cap = int(contiguous.shape[2])
        pooled = pool.at[cow_dst].set(pool[cow_src])
        rows = contiguous[0].transpose(1, 0, 2)  # [cap, H, rest]
        p = jnp.arange(cap, dtype=jnp.int32)
        live = (p >= start) & (p < length)
        phys = jnp.where(live, table_row[p // bs], 0)
        return pooled.at[phys, :, p % bs, :].set(
            rows.astype(pool.dtype))

    new_kv = jax.tree_util.tree_map(leaf, paged.kv, slot_kv)
    return PagedKV(new_kv, paged.table.at[slot].set(table_row))


def paged_adopt(paged, rows, slot, table_row):
    """The CacheInsert splice, MIGRATED form (ISSUE 17): adopt a KV
    bundle's gathered block rows into this pool. ``rows`` is the
    bundle's per-leaf stack zero-padded to the table width —
    ``[nmax, H, bs, rest]`` raw payload, or a ``(payload, scales)``
    pair for a QuantKV pool, adopted NARROW with no dequantize round
    trip (that is the bit-exact contract) — and ``table_row`` ([nmax]
    int32) names the destination physical blocks, trash-padded past
    the slot's allocation. Rows past the transferred prefix are zeros
    landing in blocks the resumed request has not written yet (or in
    trash), which nothing live attends to. One scatter per array;
    ``slot``/``table_row`` ride traced so every migration shares one
    compile."""
    kv = paged.kv
    if hasattr(kv, "q"):
        qrows, srows = rows
        new_kv = type(kv)(
            kv.q.at[table_row].set(qrows.astype(kv.q.dtype)),
            kv.scale.at[table_row].set(srows.astype(kv.scale.dtype)))
    else:
        payload = rows[0] if isinstance(rows, (tuple, list)) else rows
        new_kv = kv.at[table_row].set(payload.astype(kv.dtype))
    return PagedKV(new_kv, paged.table.at[slot].set(table_row))


def retire_tables(cache_tree, slot: int):
    """Redirect slot ``slot``'s table rows to the trash block across a
    whole cache pytree (host-side, once per retired request): after its
    blocks go back to the free list, the done slot's frozen-position
    keep-alive writes land in trash instead of a block that may already
    belong to a NEW request. Eager ``at[].set`` on the tiny int32
    tables — no compiled-program churn."""
    import jax

    def fix(leaf):
        if isinstance(leaf, PagedKV):
            return PagedKV(leaf.kv, leaf.table.at[slot].set(0))
        return leaf

    return jax.tree_util.tree_map(
        fix, cache_tree, is_leaf=lambda v: isinstance(v, PagedKV))


# ---------------------------------------------------------------------------
# host-side block pool (alloc/free is a scheduling decision: it runs
# once per REQUEST on the host, never per token, never in-graph)
# ---------------------------------------------------------------------------


class BlockPool:
    """Free-list over physical blocks ``1..P-1`` (0 is trash).

    The engine allocates a request's whole block budget at insert time
    (``prompt + max_new_tokens`` is known at submit), so appending
    mid-flight never allocates and admission is a single
    ``free >= needed`` check — the admission-control primitive the
    router's per-host accounting rides on.

    ISSUE 18 makes the pool REFCOUNT-aware: a block taken by ``alloc``
    starts at refcount 1; the prefix cache's :meth:`ref` bumps it for
    every additional reader (the index itself, each borrowing slot);
    ``release`` decrements and returns a block to the free list only
    when the last reference drops — never free-while-referenced. A
    pool that never calls ``ref`` behaves exactly like the round-13
    original (alloc at 1, release frees immediately)."""

    def __init__(self, total_blocks: int):
        if int(total_blocks) < 2:
            raise ValueError("BlockPool needs >= 2 blocks (incl. trash)")
        self.total = int(total_blocks) - 1  # allocatable (sans trash)
        self._free: List[int] = list(range(1, int(total_blocks)))
        self._refs: dict = {}
        self.freed_total = 0

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks, or None when the pool can't cover the request
        (the caller defers admission — nothing is partially taken)."""
        if n > len(self._free):
            return None
        taken, self._free = self._free[:n], self._free[n:]
        for b in taken:
            self._refs[b] = 1
        return taken

    def ref(self, blocks: List[int]) -> None:
        """Add one reference to each block (a prefix-cache publish or a
        borrowing slot's table reference). Host-side bookkeeping only."""
        for b in blocks:
            self._refs[b] = self._refs.get(b, 1) + 1

    def refcount(self, block: int) -> int:
        """Current references on an allocated block (0 if free)."""
        return self._refs.get(int(block), 0)

    def release(self, blocks: List[int]) -> None:
        """Drop one reference per block; a block rejoins the free list
        (and counts toward ``freed_total``) only at refcount zero."""
        for b in blocks:
            n = self._refs.get(b, 1) - 1
            if n <= 0:
                self._refs.pop(b, None)
                self.freed_total += 1
                self._free.append(b)
            else:
                self._refs[b] = n

    def grow(self, extra: int) -> List[int]:
        """Register ``extra`` NEW physical blocks (ids continue past
        the current pool) — the engine's ``expand_slots`` pads the
        device pool by the same count and the fresh ids go straight to
        the free list (ISSUE 16: the serving half of a fleet-controller
        lend)."""
        if int(extra) <= 0:
            return []
        ids = list(range(self.total + 1, self.total + 1 + int(extra)))
        self.total += int(extra)
        self._free.extend(ids)
        return ids

    def shrink(self, want: int) -> int:
        """Withdraw up to ``want`` blocks from the TOP of the id space —
        only ids that are currently free can go (an in-use high block
        defers; blocks are fungible, so the remainder is withdrawn on a
        later attempt once traffic frees it). Returns how many ids were
        withdrawn; the caller truncates the device pool to
        ``total + 1`` blocks to match."""
        free = set(self._free)
        withdrawn = 0
        while withdrawn < int(want) and self.total >= 1 \
                and self.total in free:
            free.discard(self.total)
            self.total -= 1
            withdrawn += 1
        if withdrawn:
            self._free = [b for b in self._free if b <= self.total]
        return withdrawn


# ---------------------------------------------------------------------------
# byte accounting (static ints — bench/telemetry price HBM from shapes)
# ---------------------------------------------------------------------------


def _leaf_bytes(arr) -> int:
    n = 1
    for d in arr.shape:
        n *= int(d)
    return n * int(getattr(arr.dtype, "itemsize", 4) or 4)


def pool_bytes(cache_tree) -> int:
    """Resident HBM bytes of every cache buffer in a pytree (paged
    pools + tables, contiguous buffers, QuantKV payload + scales) —
    static shape arithmetic, zero device reads."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(cache_tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += _leaf_bytes(leaf)
    return total


def worst_case_bytes(batch, heads, capacity, head_dim, itemsize=4,
                     layers=1) -> int:
    """What the CONTIGUOUS layout reserves for the same pool: K + V at
    [B, H, cap, Dh] per layer — the baseline the paged saving is
    measured against in bench extra."""
    return (2 * int(layers) * int(batch) * int(heads) * int(capacity)
            * int(head_dim) * int(itemsize))
