"""KV block migration plane (ISSUE 17 tentpole).

Round 15's failover/drain recovery is token-exact but pays for it by
RE-PREFILLING prompt+prefix on the survivor — recovery cost grows
linearly with context, exactly when the fleet is degraded. This module
is the recompute-free alternative: a request's live KV blocks move to
the survivor as data, the survivor splices them into its own pool, and
decode continues mid-sentence with ZERO `PrefillStep` invocations.

The unit of transfer is the :class:`KVBundle`:

- **blocks** — for every `paged_kv.PagedKV` cache leaf, the request's
  allocated physical blocks gathered through its block table into a
  ``[n, H, bs, rest]`` stack. A QuantKV pool contributes payload AND
  scales in their NARROW storage form — the bundle never dequantizes,
  so an int8/fp8 cache round-trips bit-exact (asserted in
  tests/test_serving_migration.py);
- **manifest** — everything the survivor needs to resume the request
  as host state: rid, prompt/resume/emitted tokens, the cache position
  (``ctx`` = rows actually written), the last emitted token (the next
  step's feed), sampling params, the remaining budget, and a per-block
  CRC32 over the raw bytes of every leaf's row for that block.

Transports:

- **in-process** (LocalHost -> LocalHost): the gathered leaves hand to
  the survivor engine directly; `distributed.resharding.relayout_tree`
  (the PR-11 re-layout path) re-places them onto the destination
  pool's sharding before the compiled gather-scatter insert
  (`jit.MigrateInsert`, the `CacheInsert` seam) writes them in;
- **cross-process** (FileHost): a JSON blob next to the mailbox verbs
  (``outbox/kv_<rid>.json``) written by the worker on the ``extract``
  verb, CRC-verified by the router on arrival. A blob that never
  arrives inside ``PADDLE_SERVE_MIGRATE_TIMEOUT_MS`` times out.

The fallback ladder (graceful degradation, never a dropped request):
source unreachable / blob timeout -> ``kv_migrate_fail`` (reason
``timeout``/``error``) -> round-15 re-prefill resume; any block failing
CRC -> ``kv_migrate_fail`` naming the block (reason ``crc``) ->
re-prefill; survivor pool can't cover the demand -> reason
``no_capacity`` -> re-prefill (which may queue where a splice cannot).
`serve:kv_corrupt:nth[:block]` and `serve:kv_lost:nth` fault rules
exercise the first two rungs deterministically.

The drain cost model (:func:`migrate_cost_tokens`) prices a transfer in
token-equivalents so `Router.drain_host` can compare "finish in place"
against "move the blocks" per request: a request a few tokens from done
finishes in place even above ``drain_inplace_tokens`` when its context
makes the move dearer than the remainder.

Env knobs (documented in README):
  ``PADDLE_SERVE_MIGRATE``               1 = migrate-first recovery (default);
                                         0 = always re-prefill (round-15 path)
  ``PADDLE_SERVE_MIGRATE_TIMEOUT_MS``    cross-process blob arrival deadline (500)
  ``PADDLE_SERVE_MIGRATE_COST_TOKENS``   flat transfer cost in token-equivalents (3)
  ``PADDLE_SERVE_MIGRATE_COST_PER_KCTX`` added cost per 1k tokens of context (1.0)
"""
from __future__ import annotations

import base64
import json
import os
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "KVBundle", "gather_leaves", "block_crcs", "migrate_enabled",
    "migrate_timeout_ms_default", "migrate_cost_tokens",
]

_ENABLE_ENV = "PADDLE_SERVE_MIGRATE"
_TIMEOUT_ENV = "PADDLE_SERVE_MIGRATE_TIMEOUT_MS"
_COST_FLAT_ENV = "PADDLE_SERVE_MIGRATE_COST_TOKENS"
_COST_KCTX_ENV = "PADDLE_SERVE_MIGRATE_COST_PER_KCTX"


def migrate_enabled() -> bool:
    """``PADDLE_SERVE_MIGRATE`` — block migration as the failover/drain
    fast path (default on); off = every recovery re-prefills (the
    round-15 behaviour, still the asserted fallback either way)."""
    return os.environ.get(_ENABLE_ENV, "1").lower() not in (
        "0", "false", "off")


def migrate_timeout_ms_default() -> float:
    """``PADDLE_SERVE_MIGRATE_TIMEOUT_MS`` — how long the router waits
    for a cross-process bundle blob before falling back to re-prefill
    (default 500). The in-process path is synchronous and never
    waits."""
    try:
        return max(float(os.environ.get(_TIMEOUT_ENV, "500")), 1.0)
    except ValueError:
        return 500.0


def migrate_cost_tokens(ctx: int) -> float:
    """The drain decision's price of moving ``ctx`` tokens of KV, in
    TOKEN-EQUIVALENTS (comparable to "tokens left to decode in place"):
    a flat per-migration overhead (verb/blob/splice round trip,
    ``PADDLE_SERVE_MIGRATE_COST_TOKENS``) plus a per-context term
    (bytes moved scale with ctx, ``PADDLE_SERVE_MIGRATE_COST_PER_KCTX``
    per 1k tokens). Deterministic host arithmetic — the boundary is
    testable without wall clocks; fleets with a measured link price
    retune the two knobs from PERF.md round 17."""
    try:
        flat = float(os.environ.get(_COST_FLAT_ENV, "3"))
    except ValueError:
        flat = 3.0
    try:
        per_kctx = float(os.environ.get(_COST_KCTX_ENV, "1.0"))
    except ValueError:
        per_kctx = 1.0
    return max(flat, 0.0) + max(int(ctx), 0) * max(per_kctx, 0.0) / 1e3


# ---------------------------------------------------------------------------
# leaf gather + per-block CRC
# ---------------------------------------------------------------------------


def gather_leaves(cache_tree, blocks: Sequence[int]) -> List[Tuple]:
    """Gather physical blocks ``blocks`` out of every ``PagedKV`` leaf
    of a cache pytree: one host tuple per leaf — ``(payload,)`` with
    payload ``[n, H, bs, rest]``, or ``(payload, scales)`` for a
    QuantKV pool (both NARROW — the bundle never dequantizes, which is
    what makes a quantized migration bit-exact). One gather per leaf
    per MIGRATION, not per token; the copies are host-resident so the
    CRC pass and the wire form read the same bytes."""
    import jax

    from . import paged_kv as pk

    pk.refuse_latent(cache_tree, "kv_migration.gather_leaves")

    idx = np.asarray(list(blocks), np.int32)
    out: List[Tuple] = []
    for leaf in jax.tree_util.tree_leaves(
            cache_tree, is_leaf=lambda v: isinstance(v, pk.PagedKV)):
        if not isinstance(leaf, pk.PagedKV):
            continue
        kv = leaf.kv
        if hasattr(kv, "q"):
            out.append((np.asarray(kv.q[idx]).copy(),
                        np.asarray(kv.scale[idx]).copy()))
        else:
            out.append((np.asarray(kv[idx]).copy(),))
    return out


def block_crcs(leaves: List[Tuple], n_blocks: int) -> List[int]:
    """CRC32 per logical block: block ``b``'s checksum chains over row
    ``b`` of every array of every leaf (payload then scales), so a flip
    anywhere in the block's bytes — either K or V, any layer, payload
    or scale — names exactly that block."""
    crcs = []
    for b in range(int(n_blocks)):
        c = 0
        for leaf in leaves:
            for arr in leaf:
                c = zlib.crc32(
                    np.ascontiguousarray(arr[b]).tobytes(), c)
        crcs.append(int(c) & 0xFFFFFFFF)
    return crcs


# ---------------------------------------------------------------------------
# wire form (the FileHost mailbox blob; stdlib-decodable on purpose)
# ---------------------------------------------------------------------------


def _np_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        # fp8 and friends live in ml_dtypes (a jax dependency); plain
        # numpy does not know their names
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _arr_wire(a: np.ndarray) -> dict:
    return {
        "dtype": str(a.dtype),
        "shape": [int(d) for d in a.shape],
        "data": base64.b64encode(
            np.ascontiguousarray(a).tobytes()).decode("ascii"),
    }


def _arr_unwire(d: dict) -> np.ndarray:
    raw = base64.b64decode(d["data"])
    return np.frombuffer(raw, dtype=_np_dtype(d["dtype"])).reshape(
        d["shape"]).copy()


class KVBundle:
    """One request's migratable KV: ``leaves`` (per-PagedKV-leaf host
    array tuples, see :func:`gather_leaves`) + ``manifest`` (resume
    state + per-block CRCs). The container is transport-agnostic: the
    in-process path hands it across directly, the mailbox path round-
    trips it through :meth:`write_blob`/:meth:`read_blob`."""

    def __init__(self, manifest: Dict, leaves: List[Tuple]):
        self.manifest = dict(manifest)
        self.leaves = [tuple(leaf) for leaf in leaves]

    # -- accounting --------------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return int(self.manifest.get("n_blocks", 0))

    @property
    def nbytes(self) -> int:
        return sum(int(arr.nbytes) for leaf in self.leaves
                   for arr in leaf)

    # -- integrity ---------------------------------------------------------
    def seal(self) -> "KVBundle":
        """Stamp the per-block CRCs into the manifest (extract side)."""
        self.manifest["crcs"] = block_crcs(self.leaves, self.n_blocks)
        return self

    def verify(self) -> List[int]:
        """Indices of blocks whose bytes no longer match their sealed
        CRC (empty = intact). The receive-side gate of the fallback
        ladder: ANY bad block fails the whole per-request bundle — a
        partially spliced cache would decode garbage token-exactly
        never."""
        want = list(self.manifest.get("crcs") or [])
        have = block_crcs(self.leaves, self.n_blocks)
        return [b for b in range(self.n_blocks)
                if b >= len(want) or want[b] != have[b]]

    def flip_bit(self, block: Optional[int] = None) -> int:
        """Flip one payload bit of block ``block`` (default 0) — the
        hand of ``serve:kv_corrupt:nth[:block]``. Returns the block
        index actually flipped."""
        b = int(block or 0) % max(self.n_blocks, 1)
        arr = self.leaves[0][0]
        raw = arr.view(np.uint8).reshape(arr.shape[0], -1)
        raw[b, 0] ^= 1
        return b

    # -- wire --------------------------------------------------------------
    def to_wire(self) -> dict:
        return {
            "v": 1,
            "manifest": self.manifest,
            "leaves": [[_arr_wire(a) for a in leaf]
                       for leaf in self.leaves],
        }

    @classmethod
    def from_wire(cls, d: dict) -> "KVBundle":
        return cls(d.get("manifest") or {},
                   [tuple(_arr_unwire(a) for a in leaf)
                    for leaf in d.get("leaves") or []])

    def write_blob(self, path: str) -> None:
        """Atomic JSON blob write (same tmp+replace discipline as the
        mailbox verbs — the reader never sees a torn bundle)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_wire(), f)
        os.replace(tmp, path)

    @classmethod
    def read_blob(cls, path: str) -> "KVBundle":
        with open(path) as f:
            return cls.from_wire(json.load(f))
