"""Decode loop + continuous-batching inference engine (ISSUE 9,
production tier ISSUE 13).

Two layers on top of the compiled `jit.PrefillStep`/`jit.DecodeStep`
pair:

- :func:`generate` — the whole-batch reference loop (the e2e "load
  checkpoint -> prefill -> decode N tokens" script shape): bucketed
  compiled prefill, one compiled single-token step, DEVICE-RESIDENT
  loop state. With ``sync_every=0`` (the default without a stop token)
  the host touches the device exactly once after the loop — zero
  per-token transfers, asserted in tests/test_serving.py. With a
  ``draft_model`` the greedy loop runs `jit.SpeculativeDecodeStep`
  instead: 1..k+1 tokens per dispatch, token-exact vs the plain step.

- :class:`InferenceEngine` — slot-based continuous batching: a fixed
  [slots, H, cap, Dh] cache pool, per-request prefill into a length
  bucket (compile cache is per bucket — warm compiles are cheap under
  the persistent XLA cache), insert-on-free scheduling (a finished
  slot is immediately re-filled from the queue), per-slot sampling
  params and stop conditions riding the compiled step as [S] vectors,
  and host readbacks only on the ``PADDLE_SERVE_SYNC_EVERY`` cadence —
  the same cadence `decode_metrics` telemetry rides (zero extra syncs).

Round 13 grows the engine into the production tier:

- **paged KV pool** (``PADDLE_SERVE_BLOCK_SIZE`` / ctor args): the
  cache is a `serving.paged_kv` block pool + per-slot tables; a
  request's whole block budget (``prompt + max_new_tokens``) is
  allocated at insert and freed at retire, so HBM tracks ACTUAL
  context, not slots x capacity, and a too-full pool DEFERS admission
  instead of overcommitting (the router's per-host admission signal);
- **chunked prefill** (``PADDLE_SERVE_PREFILL_CHUNK``): long prompts
  prefill in fixed-size chunks interleaved with decode windows
  through `PrefillStep`'s ``start`` seam, so one long prompt can no
  longer stall every inflight request for its whole prefill — the
  TTFT bound under load;
- **TTFT accounting**: submit -> first-token latency per request,
  riding the existing readback cadence onto `decode_metrics`.

Round 18 (multi-tenant serving): a paged engine can attach a
`serving.prefix_cache.PrefixCache` (``PADDLE_SERVE_PREFIX_CACHE=1`` or
the ``prefix_cache`` ctor arg) — published prompt blocks are shared by
table reference, admission charges only the UNSHARED block demand, the
borrower prefills just the tail (prefix K/V materialized into the
scratch by the compiled ``PrefixFetch`` gather first, so the tail's
attention sees real history), and the splice is the copy-on-write
``paged_splice_tail`` form of CacheInsert. A `serving.adapters
.AdapterSet` attached to the model BEFORE the engine threads per-slot
adapter ids through every insert path and the decode state, so one
compiled step serves a whole fine-tune fleet.

Env knobs (documented in README):
  ``PADDLE_SERVE_SYNC_EVERY``    decode steps per engine readback (16)
  ``PADDLE_SERVE_BUCKETS``       prefill length buckets ("16,32,64,128,
                                 256,512,1024")
  ``PADDLE_SERVE_BLOCK_SIZE``    KV block size; 0 = contiguous cache
  ``PADDLE_SERVE_PREFILL_CHUNK`` prefill chunk length; 0 = whole-prompt
  ``PADDLE_SERVE_SPEC_K``        draft tokens per speculative round (4)
  ``PADDLE_SERVE_PREFIX_CACHE``  1 = refcounted CoW prefix cache (0)
  ``PADDLE_SERVE_PREFIX_BLOCKS`` max prefix-cache entries (0 = pool)
"""
from __future__ import annotations

import itertools
import os
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiler as _prof
from ..jit.decode_step import (
    NO_BUDGET, DecodeState, DecodeStep, PrefillStep, SpecDecodeState,
    SpeculativeDecodeStep, spec_k_default,
)
from . import paged_kv as pk
from . import sampling
from .prefix_cache import PrefixCache, prefix_cache_enabled

__all__ = ["GenerationConfig", "generate", "Request", "GeneratedResult",
           "InferenceEngine", "prefill_buckets", "bucket_for",
           "prefill_chunk_default"]

_SYNC_ENV = "PADDLE_SERVE_SYNC_EVERY"
_BUCKETS_ENV = "PADDLE_SERVE_BUCKETS"
_CHUNK_ENV = "PADDLE_SERVE_PREFILL_CHUNK"


def sync_every_default() -> int:
    try:
        return max(int(os.environ.get(_SYNC_ENV, "16")), 1)
    except ValueError:
        return 16


def prefill_chunk_default() -> int:
    """``PADDLE_SERVE_PREFILL_CHUNK`` — prompt tokens per chunked-
    prefill piece; 0 (default) prefills whole prompts in one program."""
    try:
        return max(int(os.environ.get(_CHUNK_ENV, "0")), 0)
    except ValueError:
        return 0


def prefill_buckets() -> List[int]:
    """The prefill length buckets (sorted). Each bucket is one compile
    of the prefill program; prompts pad up to their bucket."""
    raw = os.environ.get(_BUCKETS_ENV, "16,32,64,128,256,512,1024")
    out = sorted({int(t) for t in raw.split(",") if t.strip()})
    if not out:
        raise ValueError(f"{_BUCKETS_ENV} parsed to no buckets: {raw!r}")
    return out


def bucket_for(length: int, cap: int,
               buckets: Optional[List[int]] = None) -> int:
    """Smallest bucket >= length, clamped to the cache capacity; lengths
    past the largest bucket use the capacity itself (one extra shape)."""
    if length > cap:
        raise ValueError(f"prompt length {length} exceeds cache "
                         f"capacity {cap}")
    for b in (buckets if buckets is not None else prefill_buckets()):
        if b >= length:
            return min(b, cap)
    return cap


class GenerationConfig:
    """Sampling + stop config for :func:`generate` (scalars or per-row
    vectors): temperature<=0 greedy, top_k<=0 / top_p>=1 filters off."""

    def __init__(self, max_new_tokens=16, temperature=0.0, top_k=0,
                 top_p=1.0, eos_id=None, seed=0):
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.seed = seed


def _pad_prompts(prompts, pad_to, pad_id=0):
    """Ragged [B][*] int prompts -> (ids [B, pad_to] int32, len [B])."""
    rows = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    lens = np.asarray([r.size for r in rows], np.int32)
    ids = np.full((len(rows), pad_to), pad_id, np.int32)
    for i, r in enumerate(rows):
        ids[i, : r.size] = r
    return ids, lens


def _spec_generate(model, draft_model, rows, n_new, cfg, cap, bucket,
                   sync_every, spec_k, prefill, decode):
    """The speculative greedy loop behind :func:`generate`: one
    `SpeculativeDecodeStep` dispatch emits 1..k+1 tokens per slot; the
    host compacts the -1 sentinels AFTER the loop, so transfers scale
    with readback windows exactly like the plain loop."""
    B = len(rows)
    ids, lens = _pad_prompts(rows, bucket)
    pre = prefill if prefill is not None else PrefillStep(model)
    step = decode if isinstance(decode, SpeculativeDecodeStep) else \
        SpeculativeDecodeStep(model, draft_model, k=spec_k)
    # the draft prefill reuses across calls through the step object —
    # the same compile-cache seam `prefill`/`decode` give the target
    dpre = getattr(step, "_draft_prefill", None)
    if dpre is None:
        dpre = step._draft_prefill = PrefillStep(draft_model)
    caches = model.gen_cache(B, cap)
    dcaches = draft_model.gen_cache(B, cap)
    last, cache_raws, pos = pre(caches, ids, lens)
    _, dcache_raws, _ = dpre(dcaches, ids, lens)
    first = sampling.greedy(last)
    state = SpecDecodeState.make(
        cache_raws, dcache_raws, first, pos, eos_id=cfg.eos_id,
        budget=n_new - 1)
    state.done = first == state.eos
    state.tok = jnp.where(state.done, jnp.int32(0), first)

    emits = [first[:, None]]
    # None -> the default cadence (the in-graph budget guarantees
    # termination, so early-exit checks only save wasted rounds); an
    # EXPLICIT 0 keeps the round-9 contract — zero mid-loop host syncs,
    # one readback after the loop
    sync = sync_every_default() if sync_every is None \
        else max(int(sync_every), 0)
    since = 0
    # each round emits >= 1 token per live slot, so n_new - 1 rounds
    # always exhaust the budget; the done check on the sync cadence
    # exits as soon as acceptance ran ahead of that worst case
    for _ in range(n_new - 1):
        emit, state = step(state)
        emits.append(emit)
        since += 1
        if sync and since >= sync:
            since = 0
            if bool(np.asarray(state.done).all()):
                break
    seq = np.asarray(jnp.concatenate(emits, axis=1))
    out = np.full((B, n_new), -1, np.int32)
    for b in range(B):
        row = [int(t) for t in seq[b] if t >= 0]
        out[b, : min(len(row), n_new)] = row[:n_new]
    return out


def generate(model, input_ids, max_new_tokens=None, *, config=None,
             temperature=0.0, top_k=0, top_p=1.0, eos_id=None, seed=0,
             max_length=None, sync_every=None, return_logits=False,
             prefill=None, decode=None, draft_model=None, spec_k=None):
    """Decode ``max_new_tokens`` tokens for a whole batch.

    Returns [B, max_new_tokens] int32 numpy tokens (``-1`` marks
    positions after a row hit its stop token); with
    ``return_logits=True`` also the [B, N, V] f32 per-step pre-sampling
    logits (a test/debug hook — it keeps N logits rows alive on
    device).

    ``sync_every=0`` (default when no ``eos_id``) never reads the
    device inside the loop; with a stop token the default checks the
    done mask every ``PADDLE_SERVE_SYNC_EVERY`` steps to exit early.
    ``prefill``/``decode`` accept pre-built step objects so repeated
    calls share their compile caches.

    ``draft_model`` switches the loop to SPECULATIVE decoding (ISSUE
    13): greedy-only (the in-graph accept rule compares argmaxes —
    token-exact vs the plain step by construction), ``spec_k`` drafts
    per round (default ``PADDLE_SERVE_SPEC_K``). The cache reserves
    ``spec_k`` rows of headroom for the round's in-flight rejected
    writes.
    """
    cfg = config if config is not None else GenerationConfig(
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_id=eos_id, seed=seed)
    # the explicit arg wins WITHOUT mutating a caller-owned config
    n_new = int(max_new_tokens) if max_new_tokens is not None \
        else cfg.max_new_tokens
    model.eval()
    rows = [np.asarray(p, np.int32).reshape(-1) for p in input_ids]
    B = len(rows)
    max_len = max(r.size for r in rows)
    if draft_model is not None:
        if np.any(np.asarray(cfg.temperature, np.float32) > 0.0):
            raise ValueError(
                "speculative decoding is greedy-only (the accept rule "
                "compares argmaxes); pass temperature<=0 or drop "
                "draft_model")
        if return_logits:
            raise ValueError(
                "return_logits is not supported with draft_model: the "
                "speculative step folds target logits into the accept "
                "decision in-graph")
        draft_model.eval()
        if isinstance(decode, SpeculativeDecodeStep):
            # the prebuilt step's own k drives how many rows each round
            # writes — headroom MUST follow it, not the env default
            # (a larger k than the reserved headroom would clamp-write
            # over live rows near the end of generation)
            if spec_k is not None and int(spec_k) != decode.k:
                raise ValueError(
                    f"spec_k={spec_k} conflicts with the prebuilt "
                    f"decode step's k={decode.k}")
            K = decode.k
        else:
            K = int(spec_k) if spec_k is not None else spec_k_default()
        # + K headroom: a round writes k+1 rows at pos..pos+k and the
        # rejected tail must land inside the buffer (write-then-attend
        # masks it until overwritten)
        cap = int(max_length) if max_length is not None \
            else max_len + n_new + K
        if max_len + n_new + K > cap:
            raise ValueError(
                f"max_length={cap} cannot hold prompt ({max_len}) + "
                f"{n_new} new tokens + spec_k={K} headroom")
        bucket = bucket_for(max_len, cap)
        return _spec_generate(model, draft_model, rows, n_new, cfg,
                              cap, bucket, sync_every, K, prefill,
                              decode)
    cap = int(max_length) if max_length is not None \
        else max_len + n_new
    if max_len + n_new > cap + 1:
        raise ValueError(
            f"max_length={cap} cannot hold prompt ({max_len}) + "
            f"{n_new} new tokens")
    bucket = bucket_for(max_len, cap)
    ids, lens = _pad_prompts(rows, bucket)

    pre = prefill if prefill is not None else PrefillStep(model)
    step = decode if decode is not None else DecodeStep(model)
    caches = model.gen_cache(B, cap)
    last, cache_raws, pos = pre(caches, ids, lens)

    key = jax.random.PRNGKey(cfg.seed)
    key, sub = jax.random.split(key)
    state = DecodeState.make(
        cache_raws, first_tokens=jnp.zeros((B,), jnp.int32), pos=pos,
        temperature=cfg.temperature, top_k=cfg.top_k, top_p=cfg.top_p,
        eos_id=cfg.eos_id, budget=n_new - 1)
    state.key = key
    first = sampling.sample(last, sub, state.temperature, state.top_k,
                            state.top_p)
    state.done = first == state.eos
    state.tok = jnp.where(state.done, jnp.int32(0), first)

    emits = [first]
    logits_all = [last] if return_logits else None
    if sync_every is None:
        sync_every = 0 if cfg.eos_id is None else sync_every_default()
    since_sync = 0
    for _ in range(n_new - 1):
        emit, logits, state = step(state)
        emits.append(emit)
        if return_logits:
            logits_all.append(logits)
        since_sync += 1
        if sync_every and since_sync >= sync_every:
            since_sync = 0
            if bool(np.asarray(state.done).all()):
                break
    toks = np.asarray(jnp.stack(emits, axis=1))
    out = np.full((B, n_new), -1, np.int32)
    out[:, : toks.shape[1]] = toks
    if return_logits:
        return out, np.asarray(jnp.stack(logits_all, axis=1))
    return out


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

_rid_counter = itertools.count()


class Request:
    """One generation request for the engine.

    ``resume_tokens`` (ISSUE 15) carries tokens a PREVIOUS host already
    emitted for this request: the engine prefills ``prompt_ids +
    resume_tokens`` as one prefix (the caller — Router failover — has
    already decremented ``max_new_tokens`` by the resumed count), so a
    greedy request continues TOKEN-EXACTLY where the dead host stopped.
    The engine's result holds only the NEW tokens; the router owns the
    prefix reassembly.

    ``adapter`` (ISSUE 18) names the fine-tune serving this request —
    a row of the engine model's resident :class:`serving.adapters
    .AdapterSet`; 0 (default) is the base model. Admission rejects ids
    that are not loaded."""

    def __init__(self, prompt_ids, max_new_tokens=16, temperature=0.0,
                 top_k=0, top_p=1.0, eos_id=None, rid=None,
                 trace_id=None, resume_tokens=None, adapter=0):
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.resume_tokens = (
            np.asarray([], np.int32) if resume_tokens is None
            else np.asarray(resume_tokens, np.int32).reshape(-1))
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self.adapter = int(adapter)
        self.rid = next(_rid_counter) if rid is None else rid
        #: request-scoped trace id (ISSUE 14): Router.submit stamps one
        #: so the engine's admission/prefill/decode-window/retire span
        #: rows and the decode_request row stitch into one life; None
        #: (direct engine use) keeps the span stream empty
        self.trace_id = trace_id
        self.t_submit: Optional[float] = None  # set by engine.submit

    @property
    def prefill_ids(self) -> np.ndarray:
        """The tokens the engine actually prefills: prompt plus any
        resumed prefix from a failed-over host."""
        if self.resume_tokens.size == 0:
            return self.prompt_ids
        return np.concatenate([self.prompt_ids, self.resume_tokens])


class GeneratedResult:
    """Completed request: generated ids + latency accounting."""

    def __init__(self, rid, tokens, prefill_ms, total_ms, ttft_ms=None):
        self.rid = rid
        self.tokens = list(tokens)
        self.prefill_ms = prefill_ms
        self.total_ms = total_ms
        #: submit -> first generated token (includes queue wait +
        #: chunked prefill; the SLO the router schedules against)
        self.ttft_ms = prefill_ms if ttft_ms is None else ttft_ms

    @property
    def ms_per_token(self):
        n = max(len(self.tokens), 1)
        return self.total_ms / n


class _Slot:
    __slots__ = ("req", "t_start", "prefill_ms", "tokens", "ttft_ms")

    def __init__(self, req, t_start, prefill_ms, first_token,
                 ttft_ms=None):
        self.req = req
        self.t_start = t_start
        self.prefill_ms = prefill_ms
        self.tokens = [int(first_token)]
        self.ttft_ms = prefill_ms if ttft_ms is None else ttft_ms


class _Pending:
    """A chunked prefill in flight: the slot and (paged) blocks are
    RESERVED, the batch-1 cache fills one chunk per engine turn."""

    __slots__ = ("req", "slot", "blocks", "raws", "consumed", "t0",
                 "prefill_s")

    def __init__(self, req, slot, blocks, raws, t0):
        self.req = req
        self.slot = slot
        self.blocks = blocks
        self.raws = raws
        self.consumed = 0
        self.t0 = t0
        self.prefill_s = 0.0


class InferenceEngine:
    """Slot-based continuous batching over one model.

    The decode batch is a fixed pool of ``slots``; each slot holds one
    inflight request. A finished slot (stop token, budget) is re-filled
    from the queue at the next readback (insert-on-free) — the compiled
    decode program never changes shape. Per-request prefill runs at
    batch 1 through the length-bucketed `PrefillStep` and is spliced
    into the pool by a small compiled insert program (cache buffers
    donated end to end).

    Round 13 (paged pool): with ``block_size`` (or the env default) the
    cache is a `paged_kv` block pool of ``pool_blocks`` blocks; each
    admitted request takes exactly ``ceil((prompt + max_new) / bs)``
    blocks for its lifetime, so a pool sized for the EXPECTED token
    load serves more slots than worst-case reservation would — and when
    it can't cover the next request, admission DEFERS (the queue holds)
    instead of overcommitting. Retired slots release their blocks and
    their table rows are redirected to the trash block, so the done
    slot's keep-alive writes can never corrupt a reallocated block.

    Round 13 (chunked prefill): with ``prefill_chunk`` (or the env
    default) prompts longer than one chunk prefill incrementally —
    one chunk per engine turn, decode windows in between — bounding
    every inflight request's added latency by one chunk's compute
    instead of one full prompt's.
    """

    def __init__(self, model, *, slots=4, max_length=256,
                 sync_every=None, seed=0, block_size=None,
                 pool_blocks=None, prefill_chunk=None,
                 prefix_cache=None):
        model.eval()
        self.model = model
        self.slots = int(slots)
        self.max_length = int(max_length)
        self.sync_every = (sync_every_default() if sync_every is None
                           else max(int(sync_every), 1))
        self.block_size = (int(block_size) if block_size is not None
                           else pk.block_size_default())
        self.prefill_chunk = (int(prefill_chunk)
                              if prefill_chunk is not None
                              else prefill_chunk_default())
        self._prefill = PrefillStep(model)
        self._decode = DecodeStep(model)
        #: a routed model's device counters, read with every readback
        self._expert_load = getattr(model, "expert_load", None)
        self._selected_keys = getattr(model, "selected_keys", None)
        self._insert_jitted = None
        self._slot_cache_jitted = None
        self._migrate = None  # lazy jit.MigrateInsert (ISSUE 17)
        #: resident fine-tune fleet, if the model carries one (attach
        #: the AdapterSet BEFORE building the engine — the compiled
        #: steps snapshot the buffers at construction)
        self.adapters = getattr(model, "_serve_adapters", None)
        self._prefix_fetch_jitted = None
        self._prefix_insert_jitted = None
        self._prefix_hits = 0
        self._prefix_blocks_shared = 0
        self._cow_copies = 0
        self._queue: deque = deque()
        self._active: Dict[int, _Slot] = {}
        self._pending: Dict[int, _Pending] = {}
        self._key = jax.random.PRNGKey(seed)
        self._pool: Optional[pk.BlockPool] = None
        self._slot_blocks: Dict[int, List[int]] = {}
        self._retiring: set = set()
        self._nmax = 0
        self._admit_deferred = 0
        self._ttft_window: List[float] = []
        if self.prefill_chunk > 0 and \
                self.max_length % self.prefill_chunk:
            # every chunk writes a full C-wide window; with cap % C != 0
            # the LAST chunk of a near-capacity prompt would overrun the
            # cache and dynamic_update_slice would clamp the start —
            # silently overwriting earlier prompt rows. Alignment makes
            # ceil(L/C)*C <= cap for every admissible L.
            raise ValueError(
                f"max_length={self.max_length} must be a multiple of "
                f"prefill_chunk={self.prefill_chunk} (the final chunk "
                f"writes a full chunk-wide window)")
        if self.block_size > 0:
            if self.max_length % self.block_size:
                raise ValueError(
                    f"max_length={self.max_length} must be a multiple "
                    f"of block_size={self.block_size} (the batch-1 "
                    f"prefill cache splices block-aligned)")
            self._nmax = pk.num_blocks(self.max_length, self.block_size)
            total = (pool_blocks if pool_blocks is not None
                     else self.slots * self._nmax + 1)
            self._pool = pk.BlockPool(total)
            caches = model.gen_cache(
                self.slots, self.max_length,
                block_size=self.block_size, pool_blocks=total)
        else:
            caches = model.gen_cache(self.slots, self.max_length,
                                     block_size=0)
        # refcounted CoW prefix cache (ISSUE 18): explicit ctor arg
        # wins; the env knob defaults OFF so round-17 admission stays
        # bitwise. Needs the paged pool (the share unit is a block).
        use_px = (prefix_cache if prefix_cache is not None
                  else prefix_cache_enabled())
        if use_px:
            pk.refuse_latent(caches, "the prefix cache")
        self._prefix: Optional[PrefixCache] = (
            PrefixCache(self.block_size)
            if use_px and self._pool is not None else None)
        self._state = DecodeState.make(
            caches, first_tokens=np.zeros(self.slots, np.int32),
            pos=np.zeros(self.slots, np.int32), seed=seed)
        # every slot starts free
        self._state.done = jnp.ones((self.slots,), bool)
        # commit the fresh pool once so the FIRST CacheInsert call sees
        # the same (committed) signature as every later one — the
        # DecodeStep placement-churn lesson applied to the insert jit
        from ..jit.decode_step import _commit_tree

        self._state = DecodeState(*_commit_tree(self._state.astuple()))
        from ..observability.metrics import (DecodeMetricsSampler,
                                             record_expert_load,
                                             record_sampler_steps,
                                             record_selected_keys)

        self._metrics = DecodeMetricsSampler()
        self._record_expert_load = record_expert_load
        self._record_selected_keys = record_selected_keys
        self._record_sampler_steps = record_sampler_steps

    # -- public API --------------------------------------------------------
    def needed_blocks(self, req: Request) -> int:
        """Blocks the paged pool charges ``req`` (0 when contiguous)."""
        if self._pool is None:
            return 0
        return pk.blocks_for(
            req.prefill_ids.size + req.max_new_tokens, self.block_size)

    def free_blocks(self) -> Optional[int]:
        return None if self._pool is None else self._pool.free

    def queue_depth(self) -> int:
        return len(self._queue)

    def inflight(self) -> int:
        return len(self._active) + len(self._pending)

    def expand_slots(self, n: int) -> int:
        """Grow the decode pool by ``n`` slots at a turn boundary — the
        serving half of a fleet-controller lend (ISSUE 16; under the
        ISSUE-20 live plane this is the in-process join phase: the
        ladder calls it after the lent rank's deliver-phase
        ``load_quantized`` lands, and the router's ``register_capacity``
        publishes the new depth the same tick). Every cache
        leaf gains ``n`` batch rows (paged: ``n * nmax`` fresh pool
        blocks and ``n`` all-trash table rows, registered with the
        BlockPool so admission sees the new capacity immediately), the
        per-slot state vectors extend with done/free entries, and the
        grown state is committed once so the next decode/insert call
        compiles against a committed pool — one ledger-visible
        recompile per expansion, priced in PERF.md, never hidden. New
        slots fill from the queue on the next turn like any free slot;
        weights are untouched (the replicated checkpoint already
        resident serves the wider batch). Returns the new slot count."""
        n = int(n)
        if n <= 0:
            return self.slots
        t0 = time.perf_counter()
        old = self.slots
        st = self._state

        def pad0(arr, count, fill=0):
            z = jnp.full((count,) + arr.shape[1:], fill, arr.dtype)
            return jnp.concatenate([arr, z], axis=0)

        if self._pool is not None:
            extra = n * self._nmax
            self._pool.grow(extra)

            def fix(leaf):
                if not isinstance(leaf, pk.PagedKV):
                    return leaf
                kv = leaf.kv
                if hasattr(kv, "q"):  # QuantKV: payload AND scales grow
                    kv = type(kv)(pad0(kv.q, extra),
                                  pad0(kv.scale, extra))
                else:
                    kv = pad0(kv, extra)
                return pk.PagedKV(kv, pad0(leaf.table, n))

            caches = jax.tree_util.tree_map(
                fix, st.caches,
                is_leaf=lambda v: isinstance(v, pk.PagedKV))
        else:
            caches = jax.tree_util.tree_map(
                lambda lf: pad0(lf, n), st.caches)
        self.slots = old + n
        self._state = DecodeState(
            caches, pad0(st.pos, n), pad0(st.tok, n),
            pad0(st.done, n, True), st.key, pad0(st.temperature, n),
            pad0(st.top_k, n), pad0(st.top_p, n, 1),
            pad0(st.eos, n, -1), pad0(st.budget, n, NO_BUDGET),
            pad0(st.adapter, n))
        from ..jit.decode_step import _commit_tree

        self._state = DecodeState(*_commit_tree(self._state.astuple()))
        from ..observability import bus as _bus

        # what the lend path keeps resident for the wider batch — with an
        # int8 checkpoint loaded the narrow payload + scale buffers ARE
        # the weights (ISSUE 19), so the record prices exactly what a
        # lent chip receives; static shapes, zero device reads
        w_bytes = sum(
            int(o._data.size) * o._data.dtype.itemsize
            for o in list(self.model.parameters())
            + list(self.model.buffers())
        )
        w_quant = sum(
            1 for p in self.model.parameters()
            if getattr(p, "_q_scale", None) is not None
        )
        _bus.emit("engine_expand", {
            "slots_before": old, "slots_after": self.slots,
            "blocks_total": (None if self._pool is None
                             else self._pool.total),
            "weights_bytes": w_bytes, "weights_quantized": w_quant,
            "dur_ms": round((time.perf_counter() - t0) * 1e3, 3)})
        return self.slots

    def retire_slots(self, n: int) -> List[int]:
        """Mark the top ``n`` slots retiring — the reclaim half of a
        lend round trip (the live plane's drain phase rides this exact
        never-refill semantic: ISSUE 20 asserts zero dropped requests
        across a reclaim because retiring slots finish their work
        before the leave phase takes the rank). A retiring slot is
        never refilled; work
        in flight on it finishes first (drain semantics — nothing is
        cancelled). The pool physically truncates lazily: once the
        retiring tail is free — and, for a paged pool, as the highest
        block ids free up (blocks are fungible, so an in-use high id
        defers its withdrawal to a later turn) — cache leaves, state
        vectors, and BlockPool shrink back, checked at every turn
        boundary. Returns the slot ids still marked retiring."""
        n = min(int(n), self.slots - 1)
        if n > 0:
            self._retiring.update(range(self.slots - n, self.slots))
            self._relocate_retiring()
            self._maybe_shrink()
        return sorted(self._retiring)

    def _maybe_shrink(self) -> None:
        cut = 0
        while True:
            top = self.slots - 1 - cut
            if (top not in self._retiring or top in self._active
                    or top in self._pending):
                break
            cut += 1
        if cut == 0:
            return
        t0 = time.perf_counter()
        for s in range(self.slots - cut, self.slots):
            self._retiring.discard(s)
        old = self.slots
        new = old - cut
        st = self._state
        if self._pool is not None:
            # live low slots never reference the withdrawn ids: shrink
            # only surrenders FREE top-of-id-space blocks, and retired
            # slots' table rows were redirected to trash at release
            if self._prefix is not None:
                # idle index entries pinning top-of-id-space blocks
                # would deadlock the withdrawal — evict them first
                self._prefix.evict_above(
                    self._pool, self._pool.total - cut * self._nmax)
            self._pool.shrink(cut * self._nmax)
            P = self._pool.total + 1

            def fix(leaf):
                if not isinstance(leaf, pk.PagedKV):
                    return leaf
                kv = leaf.kv
                if hasattr(kv, "q"):
                    kv = type(kv)(kv.q[:P], kv.scale[:P])
                else:
                    kv = kv[:P]
                return pk.PagedKV(kv, leaf.table[:new])

            caches = jax.tree_util.tree_map(
                fix, st.caches,
                is_leaf=lambda v: isinstance(v, pk.PagedKV))
        else:
            caches = jax.tree_util.tree_map(lambda lf: lf[:new],
                                            st.caches)
        self.slots = new
        self._state = DecodeState(
            caches, st.pos[:new], st.tok[:new], st.done[:new], st.key,
            st.temperature[:new], st.top_k[:new], st.top_p[:new],
            st.eos[:new], st.budget[:new], st.adapter[:new])
        from ..jit.decode_step import _commit_tree

        self._state = DecodeState(*_commit_tree(self._state.astuple()))
        from ..observability import bus as _bus

        _bus.emit("engine_shrink", {
            "slots_before": old, "slots_after": new,
            "blocks_total": (None if self._pool is None
                             else self._pool.total),
            "dur_ms": round((time.perf_counter() - t0) * 1e3, 3)})

    def progress(self) -> Dict[object, List[int]]:
        """rid -> tokens emitted so far, for every request the engine
        holds (ISSUE 15). HOST-side state only: active slots report the
        tokens already read back at window boundaries, pending prefills
        and queued requests report ``[]`` — the failover/drain resume
        path feeds on exactly this map, so it costs zero device reads
        by construction."""
        out: Dict[object, List[int]] = {}
        for st in self._active.values():
            out[st.req.rid] = list(st.tokens)
        for job in self._pending.values():
            out[job.req.rid] = []
        for req in self._queue:
            out[req.rid] = []
        return out

    def cancel(self, rid) -> bool:
        """Withdraw one request without a result row (ISSUE 15 drain:
        the router migrates it elsewhere and must stop THIS engine from
        also serving it — idempotent rids make a race survivable, a
        cancel makes it cheap). Queued: dropped. Pending prefill /
        active slot: the slot is marked done in-graph (its keep-alive
        writes stay masked like any retired slot) and its blocks come
        back. Returns whether anything was withdrawn."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                return True
        for slot, job in list(self._pending.items()):
            if job.req.rid == rid:
                del self._pending[slot]
                self._release(slot, job.blocks)
                return True
        for slot, st in list(self._active.items()):
            if st.req.rid == rid:
                self._active.pop(slot)
                self._state.done = self._state.done.at[slot].set(True)
                self._metrics.span(
                    "cancel", trace_id=st.req.trace_id, rid=rid,
                    slot=slot, tokens=len(st.tokens))
                self._release(slot, self._slot_blocks.pop(slot, None))
                return True
        return False

    # -- KV block migration (ISSUE 17) -------------------------------------
    def _quant_name(self) -> Optional[str]:
        """The pool's QuantKV policy name (None = raw payload) — bundle
        compatibility is checked by NAME, the narrow form never
        converts."""
        for leaf in jax.tree_util.tree_leaves(
                self._state.caches,
                is_leaf=lambda v: isinstance(v, pk.PagedKV)):
            if isinstance(leaf, pk.PagedKV) and hasattr(leaf.kv, "q"):
                return ("int8" if str(leaf.kv.q.dtype) == "int8"
                        else "fp8")
        return None

    def extract_kv(self, rid):
        """Package an ACTIVE request's live KV into a sealed
        `kv_migration.KVBundle` (paged pools only; None = not
        extractable here, the caller falls back to re-prefill). Pure
        host/gather work at a turn boundary: the request's cache
        position, feed token, and remaining budget are all derivable
        from host state (``ctx = len(prefill) + len(tokens) - 1`` — the
        DecodeStep feed contract), so extraction never reads the decode
        state vectors. The source keeps serving until the caller
        cancels — extraction is a COPY, which is what makes the
        CRC-fail fallback safe."""
        if self._pool is None:
            return None
        for slot, st in self._active.items():
            if st.req.rid == rid:
                break
        else:
            return None
        from . import kv_migration as kvm

        req, k = st.req, len(st.tokens)
        budget_left = int(req.max_new_tokens) - k
        blocks = self._slot_blocks.get(slot)
        if not blocks or k < 1 or budget_left < 1:
            return None  # nothing left worth moving — finish in place
        ctx = int(req.prefill_ids.size) + k - 1
        n_used = pk.blocks_for(ctx, self.block_size)
        leaves = kvm.gather_leaves(self._state.caches,
                                   blocks[:n_used])
        bundle = kvm.KVBundle({
            "rid": req.rid, "trace_id": req.trace_id,
            "prompt_ids": [int(t) for t in req.prompt_ids],
            "resume": [int(t) for t in req.resume_tokens],
            "emitted": [int(t) for t in st.tokens],
            "ctx": ctx, "last_tok": int(st.tokens[-1]),
            "temperature": req.temperature, "top_k": req.top_k,
            "top_p": req.top_p, "eos_id": req.eos_id,
            "budget_left": budget_left,
            "block_size": self.block_size, "n_blocks": n_used,
            "quant": self._quant_name(),
            "adapter": int(getattr(req, "adapter", 0)),
        }, leaves).seal()
        self._metrics.span(
            "kv_extract", trace_id=req.trace_id, rid=rid, slot=slot,
            blocks=n_used, bytes=bundle.nbytes)
        return bundle

    def insert_migrated(self, req: Request, bundle) -> bool:
        """Splice a migrated bundle into a free slot and resume it
        mid-decode — the receive half of the migration plane. False =
        this engine cannot host the bundle (layout mismatch, no free
        slot, pool can't cover) and the caller degrades to re-prefill;
        True = the request decodes its NEXT token here with zero
        `PrefillStep` work. The slot's block budget covers the FULL
        remaining lifetime (``ctx + budget_left``), so the defrag-free
        append contract holds exactly as for a prefilled insert."""
        if self._pool is None:
            return False
        man = bundle.manifest
        ctx = int(man.get("ctx", 0))
        budget_left = int(man.get("budget_left", 0))
        if (int(man.get("block_size", -1)) != self.block_size
                or man.get("quant") != self._quant_name()
                or budget_left < 1
                or ctx + budget_left > self.max_length):
            return False
        aid = int(man.get("adapter", 0))
        if aid and (self.adapters is None
                    or not self.adapters.is_loaded(aid)):
            return False  # this engine can't serve the fine-tune
        n_pool_leaves = sum(
            1 for leaf in jax.tree_util.tree_leaves(
                self._state.caches,
                is_leaf=lambda v: isinstance(v, pk.PagedKV))
            if isinstance(leaf, pk.PagedKV))
        if len(bundle.leaves) != n_pool_leaves:
            return False
        free = [s for s in range(self.slots)
                if s not in self._active and s not in self._pending
                and s not in self._retiring]
        if not free:
            return False
        blocks = self._pool.alloc(
            pk.blocks_for(ctx + budget_left, self.block_size))
        if blocks is None:
            return False
        slot = free[0]
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        self._splice_bundle(slot, bundle, blocks)
        sl = _Slot(req, time.perf_counter(), 0.0, 0, ttft_ms=0.0)
        sl.tokens = []  # results carry only tokens emitted HERE; the
        #                 router owns prefix reassembly (round-15 rule)
        self._active[slot] = sl
        self._slot_blocks[slot] = blocks
        self._metrics.span(
            "kv_insert", trace_id=req.trace_id, rid=req.rid, slot=slot,
            blocks=bundle.n_blocks, bytes=bundle.nbytes, ctx=ctx)
        return True

    def _splice_bundle(self, slot, bundle, blocks) -> None:
        """The compiled gather-scatter insert (`jit.MigrateInsert`, the
        CacheInsert seam): zero-pad the bundle rows to the table width,
        re-layout them onto the pool's placement (the PR-11 device_put
        path — device-to-device when source and survivor share the
        process), and splice + reset the slot state in ONE program."""
        from ..distributed import resharding as rs
        from ..jit.decode_step import MigrateInsert

        man = bundle.manifest
        pool_leaves = [
            leaf for leaf in jax.tree_util.tree_leaves(
                self._state.caches,
                is_leaf=lambda v: isinstance(v, pk.PagedKV))
            if isinstance(leaf, pk.PagedKV)]
        rows = []
        for leaf, pool in zip(bundle.leaves, pool_leaves):
            padded = []
            for arr in leaf:
                full = np.zeros((self._nmax,) + tuple(arr.shape[1:]),
                                arr.dtype)
                full[: arr.shape[0]] = arr
                padded.append(full)
            target = getattr(pk._payload(pool.kv), "sharding", None)
            rows.append(tuple(rs.relayout_tree(padded, target)))
        row = np.zeros((self._nmax,), np.int32)
        row[: len(blocks)] = blocks  # trash-padded past the allocation
        if self._migrate is None:
            self._migrate = MigrateInsert()
        st = self._state
        (caches, pos, tok, done, temp, top_k, top_p, eos, budget,
         adapter) = self._migrate(
            st.caches, rows, jnp.asarray(slot, jnp.int32),
            jnp.asarray(row),
            st.pos, st.tok, st.done, st.temperature, st.top_k,
            st.top_p, st.eos, st.budget, st.adapter,
            jnp.asarray(int(man["ctx"]), jnp.int32),
            jnp.asarray(int(man["last_tok"]), jnp.int32),
            jnp.asarray(float(man["temperature"]), jnp.float32),
            jnp.asarray(int(man["top_k"]), jnp.int32),
            jnp.asarray(float(man["top_p"]), jnp.float32),
            jnp.asarray(int(man["eos_id"]), jnp.int32),
            jnp.asarray(int(man["budget_left"]), jnp.int32),
            jnp.asarray(int(man.get("adapter", 0)), jnp.int32))
        self._state = DecodeState(caches, pos, tok, done, st.key, temp,
                                  top_k, top_p, eos, budget, adapter)

    def _relocate_retiring(self) -> None:
        """Move ACTIVE requests off retiring top slots into free low
        slots through the migration plane, so `retire_slots` reclaim
        stops waiting on in-flight completion (ISSUE 17). Each move is
        extract -> splice-low -> release-high at a turn boundary; the
        pool transiently charges both allocations, so a pool too full
        to double-charge simply retries next turn (drain semantics are
        unchanged — nothing is ever cancelled)."""
        if self._pool is None or not self._retiring:
            return
        from . import kv_migration as kvm

        if not kvm.migrate_enabled():
            return
        for slot in sorted(self._retiring, reverse=True):
            st = self._active.get(slot)
            if st is None:
                continue  # free or pending-prefill: shrink/chunks handle it
            free = [s for s in range(self.slots)
                    if s < slot and s not in self._active
                    and s not in self._pending
                    and s not in self._retiring]
            if not free:
                continue
            bundle = self.extract_kv(st.req.rid)
            if bundle is None:
                continue  # e.g. one token from done: finish in place
            blocks = self._pool.alloc(pk.blocks_for(
                int(bundle.manifest["ctx"])
                + int(bundle.manifest["budget_left"]),
                self.block_size))
            if blocks is None:
                continue
            tgt = free[0]
            self._splice_bundle(tgt, bundle, blocks)
            self._active.pop(slot)
            self._state.done = self._state.done.at[slot].set(True)
            self._release(slot, self._slot_blocks.pop(slot, None))
            moved = _Slot(st.req, st.t_start, st.prefill_ms, 0,
                          st.ttft_ms)
            moved.tokens = list(st.tokens)  # same life, new slot
            self._active[tgt] = moved
            self._slot_blocks[tgt] = blocks
            self._metrics.span(
                "kv_relocate", trace_id=st.req.trace_id,
                rid=st.req.rid, from_slot=slot, to_slot=tgt,
                blocks=bundle.n_blocks, bytes=bundle.nbytes)

    def submit(self, req: Request) -> None:
        if req.prefill_ids.size + req.max_new_tokens > self.max_length:
            raise ValueError(
                f"request {req.rid}: prompt+resume "
                f"({req.prefill_ids.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds "
                f"max_length={self.max_length}")
        if self._pool is not None and \
                self.needed_blocks(req) > self._pool.total:
            raise ValueError(
                f"request {req.rid} needs {self.needed_blocks(req)} KV "
                f"blocks but the pool only has {self._pool.total} — it "
                f"can never be admitted")
        aid = int(getattr(req, "adapter", 0))
        if aid and (self.adapters is None
                    or not self.adapters.is_loaded(aid)):
            raise ValueError(
                f"request {req.rid} names adapter {aid} but "
                + ("no AdapterSet is attached to this engine's model"
                   if self.adapters is None else
                   f"only {self.adapters.resident} are resident"))
        req.t_submit = time.perf_counter()
        self._queue.append(req)

    def run(self) -> Dict[object, GeneratedResult]:
        """Drain the queue; returns rid -> GeneratedResult."""
        results: Dict[object, GeneratedResult] = {}
        while self.turn(results):
            pass
        return results

    def turn(self, results: Dict[object, GeneratedResult]) -> bool:
        """ONE scheduling turn: advance pending prefills by a chunk,
        fill free slots, run one decode window, collect its readback.
        Returns True while work remains (``run`` is just a turn loop).
        The incremental form is what a failover-capable host endpoint
        pumps (ISSUE 15): between turns every inflight request's
        emitted tokens sit in HOST state (:meth:`progress`), so a
        router can migrate them without touching the device."""
        if not (self._queue or self._active or self._pending):
            return False
        self._advance_prefills(results)
        progress = self._fill_free_slots(results)
        if not self._active:
            if not self._pending and not progress and self._queue:
                # nothing inflight and the head request can't start:
                # with a paged pool this would spin forever (blocks
                # can only come back from retiring work, and there
                # is none) — fail loudly instead
                req = self._queue[0]
                raise RuntimeError(
                    f"request {req.rid} cannot be admitted: needs "
                    f"{self.needed_blocks(req)} blocks, "
                    f"{self.free_blocks()} free, nothing inflight "
                    f"to free more")
            return bool(self._queue or self._active or self._pending)
        window = self._window()
        t0 = time.perf_counter()
        emits = []
        with _prof.phase("engine.decode_dispatch"):
            for _ in range(window):
                emit, _, self._state = self._decode(self._state)
                emits.append(emit)
        # THE readback: one stacked token transfer + the done mask
        # per window — the only recurring device->host reads in the
        # serving loop (decode_metrics rides exactly this cadence)
        with _prof.phase("engine.readback"):
            tok_block = np.asarray(jnp.stack(emits, axis=0))
            done = np.asarray(self._state.done)
            if self._expert_load is not None:
                # the device is already drained by the token read: the
                # counters ride it, no new sync point
                self._record_expert_load(self._expert_load())
            if self._selected_keys is not None:
                self._record_selected_keys(self._selected_keys())
        dt = time.perf_counter() - t0
        with _prof.phase("engine.collect"):
            # decode-window span for traced requests: emitted on the
            # SAME readback cadence (host values only, zero new reads)
            self._metrics.window_span(
                [s.req.trace_id for s in self._active.values()],
                steps=window)
            # which side of the sampler's branch this window's steps
            # took, by what the active requests asked for
            draws = any(s.req.temperature > 0
                        for s in self._active.values())
            self._record_sampler_steps(0 if draws else window,
                                       window if draws else 0)
            self._collect(tok_block, done, results)
        with _prof.phase("engine.turn_tail"):
            if self._retiring:
                # relocate in-flight work off the retiring tail first
                # (the ISSUE-17 fast path), THEN try the truncation it
                # unblocks
                self._relocate_retiring()
                self._maybe_shrink()  # a freed retiring tail truncates
            ttfts, self._ttft_window = self._ttft_window, []
            self._metrics.window(
                steps=window, tokens=int((tok_block >= 0).sum()),
                wall_s=dt, inflight=len(self._active),
                queue_depth=len(self._queue),
                ttft_ms=ttfts,
                blocks_in_use=(None if self._pool is None
                               else self._pool.in_use),
                blocks_total=(None if self._pool is None
                              else self._pool.total),
                blocks_freed=(None if self._pool is None
                              else self._pool.freed_total),
                admit_deferred=self._admit_deferred,
                prefix_hits=(None if self._prefix is None
                             else self._prefix_hits),
                prefix_blocks_shared=(None if self._prefix is None
                                      else self._prefix_blocks_shared),
                cow_copies=(None if self._prefix is None
                            else self._cow_copies),
                adapters_resident=(None if self.adapters is None
                                   else len(self.adapters.resident)))
        return bool(self._queue or self._active or self._pending)

    # -- internals ---------------------------------------------------------
    def _window(self) -> int:
        """Decode steps until the next readback — always the full sync
        cadence: per-slot budgets and stop tokens fold into the
        IN-GRAPH done mask (DecodeStep), so one nearly-finished request
        never drags the whole pool down to per-token readbacks; a done
        slot just emits the -1 sentinel until the window closes.
        Capacity needs no clamp either — submit() bounds every slot by
        prompt + max_new_tokens <= max_length."""
        return self.sync_every

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _slot_cache(self, req, slot):
        """A CONTIGUOUS batch-1 cache for one request's prefill (the
        pool may be paged; the splice re-blocks it), as raw arrays: the
        model's own `gen_cache` zeros, built by one compiled program
        (ledger label ``SlotCache``) instead of one eager launch a layer
        and tensor. Its outputs are committed where `_commit_tree` would
        put them, so the one-shot, chunked and shared-prefix admissions
        hand `PrefillStep` one signature."""
        with _prof.phase("engine.slot_cache", rid=req.rid, slot=slot):
            if self._slot_cache_jitted is None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ..distributed import comm as _comm
                from ..jit.decode_step import _raw_tree
                from ..observability import ledger as _ledger

                mesh = _comm.hybrid_mesh()
                # a program without inputs hands back uncommitted arrays
                # on a one-device mesh; on a real one they come back
                # committed, replicated or as `gen_cache` constrained them
                pin = (NamedSharding(mesh, P())
                       if mesh is not None and mesh.size == 1 else None)
                self._slot_cache_jitted = _ledger.jit(
                    lambda: _raw_tree(self.model.gen_cache(
                        1, self.max_length, block_size=0)),
                    "SlotCache", out_shardings=pin)
            return self._slot_cache_jitted()

    def _advance_prefills(self, results) -> None:
        """One chunk per pending prefill per engine turn: the chunked-
        prefill interleave that bounds how long a decode window can be
        delayed by somebody else's long prompt."""
        for slot in list(self._pending):
            job = self._pending[slot]
            C = self.prefill_chunk
            L = job.req.prefill_ids.size
            with _prof.phase("engine.prefill_chunk", rid=job.req.rid,
                             slot=slot):
                t0 = time.perf_counter()
                take = min(C, L - job.consumed)
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :take] = job.req.prefill_ids[
                    job.consumed: job.consumed + take]
                last, job.raws, _ = self._prefill(
                    job.raws, chunk, np.asarray([take], np.int32),
                    start=np.asarray([job.consumed], np.int32),
                    adapter=np.asarray([job.req.adapter], np.int32))
                job.consumed += take
                job.prefill_s += time.perf_counter() - t0
                self._metrics.span(
                    "prefill_chunk", trace_id=job.req.trace_id,
                    rid=job.req.rid, slot=slot, consumed=job.consumed,
                    prompt_len=L,
                    chunk_ms=round((time.perf_counter() - t0) * 1e3, 3))
            if job.consumed >= L:
                del self._pending[slot]
                self._activate(slot, job.req, job.raws, last,
                               blocks=job.blocks, t_enq=job.t0,
                               prefill_ms=job.prefill_s * 1e3,
                               results=results)

    def _fill_free_slots(self, results) -> bool:
        if not self._queue:
            return False
        progress = False
        free = [s for s in range(self.slots)
                if s not in self._active and s not in self._pending
                and s not in self._retiring]
        for slot in free:
            if not self._queue:
                break
            req = self._queue[0]
            blocks = None
            share = None
            with _prof.phase("engine.admit", rid=req.rid, slot=slot):
                if self._pool is not None:
                    # prefix-cache admission (ISSUE 18): a matched
                    # prefix is taken by table reference, so the pool is
                    # charged only the UNSHARED block demand; when even
                    # that can't be covered, idle cached entries are
                    # evicted before the request defers
                    if self._prefix is not None:
                        share = self._prefix.lookup(req.prefill_ids)
                    need = self.needed_blocks(req)
                    fresh_need = need - (0 if share is None
                                         else len(share.ref_blocks))
                    blocks = self._pool.alloc(fresh_need)
                    if blocks is None and self._prefix is not None:
                        self._prefix.evict_for(self._pool, fresh_need)
                        blocks = self._pool.alloc(fresh_need)
                    if blocks is None:
                        # pool can't cover the head request: DEFER
                        # admission (blocks come back when inflight work
                        # retires) — head-of-line on purpose: skipping
                        # ahead would starve long-context requests under
                        # load
                        self._admit_deferred += 1
                        break
                self._queue.popleft()
                progress = True
                self._metrics.span(
                    "admit", trace_id=req.trace_id, rid=req.rid,
                    slot=slot,
                    queue_wait_ms=(
                        round((time.perf_counter() - req.t_submit) * 1e3,
                              3)
                        if req.t_submit is not None else None))
            if share is not None:
                self._admit_shared(slot, req, share, blocks, results)
                continue
            L = req.prefill_ids.size
            if self.prefill_chunk > 0 and L > self.prefill_chunk:
                self._pending[slot] = _Pending(
                    req, slot, blocks, self._slot_cache(req, slot),
                    time.perf_counter())
                continue
            t0 = time.perf_counter()
            scratch = self._slot_cache(req, slot)
            with _prof.phase("engine.prefill", rid=req.rid, slot=slot):
                bucket = bucket_for(L, self.max_length)
                if self.prefill_chunk > 0:
                    # a prompt of one chunk or less pads at most to the
                    # chunk, the shape every chunk of a longer prompt runs,
                    # and not past the largest bucket to the capacity
                    bucket = min(bucket, self.prefill_chunk)
                ids, lens = _pad_prompts([req.prefill_ids], bucket)
                last, slot_raws, _ = self._prefill(
                    scratch, ids, lens,
                    adapter=np.asarray([req.adapter], np.int32))
            self._activate(slot, req, slot_raws, last, blocks=blocks,
                           t_enq=t0,
                           prefill_ms=(time.perf_counter() - t0) * 1e3,
                           results=results)
        return progress

    def _activate(self, slot, req, slot_raws, last, *, blocks, t_enq,
                  prefill_ms, results) -> None:
        """Sample the first token, splice the prefilled cache into the
        pool, and either park the request in its slot or (degenerate:
        eos/1-token budget) finish it immediately."""
        first = self._insert(slot, req, slot_raws, last, blocks)
        if self._prefix is not None and blocks is not None:
            # index the freshly prefilled prompt's full blocks BEFORE
            # any degenerate release — the index's own references keep
            # them resident for the next borrower either way
            self._prefix.publish(self._pool, req.prefill_ids, blocks)
        self._park_or_finish(slot, req, first, blocks, t_enq,
                             prefill_ms, results)

    def _admit_shared(self, slot, req, share, fresh, results) -> None:
        """Admit a request over a prefix-cache hit (ISSUE 18): take the
        matched blocks by table reference, materialize them into the
        batch-1 scratch (``PrefixFetch`` — the tail's attention needs
        the real prefix K/V), prefill ONLY the unshared tail in one
        shot, and splice with `paged_kv.paged_splice_tail` — which
        copies the one colliding shared block copy-on-write first when
        the match covered the whole prompt."""
        t0 = time.perf_counter()
        scratch = self._slot_cache(req, slot)
        with _prof.phase("engine.prefill", rid=req.rid, slot=slot):
            self._pool.ref(share.ref_blocks)
            cow = share.cow_src is not None
            table = list(share.ref_blocks) + list(fresh)
            cow_src = share.cow_src if cow else 0
            cow_dst = fresh[0] if cow else 0  # 0,0 = trash self-copy
            row = np.zeros((self._nmax,), np.int32)
            row[: len(table)] = table
            row_j = jnp.asarray(row)
            # the fetch reads the SOURCE chain (share.src_blocks) — the
            # slot's table row is NOT it: on a full-prefix match its last
            # shared logical block points at the private cow_dst, which
            # holds garbage until the splice runs
            srow = np.zeros((self._nmax,), np.int32)
            srow[: len(share.src_blocks)] = share.src_blocks
            raws = self._prefix_fetch(scratch, jnp.asarray(srow))
            L = req.prefill_ids.size
            tail_start = int(share.tail_start)
            tail_len = L - tail_start
            # the tail window writes start..start+W-1 and W must keep
            # the write INSIDE the cache — dynamic_update_slice would
            # clamp an overrunning start and silently trash prefix rows
            # the same call's attention reads. bucket_for against the
            # REMAINING capacity picks the smallest bucket that fits (or
            # exactly the remainder), so the tail always prefills in ONE
            # shot.
            W = bucket_for(tail_len, self.max_length - tail_start)
            ids = np.zeros((1, W), np.int32)
            ids[0, :tail_len] = req.prefill_ids[tail_start:]
            last, raws, _ = self._prefill(
                raws, ids, np.asarray([tail_len], np.int32),
                start=np.asarray([tail_start], np.int32),
                adapter=np.asarray([req.adapter], np.int32))
        first = self._prefix_insert(slot, req, raws, last, row_j,
                                    tail_start, L, cow_src, cow_dst)
        self._prefix_hits += 1
        self._prefix_blocks_shared += len(share.ref_blocks)
        if cow:
            self._cow_copies += 1
        self._metrics.span(
            "prefix_hit", trace_id=req.trace_id, rid=req.rid,
            slot=slot, shared_blocks=len(share.ref_blocks),
            cow=int(cow), tail_tokens=tail_len)
        # publishing after the splice touches the already-indexed chain
        # (LRU) and indexes any extra full blocks the tail introduced
        self._prefix.publish(self._pool, req.prefill_ids, table)
        self._park_or_finish(slot, req, first, table, t0,
                             (time.perf_counter() - t0) * 1e3, results)

    def _park_or_finish(self, slot, req, first, blocks, t_enq,
                        prefill_ms, results) -> None:
        now = time.perf_counter()
        ttft_ms = ((now - req.t_submit) * 1e3
                   if req.t_submit is not None else prefill_ms)
        self._ttft_window.append(ttft_ms)
        self._metrics.span(
            "prefill", trace_id=req.trace_id, rid=req.rid, slot=slot,
            prefill_ms=round(prefill_ms, 3), ttft_ms=round(ttft_ms, 3))
        if first == req.eos_id or req.max_new_tokens <= 1:
            # degenerate request: done at its first token
            results[req.rid] = GeneratedResult(
                req.rid, [first], prefill_ms, prefill_ms, ttft_ms)
            self._metrics.span(
                "retire", trace_id=req.trace_id, rid=req.rid,
                slot=slot, tokens=1)
            self._metrics.request_done(
                rid=req.rid, tokens=1, latency_ms=prefill_ms,
                prefill_ms=prefill_ms, ttft_ms=ttft_ms,
                trace_id=req.trace_id)
            self._state.done = self._state.done.at[slot].set(True)
            self._release(slot, blocks)
        else:
            if blocks is not None:
                self._slot_blocks[slot] = blocks
            self._active[slot] = _Slot(req, t_enq, prefill_ms, first,
                                       ttft_ms)

    def _release(self, slot, blocks) -> None:
        """Give a retired slot's blocks back and redirect its table
        rows to trash BEFORE the blocks can be reallocated — the done
        slot keeps issuing keep-alive writes at its frozen position."""
        if self._pool is None or blocks is None:
            return
        self._state.caches = pk.retire_tables(self._state.caches, slot)
        self._pool.release(blocks)

    def _first_token(self, slot, req, last):
        """Sample the request's first token from its last prefill
        logits (eager, on the device; `_read_first` brings it over)."""
        with _prof.phase("engine.first_token", rid=req.rid, slot=slot):
            sub = self._next_key()
            return sampling.sample(
                last, sub,
                jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                jnp.asarray([req.top_p], jnp.float32))

    def _read_first(self, slot, req, first) -> int:
        """The one blocking host read a request."""
        with _prof.phase("engine.first_token_read", rid=req.rid,
                         slot=slot):
            return int(np.asarray(first)[0])

    def _insert(self, slot: int, req: Request, slot_raws, last,
                blocks) -> int:
        """Splice one prefilled batch-1 cache into the pool slot.
        Returns its first generated token (the one per-request host
        read — per REQUEST, not per token)."""
        first = self._first_token(slot, req, last)
        with _prof.phase("engine.insert", rid=req.rid, slot=slot):
            if self._insert_jitted is None:
                from ..observability import ledger as _ledger

                donate = (0,)
                fn = _paged_insert_fn if self._pool is not None \
                    else _insert_fn
                self._insert_jitted = _ledger.jit(fn, "CacheInsert",
                                                  donate_argnums=donate)
            st = self._state
            L = req.prefill_ids.size
            extra = ()
            if self._pool is not None:
                row = np.zeros((self._nmax,), np.int32)
                row[: len(blocks)] = blocks  # trash-padded past allocation
                extra = (jnp.asarray(row),)
            (caches, pos, tok, done, temp, top_k, top_p, eos, budget,
             adapter) = self._insert_jitted(
                st.caches, slot_raws, jnp.asarray(slot, jnp.int32),
                *extra,
                st.pos, st.tok, st.done, st.temperature, st.top_k,
                st.top_p, st.eos, st.budget, st.adapter,
                jnp.asarray(L, jnp.int32),
                first[0],
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.top_k, jnp.int32),
                jnp.asarray(req.top_p, jnp.float32),
                jnp.asarray(req.eos_id, jnp.int32),
                jnp.asarray(req.max_new_tokens - 1, jnp.int32),
                jnp.asarray(req.adapter, jnp.int32))
            self._state = DecodeState(caches, pos, tok, done, st.key,
                                      temp, top_k, top_p, eos, budget,
                                      adapter)
        return self._read_first(slot, req, first)

    def _prefix_fetch(self, scratch, table_row):
        """Materialize the shared-prefix blocks named by ``table_row``
        into a contiguous batch-1 scratch (compiled gather, ledger
        label ``PrefixFetch``). The POOL is never donated — other
        slots are decoding out of it; only the scratch is consumed."""
        from ..jit.decode_step import _raw_tree

        raws = _raw_tree(scratch)
        if self._prefix_fetch_jitted is None:
            from ..observability import ledger as _ledger

            donate = (1,)
            self._prefix_fetch_jitted = _ledger.jit(
                _prefix_fetch_fn, "PrefixFetch", donate_argnums=donate)
        return self._prefix_fetch_jitted(self._state.caches, raws,
                                         table_row)

    def _prefix_insert(self, slot, req, slot_raws, last, table_row,
                       start, length, cow_src, cow_dst) -> int:
        """The shared-prefix CacheInsert: tail-only splice with the
        in-graph CoW copy (`paged_kv.paged_splice_tail`) — positions
        below ``start`` stay in the refcounted shared blocks the table
        row references."""
        first = self._first_token(slot, req, last)
        with _prof.phase("engine.insert", rid=req.rid, slot=slot):
            if self._prefix_insert_jitted is None:
                from ..observability import ledger as _ledger

                donate = (0,)
                self._prefix_insert_jitted = _ledger.jit(
                    _paged_prefix_insert_fn, "CacheInsert",
                    donate_argnums=donate)
            st = self._state
            (caches, pos, tok, done, temp, top_k, top_p, eos, budget,
             adapter) = self._prefix_insert_jitted(
                st.caches, slot_raws, jnp.asarray(slot, jnp.int32),
                table_row,
                jnp.asarray(start, jnp.int32),
                jnp.asarray(length, jnp.int32),
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32),
                st.pos, st.tok, st.done, st.temperature, st.top_k,
                st.top_p, st.eos, st.budget, st.adapter,
                first[0],
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.top_k, jnp.int32),
                jnp.asarray(req.top_p, jnp.float32),
                jnp.asarray(req.eos_id, jnp.int32),
                jnp.asarray(req.max_new_tokens - 1, jnp.int32),
                jnp.asarray(req.adapter, jnp.int32))
            self._state = DecodeState(caches, pos, tok, done, st.key,
                                      temp, top_k, top_p, eos, budget,
                                      adapter)
        return self._read_first(slot, req, first)

    def poison_prefix(self, k: Optional[int] = None) -> bool:
        """Corrupt the ``k``-th oldest prefix-cache entry's key (the
        ``serve:prefix_stale`` fault's bite, forwarded by the router) —
        the next lookup MISSES it and pays a full prefill; wrong-prefix
        KV is never served. No-op without a prefix cache."""
        return (False if self._prefix is None
                else self._prefix.poison(k))

    def _collect(self, tok_block, done, results) -> None:
        """Fold one readback window into per-request host state; retire
        finished slots (insert-on-free happens on the next loop turn).
        Stop conditions (eos, budget) already fired IN-GRAPH — a done
        slot emits the -1 sentinel, so collection is a sentinel scan."""
        finished = []
        for slot, st in self._active.items():
            for t in range(tok_block.shape[0]):
                tok = int(tok_block[t, slot])
                if tok < 0:   # sentinel: slot finished in-graph
                    break
                st.tokens.append(tok)
            if done[slot]:
                finished.append(slot)
        for slot in finished:
            st = self._active.pop(slot)
            total_ms = (time.perf_counter() - st.t_start) * 1e3
            results[st.req.rid] = GeneratedResult(
                st.req.rid, st.tokens, st.prefill_ms, total_ms,
                st.ttft_ms)
            self._metrics.span(
                "retire", trace_id=st.req.trace_id, rid=st.req.rid,
                slot=slot, tokens=len(st.tokens))
            self._metrics.request_done(
                rid=st.req.rid, tokens=len(st.tokens),
                latency_ms=total_ms, prefill_ms=st.prefill_ms,
                ttft_ms=st.ttft_ms, trace_id=st.req.trace_id)
            self._state.done = self._state.done.at[slot].set(True)
            self._release(slot, self._slot_blocks.pop(slot, None))


def _insert_fn(cache_raws, slot_raws, slot, pos, tok, done, temp, top_k,
               top_p, eos, budget, adapter, length, first_tok, t_val,
               k_val, p_val, e_val, b_val, a_val):
    """Compiled slot splice: write the batch-1 prefilled cache into the
    pool at `slot` (batch-dim dynamic_update_slice per leaf) and reset
    that slot's state-vector entries. `slot` rides as a traced scalar so
    every slot shares one compile."""
    def splice(batch_leaf, slot_leaf):
        return jax.lax.dynamic_update_slice_in_dim(
            batch_leaf, slot_leaf.astype(batch_leaf.dtype), slot, axis=0)

    caches = jax.tree_util.tree_map(splice, cache_raws, slot_raws)
    return (
        caches,
        pos.at[slot].set(length),
        tok.at[slot].set(first_tok),
        done.at[slot].set(False),
        temp.at[slot].set(t_val),
        top_k.at[slot].set(k_val),
        top_p.at[slot].set(p_val),
        eos.at[slot].set(e_val),
        budget.at[slot].set(b_val),
        adapter.at[slot].set(a_val),
    )


def _paged_insert_fn(cache_raws, slot_raws, slot, table_row, pos, tok,
                     done, temp, top_k, top_p, eos, budget, adapter,
                     length, first_tok, t_val, k_val, p_val, e_val,
                     b_val, a_val):
    """The paged CacheInsert: scatter the CONTIGUOUS batch-1 prefilled
    cache into the pool blocks named by ``table_row`` and point the
    slot's table at them (`paged_kv.paged_splice` — one scatter per
    leaf). ``slot`` AND ``table_row`` ride as traced values, so every
    slot and every allocation shape shares ONE compile; the state-vector
    resets are identical to the contiguous form."""
    def splice(paged_leaf, slot_subtree):
        return pk.paged_splice(paged_leaf, slot_subtree, slot,
                               table_row)

    caches = jax.tree_util.tree_map(
        splice, cache_raws, slot_raws,
        is_leaf=lambda v: isinstance(v, pk.PagedKV))
    return (
        caches,
        pos.at[slot].set(length),
        tok.at[slot].set(first_tok),
        done.at[slot].set(False),
        temp.at[slot].set(t_val),
        top_k.at[slot].set(k_val),
        top_p.at[slot].set(p_val),
        eos.at[slot].set(e_val),
        budget.at[slot].set(b_val),
        adapter.at[slot].set(a_val),
    )


def _prefix_fetch_fn(cache_raws, slot_raws, table_row):
    """Compiled shared-prefix gather (`paged_kv.paged_fetch` per
    `PagedKV` leaf): pool blocks named by ``table_row`` land in the
    contiguous batch-1 scratch so a tail prefill's attention reads the
    CACHED prefix K/V instead of garbage. The pool rides as a read-only
    input (never donated)."""
    def fetch(paged_leaf, slot_subtree):
        return pk.paged_fetch(paged_leaf, slot_subtree, table_row)

    return jax.tree_util.tree_map(
        fetch, cache_raws, slot_raws,
        is_leaf=lambda v: isinstance(v, pk.PagedKV))


def _paged_prefix_insert_fn(cache_raws, slot_raws, slot, table_row,
                            start, length, cow_src, cow_dst, pos, tok,
                            done, temp, top_k, top_p, eos, budget,
                            adapter, first_tok, t_val, k_val, p_val,
                            e_val, b_val, a_val):
    """CacheInsert, SHARED-PREFIX form: `paged_kv.paged_splice_tail`
    writes only positions ``start..length-1`` — everything below lives
    in refcounted blocks other slots also read — and runs the one
    copy-on-write block copy (``cow_src -> cow_dst``; the trash
    self-copy when no CoW is due) before the overlay. State resets
    match the other insert forms; every scalar rides traced so all
    shared admissions reuse one compile."""
    def splice(paged_leaf, slot_subtree):
        return pk.paged_splice_tail(paged_leaf, slot_subtree, slot,
                                    table_row, start, length, cow_src,
                                    cow_dst)

    caches = jax.tree_util.tree_map(
        splice, cache_raws, slot_raws,
        is_leaf=lambda v: isinstance(v, pk.PagedKV))
    return (
        caches,
        pos.at[slot].set(length),
        tok.at[slot].set(first_tok),
        done.at[slot].set(False),
        temp.at[slot].set(t_val),
        top_k.at[slot].set(k_val),
        top_p.at[slot].set(p_val),
        eos.at[slot].set(e_val),
        budget.at[slot].set(b_val),
        adapter.at[slot].set(a_val),
    )
