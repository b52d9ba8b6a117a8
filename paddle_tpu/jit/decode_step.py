"""Compiled single-token decode step + bucketed prefill (ISSUE 9).

`jit.DecodeStep` mirrors `jit.TrainStep`'s mechanics for the OTHER hot
loop: model forward (with the static-capacity KV-cache seam) + in-graph
sampling compiled as ONE XLA program per token, with

- **donated cache buffers** — the [B, H, cap, Dh] K/V caches are
  replaced every step (written in place at per-slot positions), so XLA
  updates them in HBM instead of copying; like TrainStep, donation is
  on for every backend (the CPU client honours it, so the CPU tests
  run the same aliasing the chip does);
- **recompile-ledger instrumentation** — the jitted step dispatches
  through `observability.ledger.instrument` (labels ``DecodeStep`` /
  ``PrefillStep``), so a shape wobble in the serving loop lands on the
  bus as a named `recompile` row and the "compiles once per bucket"
  contract is assertable;
- **mesh-aware routing** — params/caches are placement-normalized onto
  the hybrid mesh exactly like TrainStep (mixed placements re-trace the
  program once on the second call) and state outputs are pinned to
  their input shardings; the decode attention itself is plain XLA, so
  GSPMD partitions it over (dp -> batch, mp -> heads) with no seam.

The decode loop's state (`DecodeState`) is DEVICE-RESIDENT: tokens,
positions, done flags and the RNG key never visit the host between
steps — zero per-token host syncs by construction (the counted-transfer
test in tests/test_serving.py asserts it). Stop conditions are folded
into the graph: a slot whose sampled token hits its per-slot ``eos`` id
flips its ``done`` flag and emits the sentinel ``-1`` from then on; the
host reads tokens in one transfer at the end (or on the scheduler's
readback cadence).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import autograd as AG
from ..core.tensor import Tensor
from .functional_call import _swapped

__all__ = ["DecodeState", "DecodeStep", "PrefillStep", "MigrateInsert",
           "SpecDecodeState", "SpeculativeDecodeStep", "spec_k_default"]


def _raw_tree(tree):
    return jax.tree_util.tree_map(
        lambda v: v._data if isinstance(v, Tensor) else v, tree,
        is_leaf=lambda v: isinstance(v, Tensor),
    )


def _wrap_tree(tree):
    return jax.tree_util.tree_map(Tensor._wrap, tree)


def _commit_tree(tree):
    """Commit every eager-built (uncommitted) array in `tree` to a
    concrete placement — mesh-replicated on a real hybrid mesh, its
    current device otherwise. Loop-carried jit OUTPUTS are committed;
    without this the second call's input signature differs from the
    first and the whole step silently compiles twice (the TrainStep
    placement-churn lesson, decode edition — caught by the
    recompile-ledger 'compiles once' assert)."""
    from jax.sharding import NamedSharding, PartitionSpec as _P

    from ..distributed import comm as _comm

    mesh = _comm.hybrid_mesh()
    # replicate on the hybrid mesh even when TRIVIAL (size 1): GSPMD
    # normalizes the step's outputs onto that mesh's NamedSharding, so
    # SingleDeviceSharding inputs would still flip the signature once
    # (serving always runs under a declared mesh — the model ctor
    # installs one — so the TrainStep trivial-mesh/DataParallel-group
    # conflict does not arise here)
    target = NamedSharding(mesh, _P()) if mesh is not None else None

    def c(x):
        if not isinstance(x, jax.Array) or getattr(x, "_committed", True):
            return x
        return jax.device_put(x, target if target is not None
                              else x.sharding)

    return jax.tree_util.tree_map(c, tree)


def _pin(tree):
    """out_shardings pin: NamedSharding leaves keep their input layout
    (same contract as TrainStep — GSPMD-normalized outputs would change
    the second call's signature and re-trace the whole step)."""
    from jax.sharding import NamedSharding as _NS

    return jax.tree_util.tree_map(
        lambda r: r.sharding
        if isinstance(getattr(r, "sharding", None), _NS) else None,
        tree,
    )


#: effectively-unbounded per-slot step budget (the host loop bounds it)
NO_BUDGET = 1 << 30


class DecodeState:
    """Device-resident decode loop state. Every field is a jax array;
    the host holds only this container between steps.

    caches  : model KV-cache pytree (raw arrays, static shapes)
    pos     : [B] int32 — next write position per slot
    tok     : [B] int32 — token to feed the model this step
    done    : [B] bool  — slot finished (eos / budget / host-marked)
    key     : PRNG key threaded through the sampling ops
    temperature/top_k/top_p : [B] per-slot sampling params
    eos     : [B] int32 — stop token id per slot (-1 = none)
    budget  : [B] int32 — remaining decode STEPS per slot; like eos it
              folds into the in-graph done mask, so heterogeneous
              max_new_tokens never force the host loop below its sync
              cadence (NO_BUDGET = bounded by the host loop only)
    adapter : [B] int32 — per-slot adapter id (ISSUE 18 fleets; 0 =
              base model / identity delta). ALWAYS materialized (zeros
              when no fleet is attached) so the step keeps ONE jit
              signature regardless of adapter mix
    """

    FIELDS = ("caches", "pos", "tok", "done", "key", "temperature",
              "top_k", "top_p", "eos", "budget", "adapter")
    __slots__ = FIELDS

    def __init__(self, caches, pos, tok, done, key, temperature, top_k,
                 top_p, eos, budget, adapter=None):
        self.caches = caches
        self.pos = pos
        self.tok = tok
        self.done = done
        self.key = key
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos = eos
        self.budget = budget
        self.adapter = (adapter if adapter is not None
                        else jnp.zeros_like(pos))

    def astuple(self):
        return tuple(getattr(self, f) for f in self.FIELDS)

    @classmethod
    def make(cls, caches, first_tokens, pos, *, seed=0, temperature=0.0,
             top_k=0, top_p=1.0, eos_id=None, budget=None, adapter=0):
        """Build a fresh state from host values (one-time transfer).
        Scalars broadcast to per-slot [B] vectors. ``budget`` is the
        remaining step count per slot AFTER the first token (None =
        unbounded, the host loop terminates the decode)."""
        tok = jnp.asarray(first_tokens, jnp.int32)
        B = int(tok.shape[0])

        def vec(v, dtype):
            return jnp.broadcast_to(jnp.asarray(v, dtype), (B,))

        eos = -1 if eos_id is None else eos_id
        return cls(
            caches=_raw_tree(caches),
            pos=jnp.asarray(pos, jnp.int32),
            tok=tok,
            done=jnp.zeros((B,), bool),
            key=jax.random.PRNGKey(seed),
            temperature=vec(temperature, jnp.float32),
            top_k=vec(top_k, jnp.int32),
            top_p=vec(top_p, jnp.float32),
            eos=vec(eos, jnp.int32),
            budget=vec(NO_BUDGET if budget is None else budget,
                       jnp.int32),
            adapter=vec(adapter, jnp.int32),
        )


class _CompiledDecodeBase:
    """Shared TrainStep-style mechanics: placement normalization on the
    hybrid mesh, the pure model-forward segment, ledger-instrumented
    lazy jit."""

    _label = "DecodeStep"

    def __init__(self, model, *, donate: bool = True):
        self.model = model
        # params + ALL buffers thread into the jitted program as inputs —
        # for an int8-checkpointed model (ISSUE 19) that is the narrow
        # weight payloads (the params' raws) plus their non-persistable
        # `weight_q_scale` buffers, so the compiled decode streams
        # int8 + scales from HBM with no wiring beyond this collection
        self._p_objs = list(model.parameters())
        self._b_objs = list(dict(model.named_buffers()).values())
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from ..distributed import comm as _comm

        mesh = _comm.hybrid_mesh()
        if mesh is not None and mesh.size <= 1:
            mesh = None  # trivial mesh = no mesh for placement purposes
        if mesh is not None:
            repl = NamedSharding(mesh, _P())
            for o in self._p_objs + self._b_objs:
                if not isinstance(
                    getattr(o._data, "sharding", None), NamedSharding
                ):
                    o._data = jax.device_put(o._data, repl)
        # a buffer the forward replaces (a device counter) is loop-carried
        # like the state: commit it, or its second call flips the
        # signature and the step compiles twice
        for o in self._b_objs:
            o._data = _commit_tree(o._data)
        self._donate = donate
        # STATIC at construction (like the model objects themselves):
        # a model with an AdapterSet attached threads per-slot adapter
        # ids into its forward; without one the traced program is
        # byte-identical to the pre-adapter step (the bitwise
        # off-switch the round-18 acceptance demands)
        self._use_adapters = (
            getattr(model, "_serve_adapters", None) is not None)
        self._jitted = None
        self._carried_idx = ()
        self._n_steps = 0

    # -- the pure forward segment -----------------------------------------
    def _fwd_objs(self, model, p_objs, b_objs, p_raws, b_raws, ids,
                  cache_raws, pos, label=None, adapter=None, carried=None):
        """A model forward with the KV-cache seam as a pure function of
        (params, buffers, ids, caches, pos) -> (logits, new caches).
        Parameterized over the model so SpeculativeDecodeStep can run
        the draft AND the target inside one program. ``adapter`` ([B]
        int32 per-slot ids) is forwarded only when the model carries an
        AdapterSet — a bare model's call signature stays untouched.
        ``carried`` (a list) collects (buffer index, new raw) of every
        buffer the forward replaced — a device counter such as
        `nn.RoutedExperts.load`; a model that replaces none leaves it
        empty and its program as it was."""
        from .. import profiler as _prof

        objs = p_objs + b_objs
        caches = _wrap_tree(cache_raws)
        kw = {}
        if adapter is not None:
            kw["adapter"] = Tensor._wrap(adapter)
        with AG.trace_mode(), \
                _prof.device_annotation(
                    label or f"{self._label}::forward"), \
                _swapped(objs, list(p_raws) + list(b_raws)):
            out, new_caches = model(
                Tensor._wrap(ids), cache=caches, pos=Tensor._wrap(pos),
                **kw
            )
            logits = out._data if isinstance(out, Tensor) else out
            new_raws = _raw_tree(new_caches)
            if carried is not None:
                carried.extend(
                    (i, b._data) for i, (b, r) in
                    enumerate(zip(b_objs, b_raws)) if b._data is not r)
        return logits, new_raws

    def _fwd(self, p_raws, b_raws, ids, cache_raws, pos, adapter=None):
        """-> (logits, new caches, the replaced buffers' new values);
        which buffers those are is noted at trace time for `_rebind`."""
        carried = []
        logits, new_raws = self._fwd_objs(
            self.model, self._p_objs, self._b_objs, p_raws, b_raws, ids,
            cache_raws, pos, adapter=adapter, carried=carried)
        self._carried_idx = tuple(i for i, _ in carried)
        return logits, new_raws, tuple(r for _, r in carried)

    def _rebind(self, carried) -> None:
        """Hand the step's replaced buffers back to their objects."""
        for i, raw in zip(self._carried_idx, carried):
            self._b_objs[i]._data = raw

    def _instrumented(self, donate, out_shardings):
        from ..observability import ledger as _ledger

        return _ledger.jit(self._step_fn, self._label,
                           donate_argnums=donate,
                           out_shardings=out_shardings)

    @property
    def compiles(self) -> Optional[int]:
        """Ledger-observed compile count of this step (None before the
        first call) — the 'compiles once per bucket' assert reads it."""
        return None if self._jitted is None else self._jitted.compiles


class DecodeStep(_CompiledDecodeBase):
    """One compiled single-token step of the decode loop.

    Usage::

        step = paddle_tpu.jit.DecodeStep(model)
        state = DecodeState.make(model.gen_cache(B, cap), first, pos)
        emitted, logits, state = step(state)   # all device-side

    ``emitted`` is [B] int32 with ``-1`` for slots that were already
    done; ``logits`` is the [B, V] f32 pre-sampling distribution of this
    step (device array — read it only where a sync is acceptable).
    """

    _label = "DecodeStep"

    def _step_fn(self, p_raws, b_raws, cache_raws, pos, tok, done, key,
                 temp, top_k, top_p, eos, budget, adapter):
        from ..serving import sampling as _sampling

        logits, new_caches, carried = self._fwd(
            p_raws, b_raws, tok[:, None], cache_raws, pos,
            adapter=adapter if self._use_adapters else None,
        )
        last = logits[:, -1, :].astype(jnp.float32)
        key, sub = jax.random.split(key)
        from .. import profiler as _prof

        with _prof.device_annotation("DecodeStep::sample"):
            nxt = _sampling.sample(last, sub, temp, top_k, top_p)
        # this step's token spends one unit of the slot's budget; both
        # stop conditions fold into the done mask IN-GRAPH so the host
        # loop never has to shrink its readback window below sync_every
        new_budget = budget - jnp.where(done, 0, 1).astype(budget.dtype)
        new_done = done | (nxt == eos) | (new_budget <= 0)
        emit = jnp.where(done, jnp.int32(-1), nxt)
        # done slots keep feeding token 0 at a frozen position: their
        # cache writes land on the same already-dead row
        feed = jnp.where(new_done, jnp.int32(0), nxt)
        new_pos = pos + jnp.where(done, 0, 1).astype(pos.dtype)
        return emit, last, (new_caches, new_pos, feed, new_done, key,
                            new_budget), carried

    def __call__(self, state: DecodeState):
        # commit EVERY call, not just the first: a fresh generate()
        # restarts from eager-built (uncommitted) arrays and would
        # otherwise re-trace once per loop; on the steady state this is
        # a no-op attribute walk over ~a dozen arrays
        state = DecodeState(*_commit_tree(state.astuple()))
        args = (
            tuple(p._data for p in self._p_objs),
            tuple(b._data for b in self._b_objs),
            state.caches, state.pos, state.tok, state.done, state.key,
            state.temperature, state.top_k, state.top_p, state.eos,
            state.budget, state.adapter,
        )
        if self._jitted is None:
            donate = (2,) if self._donate else ()
            # EVERY loop-carried output pins to its input sharding —
            # with a dp-sharded cache GSPMD would otherwise flip the
            # small vectors (tok/done/budget) to dp-sharded outputs and
            # the second call's signature would re-trace the step
            out_sh = (
                None,                       # emitted tokens
                None,                       # step logits
                (_pin(state.caches), _pin(state.pos), _pin(state.tok),
                 _pin(state.done), _pin(state.key), _pin(state.budget)),
                None,                       # buffers the forward replaced
            )
            self._jitted = self._instrumented(donate, out_sh)
        self._n_steps += 1
        emit, logits, (caches, pos, tok, done, key, budget), carried = \
            self._jitted(*args)
        self._rebind(carried)
        new_state = DecodeState(
            caches, pos, tok, done, key, state.temperature, state.top_k,
            state.top_p, state.eos, budget, state.adapter,
        )
        return emit, logits, new_state


class PrefillStep(_CompiledDecodeBase):
    """Bucketed compiled prefill: right-padded [B, L] prompt ids write
    their K/V rows into the static cache at positions 0..len-1 and the
    last REAL token's logits come back per row (the first sampling
    input). One compile per (B, L) bucket shape — jit caches by shape,
    so a single instance serves every bucket and the ledger counts the
    per-bucket compiles under ``PrefillStep``.

    Padding rows write garbage K/V at positions len..L-1; the decode
    masks every position > pos AND overwrites position p on the very
    step whose query sits at p (write-then-attend), so a stale row is
    never read.

    Round 13 (chunked prefill): ``start`` ([B] int32, default zeros)
    writes the chunk at positions start..start+len-1 instead of 0 —
    the prefill-with-history continuation the engine interleaves with
    decode windows. ``start`` is a traced argument of the SAME program
    (zeros for a whole-prompt prefill), so chunking adds no compiles
    beyond the chunk shape itself.
    """

    _label = "PrefillStep"

    def _step_fn(self, p_raws, b_raws, cache_raws, ids, length, start,
                 adapter):
        logits, new_caches, carried = self._fwd(
            p_raws, b_raws, ids, cache_raws,
            jnp.asarray(start, jnp.int32),
            adapter=adapter if self._use_adapters else None,
        )
        idx = jnp.clip(length - 1, 0, ids.shape[1] - 1)
        last = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1
        )[:, 0, :].astype(jnp.float32)
        return (last, new_caches, jnp.asarray(start + length, jnp.int32),
                carried)

    def __call__(self, caches, ids, lengths, start=None, adapter=None):
        """-> (last_logits [B, V] f32, new cache pytree, pos [B]).
        ``last_logits`` are the logits of the last REAL token of this
        chunk; ``pos`` = start + lengths (the next write position).
        ``adapter`` — per-row adapter ids (default all-zeros = base)."""
        cache_raws = _raw_tree(caches)
        ids = jnp.asarray(ids, jnp.int32)
        if start is None:
            start = jnp.zeros((int(ids.shape[0]),), jnp.int32)
        if adapter is None:
            adapter = jnp.zeros((int(ids.shape[0]),), jnp.int32)
        args = (
            tuple(p._data for p in self._p_objs),
            tuple(b._data for b in self._b_objs),
            cache_raws,
            ids,
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(adapter, jnp.int32),
        )
        if self._jitted is None:
            donate = (2,) if self._donate else ()
            out_sh = (None, _pin(cache_raws), None, None)
            self._jitted = self._instrumented(donate, out_sh)
        self._n_steps += 1
        last, new_caches, pos, carried = self._jitted(*args)
        self._rebind(carried)
        return last, new_caches, pos


class MigrateInsert:
    """Compiled insert-WITH-HISTORY (ISSUE 17): splice a migrated KV
    bundle's gathered block rows into a paged pool slot and reset that
    slot's decode-state entries to the SOURCE's mid-decode values — the
    `CacheInsert` seam's third form, next to the engine's contiguous and
    paged prefill splices (same ledger label, so the recompile contract
    covers it).

    Where `CacheInsert` writes a freshly PREFILLED batch-1 cache at
    position 0 with a first sampled token, this writes a cache with
    ``ctx`` rows of decode HISTORY already in it and resumes feeding the
    source's last emitted token at position ``ctx`` — the survivor's
    very next `DecodeStep` continues the sequence as if the request had
    never moved (zero `PrefillStep` invocations; the parity tests assert
    token-exactness against an uninterrupted run).

    ``rows`` is a flat list over the cache pytree's `PagedKV` leaves
    (tree_flatten order), each entry the bundle's zero-padded
    ``[nmax, H, bs, rest]`` stack — a bare payload tuple or a
    (payload, scales) pair for QuantKV pools, adopted NARROW
    (`paged_kv.paged_adopt`). ``slot``/``table_row`` and every state
    scalar ride traced, so ALL migrations into an engine share one
    compile."""

    _label = "CacheInsert"

    def __init__(self, *, donate: bool = True):
        self._donate = donate
        self._jitted = None
        self._carried_idx = ()
        self._n_steps = 0

    def _step_fn(self, cache_raws, rows, slot, table_row, pos, tok,
                 done, temp, top_k, top_p, eos, budget, adapter, ctx,
                 last_tok, t_val, k_val, p_val, e_val, b_val, a_val):
        from ..serving import paged_kv as pk

        flat, treedef = jax.tree_util.tree_flatten(
            cache_raws, is_leaf=lambda v: isinstance(v, pk.PagedKV))
        it = iter(rows)
        out = [pk.paged_adopt(leaf, next(it), slot, table_row)
               if isinstance(leaf, pk.PagedKV) else leaf
               for leaf in flat]
        caches = jax.tree_util.tree_unflatten(treedef, out)
        return (
            caches,
            pos.at[slot].set(ctx),
            tok.at[slot].set(last_tok),
            done.at[slot].set(False),
            temp.at[slot].set(t_val),
            top_k.at[slot].set(k_val),
            top_p.at[slot].set(p_val),
            eos.at[slot].set(e_val),
            budget.at[slot].set(b_val),
            adapter.at[slot].set(a_val),
        )

    @property
    def compiles(self) -> Optional[int]:
        return None if self._jitted is None else self._jitted.compiles

    def __call__(self, cache_raws, rows, slot, table_row, pos, tok,
                 done, temp, top_k, top_p, eos, budget, adapter, ctx,
                 last_tok, t_val, k_val, p_val, e_val, b_val, a_val):
        if self._jitted is None:
            from ..observability import ledger as _ledger

            donate = (0,) if self._donate else ()
            self._jitted = _ledger.jit(self._step_fn, self._label,
                                       donate_argnums=donate)
        self._n_steps += 1
        return self._jitted(cache_raws, rows, slot, table_row, pos, tok,
                            done, temp, top_k, top_p, eos, budget,
                            adapter, ctx, last_tok, t_val, k_val, p_val,
                            e_val, b_val, a_val)


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 13 tentpole c)
# ---------------------------------------------------------------------------


def spec_k_default() -> int:
    """``PADDLE_SERVE_SPEC_K`` — tokens the draft model proposes per
    speculative round (default 4)."""
    import os

    try:
        return max(int(os.environ.get("PADDLE_SERVE_SPEC_K", "4")), 1)
    except ValueError:
        return 4


class SpecDecodeState:
    """Device-resident loop state of the speculative decode: the target
    model's caches AND the draft model's caches ride together (both
    position-synced to the accepted sequence), plus the usual per-slot
    vectors. Greedy-only — the accept rule compares the draft's argmax
    against the target's argmax, which is what makes the output
    TOKEN-EXACT vs the non-speculative DecodeStep (the acceptance
    contract); sampled slots take the plain DecodeStep."""

    FIELDS = ("caches", "draft_caches", "pos", "tok", "done", "eos",
              "budget")
    __slots__ = FIELDS

    def __init__(self, caches, draft_caches, pos, tok, done, eos,
                 budget):
        self.caches = caches
        self.draft_caches = draft_caches
        self.pos = pos
        self.tok = tok
        self.done = done
        self.eos = eos
        self.budget = budget

    def astuple(self):
        return tuple(getattr(self, f) for f in self.FIELDS)

    @classmethod
    def make(cls, caches, draft_caches, first_tokens, pos, *,
             eos_id=None, budget=None):
        tok = jnp.asarray(first_tokens, jnp.int32)
        B = int(tok.shape[0])

        def vec(v, dtype):
            return jnp.broadcast_to(jnp.asarray(v, dtype), (B,))

        eos = -1 if eos_id is None else eos_id
        return cls(
            caches=_raw_tree(caches),
            draft_caches=_raw_tree(draft_caches),
            pos=jnp.asarray(pos, jnp.int32),
            tok=tok,
            done=jnp.zeros((B,), bool),
            eos=vec(eos, jnp.int32),
            budget=vec(NO_BUDGET if budget is None else budget,
                       jnp.int32),
        )


class SpeculativeDecodeStep(_CompiledDecodeBase):
    """One compiled speculative round: the DRAFT model proposes ``k``
    tokens autoregressively (k unrolled single-token forwards inside
    THIS program), the TARGET model scores all ``k+1`` inputs in one
    forward, and the accept/reject fold happens IN-GRAPH — the host
    never sees a drafted token, so the device->host transfer count is
    independent of ``k`` and of how many drafts survive (the DecodeStep
    contract, extended).

    Greedy acceptance: drafted token ``d_i`` survives while every
    earlier draft matched the target's argmax; the round emits the
    target's own argmax at each surviving position plus its correction
    at the first mismatch — by construction EXACTLY the token sequence
    the non-speculative greedy DecodeStep emits, just 1..k+1 tokens per
    program dispatch instead of 1 (the acceptance-rate win PERF.md
    round-13 prices). ``emitted`` comes back as [B, k+1] with ``-1``
    sentinels past each slot's accepted count (and everywhere for done
    slots) — the engine/generate readback compacts them exactly like
    the windowed non-speculative sentinels.

    Capacity contract: each round writes ``k+1`` rows at pos..pos+k
    (rejected rows are overwritten before they can ever be attended —
    the same write-then-attend invariant PrefillStep's padding relies
    on), so caches need ``k`` rows of headroom past the last real
    token. ``generate()``/the engine reserve it.
    """

    _label = "SpeculativeDecodeStep"

    def __init__(self, model, draft_model, *, k=None, donate=True):
        super().__init__(model, donate=donate)
        self.draft_model = draft_model
        self.k = int(k) if k is not None else spec_k_default()
        if self.k < 1:
            # the env path clamps to >= 1 (spec_k_default); the explicit
            # path must not crash obscurely inside jnp.stack at trace
            raise ValueError(
                f"SpeculativeDecodeStep needs k >= 1 draft tokens per "
                f"round (got {self.k})")
        self._dp_objs = list(draft_model.parameters())
        self._db_objs = list(
            dict(draft_model.named_buffers()).values())
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from ..distributed import comm as _comm

        mesh = _comm.hybrid_mesh()
        if mesh is not None and mesh.size > 1:
            repl = NamedSharding(mesh, _P())
            for o in self._dp_objs + self._db_objs:
                if not isinstance(
                    getattr(o._data, "sharding", None), NamedSharding
                ):
                    o._data = jax.device_put(o._data, repl)

    def _step_fn(self, p_raws, b_raws, dp_raws, db_raws, cache_raws,
                 dcache_raws, pos, tok, done, eos, budget):
        K = self.k
        # -- draft: K unrolled single-token greedy forwards ------------
        cur, dc = tok, dcache_raws
        drafts = []
        for i in range(K):
            dlogits, dc = self._fwd_objs(
                self.draft_model, self._dp_objs, self._db_objs,
                dp_raws, db_raws, cur[:, None], dc, pos + i,
                label="SpeculativeDecodeStep::draft",
            )
            cur = jnp.argmax(
                dlogits[:, -1, :].astype(jnp.float32), -1
            ).astype(jnp.int32)
            drafts.append(cur)
        drafts = jnp.stack(drafts, axis=1)  # [B, K]
        # -- target: ONE forward over all K+1 inputs -------------------
        inputs = jnp.concatenate([tok[:, None], drafts], axis=1)
        # (a speculative step hands no replaced buffer on: its models
        # carry no device counter)
        tlogits, new_caches, _ = self._fwd(
            p_raws, b_raws, inputs, cache_raws, pos
        )
        g = jnp.argmax(
            tlogits.astype(jnp.float32), -1
        ).astype(jnp.int32)  # [B, K+1] target greedy at each position
        # -- in-graph accept/reject ------------------------------------
        # d_i survives while every draft before it (and itself) matched
        # the target's argmax; the emitted tokens are the target's own
        # choices g_1..g_{n+1}, so equality with non-speculative greedy
        # is by construction, not by luck
        match = (drafts == g[:, :K]).astype(jnp.int32)
        n_acc = jnp.cumprod(match, axis=1).sum(axis=1)  # [B] 0..K
        n_emit = jnp.minimum(n_acc + 1, jnp.maximum(budget, 0))
        n_emit = jnp.where(done, 0, n_emit)
        j = jnp.arange(K + 1, dtype=jnp.int32)
        base = j[None, :] < n_emit[:, None]
        eos_hit = base & (g == eos[:, None])
        first_eos = jnp.where(
            eos_hit.any(axis=1), jnp.argmax(eos_hit, axis=1),
            jnp.int32(K + 1))
        emit_mask = base & (j[None, :] <= first_eos[:, None])
        emit = jnp.where(emit_mask, g, jnp.int32(-1))
        n_final = emit_mask.sum(axis=1).astype(pos.dtype)
        new_pos = pos + n_final
        new_budget = budget - n_final.astype(budget.dtype)
        new_done = done | eos_hit.any(axis=1) | (new_budget <= 0)
        last_idx = jnp.clip(n_final - 1, 0, K)
        feed = jnp.take_along_axis(g, last_idx[:, None], axis=1)[:, 0]
        feed = jnp.where(new_done, jnp.int32(0), feed)
        return emit, (new_caches, dc, new_pos, feed, new_done,
                      new_budget)

    def __call__(self, state: SpecDecodeState):
        """-> (emitted [B, k+1] int32 with -1 sentinels, new state)."""
        state = SpecDecodeState(*_commit_tree(state.astuple()))
        args = (
            tuple(p._data for p in self._p_objs),
            tuple(b._data for b in self._b_objs),
            tuple(p._data for p in self._dp_objs),
            tuple(b._data for b in self._db_objs),
            state.caches, state.draft_caches, state.pos, state.tok,
            state.done, state.eos, state.budget,
        )
        if self._jitted is None:
            donate = (4, 5) if self._donate else ()
            out_sh = (
                None,
                (_pin(state.caches), _pin(state.draft_caches),
                 _pin(state.pos), _pin(state.tok), _pin(state.done),
                 _pin(state.budget)),
            )
            self._jitted = self._instrumented(donate, out_sh)
        self._n_steps += 1
        emit, (caches, dcaches, pos, tok, done, budget) = \
            self._jitted(*args)
        return emit, SpecDecodeState(caches, dcaches, pos, tok, done,
                                     state.eos, budget)
