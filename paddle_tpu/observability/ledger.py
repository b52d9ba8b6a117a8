"""Compile ledger — every jit cache miss and every compile stage, on record.

A silent recompile is the classic TPU training-loop performance cliff:
an input whose shape/dtype wobbles per step (a last partial batch, a
python float that flips between int and float, a donation change) turns
the "compiled once" hot path into a compile-per-step crawl, and nothing
in the runtime says so. The reference framework's executor cache logs
its misses; jax's is invisible by default.

The ledger instruments OUR compiled entry points (``jit.TrainStep``,
``LocalSGDStep``, anything wrapped with :func:`instrument`):

- cache misses are detected by the jitted callable's ``_cache_size()``
  delta across a call — a per-call integer compare, nothing on the hit
  path (fallback when the attribute is missing: fingerprint compare,
  paid per call);
- each miss emits a ``recompile`` row carrying the call's **argument
  fingerprint** (per-leaf ``dtype[shape]`` strings + the donation
  config), the wall seconds the compiling call took, and the per-label
  compile ordinal;
- a **storm detector** compares consecutive fingerprints: from the
  ``PADDLE_OBS_STORM_N``-th compile of one label (default 3) it emits
  ``recompile_storm`` NAMING the fingerprint field that keeps changing
  (``args[3].shape: f32[32,128] -> f32[33,128]``) — the answer to "why
  is every step compiling", read straight off the bus.

``compile_count()`` is the process-wide miss total.
``compile_seconds()`` is the wall time of those compiling calls, summed:
the part of a process's set-up that went into compiling its steps.

**Compile stages.** One ``jax.monitoring`` listener, installed when this
module is imported (with ``paddle_tpu``), records every program jax
makes, eager operations and unledgered jits included, as
``(stage, start_ns, end_ns, label)`` on ``time.perf_counter_ns()``:

- ``trace``: ``/jax/core/compile/jaxpr_trace_duration`` (a function to
  its jaxpr; a jit called inside another's trace nests in its parent's);
- ``lower``: ``/jax/core/compile/jaxpr_to_mlir_module_duration`` (the
  jaxpr to an MLIR module, Pallas kernels through Mosaic included);
- ``xla``: ``/jax/core/compile/backend_compile_duration`` of a program
  the backend compiled (the persistent cache's key, lookup and write
  inside it);
- ``cache_read``: ``/jax/compilation_cache/cache_retrieval_time_sec``,
  and the ``backend_compile_duration`` that wraps it — a program the
  persistent cache served is read and loaded, not compiled;
- ``cache_request``, ``cache_hit``, ``cache_miss``: the persistent
  cache's counts (``compile_requests_use_cache``, ``cache_hits``,
  ``cache_misses``), as records of no length when each fired.

A record ends when its event fires and starts the event's seconds
earlier. Its ``label`` is the ledger label of the compiling call that
contains it (the innermost), set when that call returns; ``None`` for
everything outside one. Nested records overlap, so a stage's seconds
are the union of its intervals, never their sum. A record takes in the
newest unlabelled records of its stage that lie inside it, and a
lowering the traces inside it: the union of each other stage, and that
of ``trace`` with ``lower``, stay as they were. The records live in a
deque of the last :data:`STAGE_RECORDS_MAX`; read them with
:func:`compile_stages`. After warm-up no compile event fires, so the hot
path pays nothing. With the bus on, the listener also writes a
``backend_compile`` row for each backend event.

:func:`jit` is the one way a compiled step is made: it names the function
by its ledger label before jitting it, so the label on a ``recompile``
row and the module a device trace shows (``jit_<label>``) are one string.
"""
from __future__ import annotations

import collections
import functools
import os
import threading
import time
from typing import List, Optional, Tuple

from . import bus

__all__ = [
    "arg_fingerprint", "diff_fingerprints", "instrument", "jit",
    "LedgeredFunction", "compile_count", "compile_seconds",
    "compile_stages", "STAGE_RECORDS_MAX", "reset",
]

_STORM_ENV = "PADDLE_OBS_STORM_N"

_total_compiles = 0
_total_compile_s = 0.0

#: the compile records kept, newest last; a set-up leaves a few hundred
STAGE_RECORDS_MAX = 1 << 16
_stages: collections.deque = collections.deque(maxlen=STAGE_RECORDS_MAX)
_stages_lock = threading.Lock()


def compile_count() -> int:
    """Process-wide jit cache misses observed by instrumented wrappers."""
    return _total_compiles


def compile_seconds() -> float:
    """Process-wide wall seconds of the calls :func:`compile_count`
    counts (trace + lower + compile + that call's dispatch)."""
    return _total_compile_s


def compile_stages(until_ns: Optional[int] = None
                   ) -> List[Tuple[str, int, int, Optional[str]]]:
    """The compile records, oldest first, as ``(stage, start_ns, end_ns,
    label)``; with ``until_ns`` only those that started before it."""
    with _stages_lock:
        recs = [tuple(r) for r in _stages]
    if until_ns is not None:
        recs = [r for r in recs if r[1] < until_ns]
    return recs


def reset() -> None:
    """Tests: zero the process-wide counters and drop the records."""
    global _total_compiles, _total_compile_s
    _total_compiles = 0
    _total_compile_s = 0.0
    with _stages_lock:
        _stages.clear()


def _leaf_sig(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        # static (weak-typed python scalar / None / config): the VALUE
        # is part of the jit cache key, so it belongs in the fingerprint
        return f"static:{type(x).__name__}:{x!r}"
    return f"{dtype}[{','.join(str(int(d)) for d in shape)}]"


def arg_fingerprint(args, kwargs=None) -> List[Tuple[str, str]]:
    """Flat ``(path, sig)`` list over the call's leaves — the shape/dtype
    identity jit keys on, in a diffable form."""
    import jax

    out: List[Tuple[str, str]] = []
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves_with_path(a)
        if not leaves and a is not None:
            out.append((f"args[{i}]", _leaf_sig(a)))
        for path, leaf in leaves:
            key = f"args[{i}]" + jax.tree_util.keystr(path)
            out.append((key, _leaf_sig(leaf)))
    for k, v in sorted((kwargs or {}).items()):
        for path, leaf in jax.tree_util.tree_leaves_with_path(v):
            out.append((f"{k}{jax.tree_util.keystr(path)}",
                        _leaf_sig(leaf)))
    return out


def diff_fingerprints(prev, cur) -> List[str]:
    """Human lines naming what changed between two fingerprints."""
    pd, cd = dict(prev), dict(cur)
    lines = []
    for key in sorted(set(pd) | set(cd)):
        a, b = pd.get(key), cd.get(key)
        if a == b:
            continue
        if a is None:
            lines.append(f"{key}: (new) {b}")
        elif b is None:
            lines.append(f"{key}: {a} (gone)")
        else:
            lines.append(f"{key}: {a} -> {b}")
    return lines


class LedgeredFunction:
    """Callable wrapper around one jitted function; transparent on the
    cache-hit path (one int compare + one perf_counter pair)."""

    def __init__(self, jitted, label: str, donate=()):
        self._jitted = jitted
        self.label = label
        self._donate = tuple(donate)
        self._storm_n = max(int(os.environ.get(_STORM_ENV, "3") or 3), 2)
        self._prev_fp: Optional[List[Tuple[str, str]]] = None
        # fallback-path cache mirror: signatures already compiled. jit's
        # cache holds EVERY past signature, so "differs from the
        # previous call" is not "miss" — an A,B,A,B shape alternation
        # after two real compiles is all hits
        self._seen: set = set()
        self.compiles = 0

    # lowering stays reachable through the wrapper: chip_smoke.py counts
    # the Mosaic calls of a lowered TrainStep, tests/test_trace_names.py
    # reads the module's name from the lowered text
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def _cache_size(self) -> Optional[int]:
        fn = getattr(self._jitted, "_cache_size", None)
        if fn is None:
            return None
        try:
            return int(fn())
        except Exception:  # noqa: BLE001
            return None

    def __call__(self, *args, **kwargs):
        n0 = self._cache_size()
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        wall = time.perf_counter() - t0
        n1 = self._cache_size()
        if n0 is not None and n1 is not None:
            missed = n1 > n0
            # fingerprint only on a miss: the hit path stays free
            fp = arg_fingerprint(args, kwargs) if missed else None
        else:
            # no cache introspection on this jax: fingerprint every call
            # and mirror the jit cache — a signature seen before is a hit
            fp = arg_fingerprint(args, kwargs)
            key = tuple(fp)
            missed = key not in self._seen
            self._seen.add(key)
        if missed:
            self._on_compile(fp, t0, wall)
        if fp is not None:
            self._prev_fp = fp
        return out

    def _on_compile(self, fp, t0: float, wall_s: float) -> None:
        global _total_compiles, _total_compile_s
        self.compiles += 1
        _total_compiles += 1
        _total_compile_s += wall_s
        _label_since(int(t0 * 1e9), self.label)
        changed = (diff_fingerprints(self._prev_fp, fp)
                   if self._prev_fp is not None and fp is not None else [])
        if bus.enabled():
            bus.emit("recompile", {
                "label": self.label,
                "ordinal": self.compiles,
                "compile_wall_s": round(wall_s, 3),
                "donate_argnums": list(self._donate),
                "fingerprint": [list(kv) for kv in (fp or [])],
                "changed": changed,
            })
            if self.compiles >= self._storm_n and changed:
                bus.emit("recompile_storm", {
                    "label": self.label,
                    "compiles": self.compiles,
                    "changing_fields": changed[:8],
                    "detail": (
                        f"{self.label} compiled {self.compiles}x — the "
                        f"argument signature keeps changing: "
                        + "; ".join(changed[:3])
                    ),
                })


def instrument(jitted, label: str, donate=()) -> LedgeredFunction:
    """Wrap one jitted callable so its cache misses feed the ledger."""
    return LedgeredFunction(jitted, label, donate)


def jit(fn, label: str, *, donate_argnums=(),
        **jit_kwargs) -> LedgeredFunction:
    """``jax.jit`` of ``fn`` under the name ``label``, instrumented: the
    compiled module is ``jit_<label>`` in HLO text and in a device trace,
    and its cache misses are ``recompile`` rows with the same label.
    Keywords are ``jax.jit``'s own."""
    import jax

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = label
    return instrument(
        jax.jit(named, donate_argnums=donate_argnums, **jit_kwargs),
        label=label, donate=donate_argnums)


_DURATION_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_COUNT_STAGES = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
_ABSORBS = {"lower": ("lower", "trace")}
# a cache hit fires inside the backend_compile_duration that wraps it,
# in the same thread: that wrapper then read a program, it compiled none
_served = threading.local()


def _on_duration(event: str, secs: float, **kw) -> None:
    stage = _DURATION_STAGES.get(event)
    if stage is None:
        return
    end = time.perf_counter_ns()
    if stage == "xla":
        if getattr(_served, "hit", False):
            _served.hit = False
            stage = "cache_read"
        if bus.enabled():
            bus.emit("backend_compile", {
                "key": event, "seconds": round(float(secs), 3)})
    start = end - int(secs * 1e9)
    absorbs = _ABSORBS.get(stage, (stage,))
    with _stages_lock:
        # a record absorbs the newest unlabelled records inside it of its
        # own stage, or traces inside a lowering (the jnp functions traced
        # inside a jit's trace or as a primitive is lowered, the read
        # inside the backend step of a hit): the union of trace and lower
        # is the same, and a step keeps a few records, not thousands
        while (_stages and _stages[-1][0] in absorbs
               and _stages[-1][1] >= start and _stages[-1][3] is None):
            _stages.pop()
        _stages.append([stage, start, end, None])


def _on_count(event: str, **kw) -> None:
    stage = _COUNT_STAGES.get(event)
    if stage is None:
        return
    if stage == "cache_hit":
        _served.hit = True
    now = time.perf_counter_ns()
    with _stages_lock:
        _stages.append([stage, now, now, None])


def _label_since(t0_ns: int, label: str) -> None:
    """Give ``label`` to the unlabelled records that began at or after
    ``t0_ns``: those of the compiling call that started then."""
    with _stages_lock:
        for rec in reversed(_stages):
            if rec[2] < t0_ns:
                break
            if rec[1] >= t0_ns and rec[3] is None:
                rec[3] = label


import jax.monitoring as _monitoring  # noqa: E402

_monitoring.register_event_duration_secs_listener(_on_duration)
_monitoring.register_event_listener(_on_count)
