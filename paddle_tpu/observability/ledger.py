"""Recompile ledger — every jit cache miss becomes a bus record.

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

``install_backend_listener()`` additionally taps ``jax.monitoring``'s
event-duration stream for backend compile keys, so compiles that happen
OUTSIDE an instrumented wrapper (eager ops, collectives) still land on
the bus as ``backend_compile`` rows with their true compile seconds.

``compile_count()`` is the process-wide miss total.
``compile_seconds()`` is the wall time of those compiling calls, summed:
the part of a process's set-up that went into compiling its steps.

:func:`jit` is the one way a compiled step is made: it names the function
by its ledger label before jitting it, so the label on a ``recompile``
row and the module a device trace shows (``jit_<label>``) are one string.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Tuple

from . import bus

__all__ = [
    "arg_fingerprint", "diff_fingerprints", "instrument", "jit",
    "LedgeredFunction", "compile_count", "compile_seconds",
    "install_backend_listener", "reset",
]

_STORM_ENV = "PADDLE_OBS_STORM_N"

_total_compiles = 0
_total_compile_s = 0.0
_listener_installed = False


def compile_count() -> int:
    """Process-wide jit cache misses observed by instrumented wrappers."""
    return _total_compiles


def compile_seconds() -> float:
    """Process-wide wall seconds of the calls :func:`compile_count`
    counts (trace + lower + compile + that call's dispatch)."""
    return _total_compile_s


def reset() -> None:
    """Tests: zero the process-wide counters."""
    global _total_compiles, _total_compile_s
    _total_compiles = 0
    _total_compile_s = 0.0


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
            self._on_compile(fp, wall)
        if fp is not None:
            self._prev_fp = fp
        return out

    def _on_compile(self, fp, wall_s: float) -> None:
        global _total_compiles, _total_compile_s
        self.compiles += 1
        _total_compiles += 1
        _total_compile_s += wall_s
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


def install_backend_listener() -> None:
    """Tap jax.monitoring's duration events for backend compiles (once
    per process; covers compiles outside instrumented wrappers). Only
    meaningful when the bus is on — rows go nowhere otherwise."""
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    try:
        import jax.monitoring as M

        def _on_duration(key: str, value: float, **kw) -> None:
            # only true XLA backend compiles: the trace/lowering keys
            # ('jaxpr_trace_duration' etc.) fire for every trivial eager
            # jaxpr and would drown the stream
            if "backend_compile" not in key:
                return
            if bus.enabled():
                bus.emit("backend_compile", {
                    "key": key, "seconds": round(float(value), 3)})

        M.register_event_duration_secs_listener(_on_duration)
    except Exception:  # noqa: BLE001 — telemetry stays best-effort
        pass
