"""Profiling / tracing (SURVEY.md §5; VERDICT r3 item 7).

Reference: paddle/fluid/platform/profiler.h — RAII `RecordEvent` (:127)
host annotations sprinkled through hot paths (tracer.cc:137,
basic_engine.cc:284), `EnableProfiler`/`DisableProfiler` (:210,:213) with
per-event aggregation tables; device timeline via CUPTI DeviceTracer
(device_tracer.cc:278) dumping a chrome-trace proto; Python facade
fluid/profiler.py.

TPU-native: `RecordEvent` pairs a host-side timing registry with
`jax.profiler.TraceAnnotation`, so events appear both in the host summary
table and on the device timeline; `start_profiler`/`stop_profiler` wrap
`jax.profiler.start_trace` (XPlane/TensorBoard artifact — the
DeviceTracer analog, produced by libtpu rather than CUPTI). Op dispatch
and TrainStep carry RecordEvent hooks that cost one module-flag check
when profiling is off.

`phase(name, **meta)` is the ungated primitive under `RecordEvent`: a bare
`TraceAnnotation`, entered whether or not `start_profiler()` was called, so
it lands in whatever profiler session is open (the benchmark harness starts
`jax.profiler` itself) on the device trace's clock, and costs about a
microsecond when none is. It marks work done once a turn, a request or a
step: the `engine.*` phases of `InferenceEngine.turn` and the `TrainStep.*`
phases of `TrainStep.__call__`, flat siblings that never nest, so an idle
gap of the device reads as the one phase the host was in.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = [
    "RecordEvent", "record_event", "phase", "start_profiler", "stop_profiler",
    "profiler", "is_profiling", "event_summary", "reset_profiler",
    "device_annotation", "arm_trace", "disarm_trace", "step_boundary",
    "trace_window_state",
]

_enabled = False          # host event recording on?
_trace_dir: Optional[str] = None


class _Registry(threading.local):
    def __init__(self):
        self.events: Dict[str, List[float]] = {}
        self.stack: List = []


_reg = _Registry()


def is_profiling() -> bool:
    return _enabled


def phase(name: str, **meta):
    """A host span named ``name`` in the open profiler session, if any;
    ``meta`` (``rid=``, ``slot=``) arrives as the event's stats and leaves
    its name bare. Not gated by :func:`start_profiler` and not recorded in
    :func:`event_summary`: per-op events use :class:`RecordEvent`."""
    import jax

    return jax.profiler.TraceAnnotation(name, **meta)


class RecordEvent:
    """RAII event annotation (profiler.h:127). Usable as a context manager
    or decorator; nests; no-op (one flag check) when profiling is off."""

    def __init__(self, name: str):
        self.name = name
        self._t0 = None
        self._ann = None

    def __enter__(self):
        if _enabled:
            self._ann = phase(self.name)
            self._ann.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            dt = time.perf_counter() - self._t0
            _reg.events.setdefault(self.name, []).append(dt)
            self._ann.__exit__(*exc)
            self._t0 = None
        return False

    def __call__(self, fn):
        def wrapped(*a, **kw):
            with RecordEvent(self.name):
                return fn(*a, **kw)

        wrapped.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapped


record_event = RecordEvent


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   trace_dir: Optional[str] = None):
    """EnableProfiler analog (profiler.h:210). `trace_dir` additionally
    captures a device XPlane trace (TensorBoard-loadable)."""
    global _enabled, _trace_dir
    _enabled = True
    _reg.events = {}
    if trace_dir is not None:
        import jax

        jax.profiler.start_trace(trace_dir)
        _trace_dir = trace_dir


def stop_profiler(sorted_key: str = "total", profile_path: Optional[str] = None):
    """DisableProfiler analog: stops recording, dumps the event table
    (and ends the device trace if one is running). Returns the summary."""
    global _enabled, _trace_dir
    _enabled = False
    if _trace_dir is not None:
        import jax

        jax.profiler.stop_trace()
        _trace_dir = None
    summary = event_summary(sorted_key)
    if profile_path:
        import json

        with open(profile_path, "w") as f:
            json.dump(summary, f, indent=2)
    return summary


def event_summary(sorted_key: str = "total") -> Dict[str, Dict[str, float]]:
    """Aggregated event table (profiler's PrintProfiler analog):
    name -> {calls, total_ms, avg_ms, max_ms, min_ms}."""
    out = {}
    for name, times in _reg.events.items():
        total = sum(times)
        out[name] = {
            "calls": len(times),
            "total_ms": total * 1e3,
            "avg_ms": total / len(times) * 1e3,
            "max_ms": max(times) * 1e3,
            "min_ms": min(times) * 1e3,
        }
    key = {"total": "total_ms", "calls": "calls", "max": "max_ms",
           "min": "min_ms", "ave": "avg_ms"}.get(sorted_key, "total_ms")
    return dict(
        sorted(out.items(), key=lambda kv: -kv[1][key])
    )


def reset_profiler():
    _reg.events = {}


@contextlib.contextmanager
def profiler(state: str = "All", tracer_option: str = "Default",
             trace_dir: Optional[str] = None, profile_path: Optional[str] = None):
    """fluid/profiler.py context-manager facade."""
    start_profiler(state, tracer_option, trace_dir)
    try:
        yield
    finally:
        stop_profiler(profile_path=profile_path)


# ---------------------------------------------------------------------------
# device-timeline annotation seam (ISSUE 8 tentpole d)
# ---------------------------------------------------------------------------


def device_annotation(name: str):
    """Name a region of a TRACED computation on the device timeline.

    `RecordEvent` is the host-side RAII seam; inside a jitted body it
    would only time tracing. This is its compiled-region counterpart:
    `jax.named_scope` attaches the name to the HLO ops traced under it,
    so a captured device trace (`arm_trace` / `start_profiler(trace_dir=)`)
    shows `attention::flash`, `TrainStep::opt_update`, ... as named
    spans. Pure trace-time metadata — zero bytes and zero nanoseconds in
    the compiled program — so the hot-path modules wear it
    unconditionally.
    """
    try:
        import jax

        return jax.named_scope(name)
    except Exception:  # noqa: BLE001 — annotation must never break math
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# capture-on-anomaly trace window (ISSUE 8 tentpole d)
# ---------------------------------------------------------------------------
#
# A guard trip (or PADDLE_OBS_TRACE_AT_STEP) *arms* a bounded device
# trace: the NEXT `PADDLE_OBS_TRACE_STEPS` steps are captured with
# jax.profiler.trace into PADDLE_OBS_TRACE_DIR (default:
# $PADDLE_OBS_DIR/traces). At most PADDLE_OBS_TRACE_MAX windows per
# process (default 1) — a flapping guard must not fill the disk with
# XPlane artifacts. The compiled step objects call `step_boundary(step)`
# once per step; disarmed, that costs one attribute check.

_TRACE_AT_ENV = "PADDLE_OBS_TRACE_AT_STEP"
_TRACE_STEPS_ENV = "PADDLE_OBS_TRACE_STEPS"
_TRACE_DIR_ENV = "PADDLE_OBS_TRACE_DIR"
_TRACE_MAX_ENV = "PADDLE_OBS_TRACE_MAX"

_window_lock = threading.Lock()
_window = None          # {"remaining", "dir", "reason", "active"}
_windows_taken = 0
_env_arm_at = "unparsed"   # lazily parsed PADDLE_OBS_TRACE_AT_STEP


def _reset_trace_state() -> None:
    """Tests: disarm and forget the per-process window budget."""
    global _windows_taken, _env_arm_at
    disarm_trace()
    _windows_taken = 0
    _env_arm_at = "unparsed"


def _trace_dest() -> Optional[str]:
    d = os.environ.get(_TRACE_DIR_ENV)
    if d:
        return d
    obs = os.environ.get("PADDLE_OBS_DIR")
    return os.path.join(obs, "traces") if obs else None


def trace_window_state() -> Optional[dict]:
    """The armed/active window (None when disarmed) — test/debug view."""
    return dict(_window) if _window else None


def arm_trace(steps: Optional[int] = None, reason: str = "manual",
              trace_dir: Optional[str] = None) -> bool:
    """Arm a bounded device-trace window for the next `steps` steps.
    Returns False (and stays disarmed) when no destination is
    configured, a window is already armed/active, or the per-process
    budget (`PADDLE_OBS_TRACE_MAX`) is spent."""
    global _window, _windows_taken
    dest = trace_dir or _trace_dest()
    if not dest:
        return False
    n = steps if steps is not None else int(
        os.environ.get(_TRACE_STEPS_ENV, "3") or 3)
    if n <= 0:
        return False
    budget = int(os.environ.get(_TRACE_MAX_ENV, "1") or 1)
    with _window_lock:
        if _window is not None or _windows_taken >= budget:
            return False
        _windows_taken += 1
        _window = {"remaining": int(n), "dir": dest, "reason": reason,
                   "active": False}
    from ..observability import bus as _bus

    _bus.emit("trace_armed", {"reason": reason, "steps": int(n),
                              "dir": dest})
    return True


def disarm_trace() -> None:
    """Cancel an armed window / stop an active one (tests, teardown)."""
    global _window
    with _window_lock:
        w, _window = _window, None
    if w and w["active"]:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            pass


def step_boundary(step: int) -> None:
    """Per-step hook from the compiled step objects (called BEFORE the
    step's dispatch): open the armed window, count it down, close it.
    One `is None` check when disarmed.

    The window covers exactly `steps` dispatches: the first boundary
    call after arming starts the trace (never a torn half-step), each
    covered call decrements, and the trace is stopped at the START of
    the first boundary call PAST the window — stopping on the closing
    step's own boundary would end the capture before that step's
    dispatch (with steps=1 it would capture nothing). If training ends
    exactly at the window's edge the trace stays open until
    :func:`disarm_trace` / `stop_profiler` (best-effort by design)."""
    global _window, _windows_taken
    if _window is None:
        _maybe_env_arm(step)
        if _window is None:
            return
    with _window_lock:
        w = _window
        if w is None:
            return
        if w["active"] and w["remaining"] <= 0:
            _window = None          # window spent: close before this
            done = True             # step's dispatch joins the capture
        else:
            done = False
            if not w["active"]:
                rank = os.environ.get("PADDLE_TRAINER_ID", "0")
                dest = os.path.join(
                    w["dir"], f"step{step}.rank{rank}.{w['reason']}")
                try:
                    import jax

                    os.makedirs(dest, exist_ok=True)
                    jax.profiler.start_trace(dest)
                except Exception:  # noqa: BLE001 — tracing best-effort
                    # a transient failure (unwritable dir, profiler
                    # busy) must not burn the per-process budget: the
                    # next anomaly gets another shot
                    _window = None
                    _windows_taken = max(_windows_taken - 1, 0)
                    return
                w["active"] = True
                w["dest"] = dest
                w["start_step"] = step
            w["remaining"] -= 1
            w["last_step"] = step
    if done:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            return
        from ..observability import bus as _bus

        _bus.emit("trace_captured", {
            "reason": w["reason"], "dir": w["dest"],
            "first_step": w["start_step"], "last_step": w["last_step"],
        }, step=step)


def _maybe_env_arm(step: int) -> None:
    """PADDLE_OBS_TRACE_AT_STEP=N arms the window the moment step N
    begins (step_boundary runs before the step's dispatch, so the
    capture covers step N onward). Parsed once per process."""
    global _env_arm_at
    if _env_arm_at == "unparsed":
        raw = os.environ.get(_TRACE_AT_ENV, "").strip()
        try:
            _env_arm_at = int(raw) if raw else None
        except ValueError:
            _env_arm_at = None
    if _env_arm_at is None:
        return
    if step >= _env_arm_at:
        _env_arm_at = None
        arm_trace(reason=f"at_step_{step}")
