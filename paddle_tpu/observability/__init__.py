"""Observability plane (ISSUE 8): unified telemetry bus, step
metrics, recompile ledger.

- :mod:`.bus` — the one per-rank JSONL event schema every runtime
  emitter (guard, comm monitor, ElasticManager, metrics, ledger,
  profiler) writes through; legacy ``PADDLE_*_EVENT_FILE`` streams stay
  as compat aliases. Stdlib-pure.
- :mod:`.metrics` — periodic ``step_metrics`` records riding the
  guard's ``PADDLE_GUARD_SYNC_EVERY`` async host read (zero new
  per-step syncs), and ``decode_metrics``/``decode_request`` records
  riding the serving engine's ``PADDLE_SERVE_SYNC_EVERY`` readback
  cadence (ISSUE 9, same discipline).
- :mod:`.ledger` — jit cache misses as ``recompile`` records with arg
  shape/dtype/donation fingerprints, compile seconds, and a
  recompile-storm detector naming the changing fingerprint field.
- :mod:`.monitor` — the LIVE fleet monitor (ISSUE 14): incremental
  per-rank stream cursors, straggler ranking, online percentile
  digests, and the incident correlator; embedded in the elastic
  launcher or standalone via
  ``python -m paddle_tpu.observability.monitor``. Stdlib-pure.

Capture-on-anomaly device tracing lives in :mod:`paddle_tpu.profiler`
(it owns the ``jax.profiler`` surface); ``tools/timeline.py`` merges
the per-rank streams into a chrome trace + summary.
"""
from __future__ import annotations

from . import bus, ledger, metrics, monitor
from .bus import current_step, emit, emit_span, read_stream, set_step
from .monitor import FleetMonitor

__all__ = [
    "bus", "metrics", "ledger", "monitor",
    "emit", "emit_span", "set_step", "current_step", "read_stream",
    "FleetMonitor",
]
