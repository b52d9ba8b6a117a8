"""MFU / achieved-FLOPs accounting (ISSUE 8 tentpole b).

The PERF.md attribution protocol ("what fraction of achievable peak is
this step") has been a by-hand exercise: read the per-op table, price
each op at the calibrated rates, divide. This module mechanizes the
numerator and the denominator:

- **per-step FLOPs** come from XLA's own cost model —
  ``jitted.lower(*avals).cost_analysis()['flops']`` over the EXACT
  program the step runs (forward + backward + optimizer update, fused).
  Lowering from ``ShapeDtypeStruct`` avals costs one re-trace, no
  compile and no device work; ``jit.TrainStep.flops_per_step()`` caches
  the number after the first ask.
- **peak FLOPs** come from a per-device-kind table (bf16/matmul peak
  per chip — the MXU number a tuned step is priced against), overridable
  with ``PADDLE_OBS_PEAK_FLOPS`` for new silicon or f32-bound models.

``mfu_pct(flops_per_step, step_seconds)`` is then the model-FLOPs
utilization the MLPerf-on-pods tuning loop keys on. ``bench.py``
records it per round (``*_mfu_pct`` keys) and
``tools/bench_continuity.py`` reports drift WITHOUT gating — MFU moves
with every legitimate model change, so it is a trend line, not a gate.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["peak_flops", "mfu_pct", "flops_of_lowered", "PEAK_FLOPS"]

_PEAK_ENV = "PADDLE_OBS_PEAK_FLOPS"

#: per-CHIP dense matmul peak (bf16 where the unit has one, else f32),
#: matched by substring against ``Device.device_kind`` lowercased.
#: Sources: published TPU spec sheets (per-chip, both cores).
PEAK_FLOPS = (
    ("v6", 918e12),          # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops() -> Optional[float]:
    """Per-device peak FLOPs/s, or None when the device kind is not in
    the table (CPU CI without the ``PADDLE_OBS_PEAK_FLOPS`` override —
    MFU is then not reported rather than reported against a made-up
    number). A backend that fails to initialize raises."""
    raw = os.environ.get(_PEAK_ENV, "").strip()
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    import jax

    kind = jax.local_devices()[0].device_kind.lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def flops_of_lowered(lowered) -> Optional[float]:
    """The 'flops' entry of a Lowered/Compiled cost analysis (per
    device: XLA reports the per-partition program)."""
    try:
        ca = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — not all backends cost-model
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    return float(flops) if isinstance(flops, (int, float)) else None


def mfu_pct(flops_per_step: Optional[float],
            step_seconds: float) -> Optional[float]:
    """Model-FLOPs utilization, percent of per-device peak."""
    peak = peak_flops()
    if not peak or not flops_per_step or step_seconds <= 0:
        return None
    return round(flops_per_step / step_seconds / peak * 100.0, 2)
