"""The device a run is on, as JAX reports it."""
from __future__ import annotations

import sys


def require(chips: int, rehearse: bool) -> dict:
    """Anything but a TPU with the cell's chips ends the run with no
    result; there is no CPU fallback (`rehearse` is the builder's toy)."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"run.py: no TPU: jax found no usable backend ({e})")
    d0 = devs[0]
    if rehearse:
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": min(len(devs), chips)}
    if d0.platform != "tpu":
        sys.exit(f"run.py: no TPU: jax.devices()[0].platform is "
                 f"{d0.platform!r}; the benchmark has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell asks for {chips} chips, jax sees "
                 f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    """`peak_bytes_in_use` of the fullest chip the cell uses."""
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)
