"""paddle.device parity surface + HBM budgeting.

Reference: python/paddle/device/__init__.py (set_device/get_device) and
python/paddle/device/cuda (memory_allocated / max_memory_allocated /
memory_reserved over the C++ allocator's stats,
memory/allocation/allocator_facade.*).

TPU-native: PJRT owns the allocator; the budgeting surface reads each
device's live allocator statistics (`jax.Device.memory_stats()`), so the
same API answers "how much HBM is this job using / what is the limit"
that the reference's StatAllocator answers for GPU memory.
"""
from __future__ import annotations

from ..core.device import (  # noqa: F401
    get_device,
    is_compiled_with_cuda,
    set_device,
)

__all__ = [
    "set_device", "get_device", "memory_stats", "memory_allocated",
    "max_memory_allocated", "memory_reserved", "device_count", "cuda",
]


def _device(dev=None):
    import jax

    if dev is None:
        return jax.devices()[0]
    if isinstance(dev, int):
        return jax.devices()[dev]
    return dev


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator stats (bytes_in_use, peak_bytes_in_use,
    bytes_limit, ...). Empty dict on backends without stats (CPU)."""
    return dict(_device(device).memory_stats() or {})


def memory_allocated(device=None) -> int:
    """paddle.device.cuda.memory_allocated analog: live HBM bytes."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak HBM bytes since process start."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Total HBM the allocator may use (bytes_limit)."""
    return int(memory_stats(device).get("bytes_limit", 0))


def device_count() -> int:
    import jax

    return jax.device_count()


class _CudaShim:
    """paddle.device.cuda compatibility: scripts probing GPU memory get
    the accelerator's numbers (TPU HBM here)."""

    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(memory_reserved)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def empty_cache():
        return None  # PJRT frees eagerly; parity no-op


cuda = _CudaShim()
