"""Device / place management.

TPU-native analog of the reference's Place + DeviceContextPool
(reference: paddle/fluid/platform/place.h, device_context.h). Under JAX the
device runtime is PJRT; a "place" is a jax.Device, and the context pool's job
(streams, handles) is owned by XLA. What remains for the framework is device
*selection* for eager ops and host/device transfer policy.
"""
from __future__ import annotations

import contextlib
import multiprocessing.context
import os
import threading

import jax

_current_device = None  # None -> jax default device


class Place:
    """Lightweight place tag mirroring paddle.CPUPlace()/CUDAPlace(i).

    reference: paddle/fluid/platform/place.h — a tagged union over device
    kinds. Here it resolves to a concrete jax.Device.
    """

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.kind]
        if not devs:
            # fall back to any device of requested kind on other backends
            try:
                devs = jax.devices(self.kind)
            except RuntimeError:
                devs = []
        if not devs:
            raise RuntimeError(f"No {self.kind} device available")
        if not 0 <= self.index < len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.kind} device(s) "
                "available")
        return devs[self.index]

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


# CUDA alias kept for script parity: maps onto the accelerator device.
def CUDAPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def set_device(device) -> Place:
    """paddle.set_device('tpu'|'cpu'|'tpu:0'|'gpu:0').

    'gpu' is accepted for script parity and maps to the TPU chip — the point
    of the framework is that reference training scripts run unmodified
    (BASELINE.json north_star).
    """
    global _current_device
    if isinstance(device, Place):
        _current_device = device
        return device
    name = str(device)
    if ":" in name:
        kind, idx = name.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = name, 0
    kind = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(kind, kind)
    place = Place(kind, idx)
    _current_device = place
    return place


def get_device() -> str:
    if _current_device is None:
        d = jax.devices()[0]
        return f"{d.platform}:{d.id}"
    return f"{_current_device.kind}:{_current_device.index}"


def current_jax_device():
    """The jax.Device eager ops should run on (None -> jax default)."""
    if _current_device is None:
        return None
    return _current_device.jax_device()


def force_cpu_devices(n: int = 8):
    """Force the CPU backend with `n` virtual devices — the sharding test
    harness (SURVEY.md §4: ranks ≙ in-process XLA devices).

    Must run before the first backend query: `XLA_FLAGS` is read at
    backend init and `jax_platforms` is updated through jax.config
    because jax captured `JAX_PLATFORMS` when it was imported. Note hosts
    may export XLA_FLAGS="" (empty): append, don't setdefault. Raises if
    jax already initialized with fewer devices.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            f"--xla_force_host_platform_device_count={n}", flags,
        )
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    # the persistent compilation cache (core/compile_cache.py) exists
    # for tens-of-seconds TPU compiles; XLA:CPU AOT cache entries embed
    # target-tuning pseudo-features (+prefer-no-scatter/-gather) that
    # the loader flags as machine mismatches with a SIGILL warning —
    # not worth it for millisecond CPU compiles
    jax.config.update("jax_enable_compilation_cache", False)
    ndev = len(jax.devices())
    if ndev < n:
        raise RuntimeError(
            f"need {n} CPU devices but jax already initialized with {ndev}; "
            "call force_cpu_devices before any jax backend query"
        )


_CHILD_ENV_LOCK = threading.Lock()


@contextlib.contextmanager
def child_environ(env):
    """Start child processes with `env` merged over this process's
    environment. A chip belongs to one process: a child is kept off it by
    ``JAX_PLATFORMS=cpu`` being in its environment BEFORE it imports jax
    (jax reads the variable at import, and unpickling the child's target
    can already import it) and by nothing else. multiprocessing's spawn
    children take ``os.environ`` as it stands when they start and accept
    no ``env=``, so the variables are set here for the duration of the
    start; this process's own jax has long read its configuration."""
    with _CHILD_ENV_LOCK:
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


class _CpuSpawnProcess(multiprocessing.context.SpawnProcess):
    def start(self):
        with child_environ({"JAX_PLATFORMS": "cpu"}):
            super().start()


class CpuSpawnContext(multiprocessing.context.SpawnContext):
    """The ``spawn`` multiprocessing context for host-side workers
    (DataLoader pools): every process it starts is pinned to the CPU
    backend from its first instruction, whenever the pool starts it."""

    Process = _CpuSpawnProcess


def is_compiled_with_cuda() -> bool:
    """Parity shim: scripts gate GPU paths on this; TPU counts as accelerator."""
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
