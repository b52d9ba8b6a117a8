"""paddle.distributed.spawn — in-Python multiprocess launcher.

Reference: python/paddle/distributed/spawn.py:276 — start nprocs python
processes running `func(*args)` with the cluster env injected, join, and
re-raise the first failure.

TPU note: one jax process per HOST owns the chip; nprocs>1 is the
CPU-backend testing path (`backend="cpu"` puts JAX_PLATFORMS=cpu in each
child's environment). The cluster env is in the child's environment from
its first instruction — before `func` is unpickled, which may already
import jax.
"""
from __future__ import annotations

import multiprocessing as mp
from typing import Tuple

from ..core.device import child_environ
from .launch import build_cluster_env

__all__ = ["spawn"]


def spawn(func, args: Tuple = (), nprocs: int = 1, join: bool = True,
          daemon: bool = False, backend: str = None, start_port: int = 6170,
          **options):
    """spawn.py:276 parity. Returns the process list when join=False."""
    ctx = mp.get_context("spawn")
    envs = build_cluster_env(nprocs, start_port=start_port)
    procs = []
    for env in envs:
        if backend:
            env["JAX_PLATFORMS"] = backend
        p = ctx.Process(target=func, args=args, daemon=daemon)
        with child_environ(env):
            p.start()
        procs.append(p)
    if not join:
        return procs
    # polling watch loop (launch_utils.py teardown semantics): the first
    # failing rank tears the job down, so a sibling blocked on a dead
    # coordinator cannot hang the launcher forever
    import time

    failed = None
    while True:
        all_done = True
        for rank, p in enumerate(procs):
            if p.is_alive():
                all_done = False
            elif p.exitcode != 0 and failed is None:
                failed = (rank, p.exitcode)
        if failed is not None or all_done:
            break
        time.sleep(0.2)
    if failed is not None:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=10)
        raise RuntimeError(
            f"spawned rank {failed[0]} exited with code {failed[1]}"
        )
    return procs
