"""Communication core: device mesh, groups, and the collective engine.

Reference analog (SURVEY.md §2.9 / §5 backend table):
  - ring_id-keyed NCCL communicators (platform/collective_helper.h:52,72;
    gen_comm_id_helper.cc TCP bootstrap) ≙ named axes of a
    `jax.sharding.Mesh` over ICI — a Group here IS a mesh axis; there are no
    streams or comm-id exchanges because XLA compiles collectives into the
    program and the PJRT runtime owns topology discovery.
  - multi-host bootstrap (`init_parallel_env`, distributed/parallel.py:57 +
    c_gen_nccl_id/c_comm_init ops) ≙ `jax.distributed.initialize`
    (coordinator service) + the global device list.

Single-controller SPMD model: one Python process drives all devices. A
"per-rank value" is a global array whose leading axis is the rank axis,
sharded over the group's mesh axis (`shard_rank_axis`). Collectives are
shard_map'd XLA ops jitted once per (shape, dtype, op); inside an spmd
region (shard_map trace entered via this module) they lower directly to
`lax.psum`/`all_gather`/`ppermute` on the axis name.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map


# The mesh-axis classification every hot-path router shares (round 7):
# these axes shard the BATCH/row dims of an operand ('dp' flat
# data-parallel, or the hierarchical 'dcn' x 'ici' pair); 'mp' shards
# heads/features; anything else ('pp' pipeline stages, 'sp' ring
# attention's sequence axis) belongs to its own schedule and makes the
# shard_map seams decline. One constant so the three routing policies
# (attention.shard_factoring, norm._ln_row_factoring,
# overlap.row_overlap_plan) cannot drift.
DP_AXES = ("dp", "dcn", "ici")


def partitioning_axes(mesh) -> tuple:
    """The mesh axes that actually partition a program: every axis with
    size > 1, in mesh order (size-1 axes partition nothing and must
    never veto a routing decision)."""
    return tuple(a for a in mesh.axis_names if int(mesh.shape[a]) > 1)


def shard_map(f, mesh, in_specs, out_specs, auto=None):
    """The repo-wide shard_map wrapper (replication checking off — bodies
    use explicit collectives). `auto` names mesh axes left to GSPMD
    inside the body (partial-manual regions: the async-dcn grad
    reduction is manual over 'dcn', auto over ici/mp/...); jax spells
    that as its complement, `axis_names` = the manual axes."""
    kw = {}
    if auto is not None:
        kw["axis_names"] = frozenset(mesh.axis_names) - frozenset(auto)
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, **kw,
    )


class Group:
    """A communicator: a set of devices bound to one mesh axis.

    The ring_id/NCCLComm analog (collective_helper.h:52) — but declarative:
    holding a Group means collectives over its axis name compile to ICI
    collectives among exactly these devices.
    """

    _counter = 0

    def __init__(self, devices: Sequence, axis_name: Optional[str] = None,
                 gid: Optional[int] = None, ranks: Optional[List[int]] = None):
        self.devices = list(devices)
        self.nranks = len(self.devices)
        self.id = Group._counter if gid is None else gid
        Group._counter += 1
        self.axis_name = axis_name or f"g{self.id}"
        self.ranks = list(ranks) if ranks is not None else list(
            range(self.nranks)
        )
        self.mesh = Mesh(
            np.array(self.devices).reshape(self.nranks), (self.axis_name,)
        )

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return (f"Group(id={self.id}, nranks={self.nranks}, "
                f"axis='{self.axis_name}')")


class _CommState(threading.local):
    def __init__(self):
        self.default_group: Optional[Group] = None
        self.groups: Dict[int, Group] = {}
        self.spmd_axes: Tuple[str, ...] = ()  # inside shard_map regions
        self.hybrid_mesh: Optional[Mesh] = None


_state = _CommState()
_jax_dist_initialized = False


def _ensure_init() -> Group:
    if _state.default_group is None:
        init_parallel_env()
    return _state.default_group


def _probe_endpoint(endpoint: str, timeout: float = 1.0) -> bool:
    """Cheap TCP reachability check of a host:port (the coordinator)."""
    import socket

    host, _, port = endpoint.rpartition(":")
    try:
        with socket.create_connection((host, int(port)), timeout):
            return True
    except (OSError, ValueError):
        return False


def _rdv_diagnose(coordinator: str, num: int, pid: int) -> str:
    """Attribution for a failed rendezvous: coordinator reachability plus
    which ranks never checked in through the launcher's shared sync dir."""
    import os

    parts = [
        f"rendezvous failed: rank {pid}/{num}, coordinator {coordinator} "
        f"tcp-{'reachable' if _probe_endpoint(coordinator) else 'UNREACHABLE'}"
    ]
    sync_dir = os.environ.get("PADDLE_COLL_SYNC_DIR")
    if sync_dir:
        d = os.path.join(sync_dir, "rdv")
        missing = [r for r in range(num)
                   if not os.path.exists(os.path.join(d, f"rank{r}"))]
        if missing:
            parts.append(f"ranks that never reached rendezvous: {missing}")
        else:
            parts.append(
                "all ranks checked in — suspect coordinator service or "
                "network between hosts, not a missing rank")
    return "; ".join(parts)


def _rendezvous_with_retry(init_fn, coordinator: str, num: int, pid: int,
                           deadline: Optional[float] = None,
                           backoff_base: Optional[float] = None,
                           backoff_cap: float = 15.0,
                           sleep=None) -> None:
    """Run `init_fn(remaining_seconds)` (jax.distributed.initialize) with
    exponential backoff + jitter under an overall PADDLE_RDV_DEADLINE.

    Mirrors the reference's TCP comm-id exchange retry loop
    (gen_comm_id_helper.cc retries connect with a bounded budget) — a
    slow-to-start peer must not fail the job, but a truly absent one must
    fail it LOUDLY with attribution instead of hanging forever."""
    import os
    import random
    import sys
    import time

    def _envf(name, default):
        raw = os.environ.get(name, "")
        return float(raw) if raw.strip() else default

    deadline = deadline if deadline is not None else _envf(
        "PADDLE_RDV_DEADLINE", 300.0)
    base = backoff_base if backoff_base is not None else _envf(
        "PADDLE_RDV_BACKOFF", 1.0)
    sleep = sleep or time.sleep
    sync_dir = os.environ.get("PADDLE_COLL_SYNC_DIR")
    if sync_dir:
        # check in BEFORE attempting: peers diagnosing a failure see who
        # ever made it this far (unreachable-rank attribution)
        try:
            d = os.path.join(sync_dir, "rdv")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, f"rank{pid}"), "w") as f:
                f.write(str(time.time()))
        except OSError:
            pass
    t_end = time.monotonic() + deadline
    attempt = 0
    while True:
        remaining = t_end - time.monotonic()
        try:
            if remaining <= 0:
                raise TimeoutError(
                    f"rendezvous deadline {deadline:g}s exhausted")
            init_fn(remaining)
            return
        except Exception as e:
            attempt += 1
            delay = min(base * (2.0 ** (attempt - 1)), backoff_cap)
            delay *= 0.5 + random.random()  # ±50% jitter: no stampedes
            if remaining <= 0 or time.monotonic() + delay >= t_end:
                raise RuntimeError(
                    _rdv_diagnose(coordinator, num, pid)
                    + f" (after {attempt} attempt(s), {deadline:g}s "
                      f"deadline; last error: {e})"
                ) from e
            print(
                f"paddle_tpu.rendezvous: attempt {attempt} failed ({e}); "
                f"retrying in {delay:.1f}s", file=sys.stderr, flush=True)
            sleep(delay)


def init_parallel_env(backend: Optional[str] = None) -> "ParallelEnv":
    """Bootstrap distributed state (reference: parallel.py:57
    init_parallel_env → NCCLParallelContext::Init + TCP comm-id exchange).

    TPU-native: multi-host rendezvous is jax.distributed (coordinator env:
    COORDINATOR_ADDRESS / PADDLE_TRAINER_ENDPOINTS honored); the default
    group spans every device in the job over axis 'dp'. The coordinator
    connection retries with exponential backoff + jitter under an overall
    PADDLE_RDV_DEADLINE and fails with unreachable-rank attribution
    (:func:`_rendezvous_with_retry`).
    """
    import os

    global _jax_dist_initialized

    def _dist_client_active():
        # must not touch jax.process_count() here: that initializes the
        # XLA backend, after which jax.distributed.initialize refuses to
        # run. The distributed client state is the pre-backend signal.
        try:
            from jax._src import distributed as _jd

            return _jd.global_state.client is not None
        except Exception:
            return False

    if (int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1
            and os.environ.get("PADDLE_TRAINER_ENDPOINTS")
            and not _jax_dist_initialized
            and not _dist_client_active()):
        # Multi-host launch: endpoints list ≙ coordinator bootstrap
        # (gen_comm_id_helper.cc:284 SendBroadCastCommID analog). Failures
        # propagate: a typo'd coordinator address must NOT degrade to
        # silent single-host training.
        coordinator = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")[0]
        if ":" not in coordinator:
            raise ValueError(
                "PADDLE_TRAINER_ENDPOINTS entries must be host:port, got "
                f"{coordinator!r}"
            )
        num = int(os.environ["PADDLE_TRAINERS_NUM"])
        pid = int(os.environ.get("PADDLE_TRAINER_ID", "0"))

        def _init(remaining):
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num, process_id=pid,
                    initialization_timeout=max(int(remaining), 1),
                )
            except Exception:
                try:  # leave no half-initialized client behind a retry
                    jax.distributed.shutdown()
                except Exception:
                    pass
                raise

        _rendezvous_with_retry(_init, coordinator, num, pid)
        _jax_dist_initialized = True
    if _state.default_group is None:
        devs = jax.devices()
        _state.default_group = Group(devs, axis_name="dp", gid=0)
        _state.groups[0] = _state.default_group
    return ParallelEnv()


def is_initialized() -> bool:
    return _state.default_group is not None


def get_group(gid: int = 0) -> Optional[Group]:
    return _state.groups.get(gid)


def _default_group() -> Group:
    return _ensure_init()


def new_group(ranks: Optional[List[int]] = None, backend: Optional[str] = None,
              axis_name: Optional[str] = None) -> Group:
    """Create a communicator over a device subset (collective.py new_group)."""
    world = _ensure_init()
    if ranks is None:
        ranks = list(range(world.nranks))
    devs = [world.devices[r] for r in ranks]
    g = Group(devs, axis_name=axis_name, ranks=ranks)
    _state.groups[g.id] = g
    return g


class ParallelEnv:
    """Env facade (reference: fluid/dygraph/parallel.py ParallelEnv)."""

    @property
    def rank(self) -> int:
        import os

        if "PADDLE_TRAINER_ID" in os.environ:
            return int(os.environ["PADDLE_TRAINER_ID"])
        return jax.process_index()

    @property
    def world_size(self) -> int:
        g = _state.default_group
        return g.nranks if g is not None else len(jax.devices())

    @property
    def nranks(self) -> int:
        return self.world_size

    @property
    def local_rank(self) -> int:
        return self.rank

    @property
    def dev_id(self) -> int:
        return self.rank

    @property
    def device_id(self) -> int:
        return self.rank


def get_rank() -> int:
    """Trainer rank (reference parallel.py get_rank: PADDLE_TRAINER_ID or
    the process index)."""
    return ParallelEnv().rank


def get_world_size() -> int:
    """Number of TRAINER PROCESSES (reference get_world_size semantics —
    PADDLE_TRAINERS_NUM / process count), distinct from
    ParallelEnv().world_size which counts mesh devices in the
    single-controller model."""
    import os

    if "PADDLE_TRAINERS_NUM" in os.environ:
        return int(os.environ["PADDLE_TRAINERS_NUM"])
    return jax.process_count()


# ---------------------------------------------------------------------------
# spmd region tracking: inside a shard_map'd program, collectives lower to
# bare lax ops on the axis name instead of launching their own shard_map.
# ---------------------------------------------------------------------------


class _SpmdRegion:
    def __init__(self, axes: Tuple[str, ...]):
        self.axes = axes

    def __enter__(self):
        self._prev = _state.spmd_axes
        _state.spmd_axes = self._prev + self.axes
        return self

    def __exit__(self, *exc):
        _state.spmd_axes = self._prev


def spmd_region(*axes: str) -> _SpmdRegion:
    """Mark that code runs inside a shard_map over `axes` (used by
    DataParallel/pipeline/ring-attention internals and user rank programs)."""
    return _SpmdRegion(tuple(axes))


def in_spmd_region(axis_name: Optional[str] = None) -> bool:
    if axis_name is None:
        return bool(_state.spmd_axes)
    return axis_name in _state.spmd_axes


# ---------------------------------------------------------------------------
# Hybrid topology: one mesh, axes = parallelism dimensions
# ---------------------------------------------------------------------------


def init_hybrid_mesh(dp: int = 1, mp: int = 1, pp: int = 1,
                     sp: int = 1, dp_inner: int = 1) -> Mesh:
    """Build the job-wide hybrid mesh (dp, pp, sp, mp axes; mp innermost for
    ICI locality — model-parallel collectives are the latency-critical ones).

    The analog of the reference's per-strategy comm-ring construction
    (fleet meta_optimizers/common.py CollectiveHelper ring setup): here ONE
    declaration; each strategy consumes its axis by sharding on it.

    `dp_inner > 1` factors the dp axis into TWO mesh axes ('dcn' outer x
    'ici' inner, dp = dcn * dp_inner) — the two-level topology behind
    DistributedStrategy.hierarchical_allreduce: anything sharded or
    reduced over data-parallel uses the axis PAIR, so GSPMD emits the
    grad reduction as reduce-scatter/all-reduce over the fast inner
    (intra-pod ICI) axis composed with the slow outer (cross-pod DCN)
    axis, instead of one flat ring spanning both fabrics (the reference's
    hierarchical_allreduce inter/exter NCCL ring split,
    fleet meta_optimizers/common.py)."""
    _ensure_init()
    devs = jax.devices()
    need = dp * mp * pp * sp
    if len(devs) < need:
        raise ValueError(
            f"hybrid topology dp={dp} x pp={pp} x sp={sp} x mp={mp} needs "
            f"{need} devices, have {len(devs)}"
        )
    if dp_inner > 1:
        if dp % dp_inner:
            raise ValueError(
                f"hierarchical dp: dp={dp} not divisible by "
                f"dp_inner={dp_inner}"
            )
        arr = np.array(devs[:need]).reshape(
            dp // dp_inner, dp_inner, pp, sp, mp
        )
        mesh = Mesh(arr, ("dcn", "ici", "pp", "sp", "mp"))
    else:
        arr = np.array(devs[:need]).reshape(dp, pp, sp, mp)
        mesh = Mesh(arr, ("dp", "pp", "sp", "mp"))
    _state.hybrid_mesh = mesh
    return mesh


def hybrid_mesh() -> Optional[Mesh]:
    return _state.hybrid_mesh


def set_hybrid_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Swap the job-wide hybrid mesh in place (the elastic-reshard seam:
    survivors re-factor onto a smaller/larger device set mid-job —
    distributed/resharding.py). Returns the previous mesh."""
    prev = _state.hybrid_mesh
    _state.hybrid_mesh = mesh
    return prev


def rebuild_world(devices: Sequence) -> Group:
    """Re-point the default communicator ('dp' axis, group id 0) at
    exactly `devices` — the comm-group half of an elastic reshard: after
    rank departure/arrival the eager collectives and DataParallel input
    sharding must span the SURVIVORS, not the spawn-time world."""
    g = Group(list(devices), axis_name="dp", gid=0)
    _state.default_group = g
    _state.groups[0] = g
    return g


def dp_axes(mesh: Optional[Mesh] = None):
    """The mesh axis (or axis pair) data-parallel work shards over:
    'dp' on a flat mesh, ('dcn', 'ici') on a hierarchical one. The tuple
    drops straight into a PartitionSpec element."""
    m = mesh if mesh is not None else _state.hybrid_mesh
    if m is not None and "ici" in m.axis_names:
        return ("dcn", "ici")
    return "dp"


def dp_size(mesh: Optional[Mesh] = None) -> int:
    """Total data-parallel degree of the mesh (product over dp axes)."""
    m = mesh if mesh is not None else _state.hybrid_mesh
    if m is None:
        return 1
    ax = dp_axes(m)
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= m.shape[a]
        return int(n)
    return int(m.shape[ax]) if ax in m.shape else 1


def mp_mesh() -> Mesh:
    """Mesh tensor-parallel params shard over ('mp' axis of the hybrid
    mesh). Declared by fleet.init(strategy with hybrid_configs mp_degree)
    or comm.init_hybrid_mesh."""
    if _state.hybrid_mesh is None:
        raise RuntimeError(
            "model-parallel layers need a hybrid mesh: call "
            "fleet.init(strategy=DistributedStrategy with "
            "hybrid_configs={'mp_degree': N}) or "
            "distributed.comm.init_hybrid_mesh(mp=N) first"
        )
    return _state.hybrid_mesh


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def shard_rank_axis(raw, group: Optional[Group] = None):
    """Lay a [nranks, ...] array out with one leading-axis slice per device
    of the group — the canonical 'per-rank value' layout."""
    g = group or _ensure_init()
    return jax.device_put(raw, NamedSharding(g.mesh, P(g.axis_name)))


def replicate(raw, group: Optional[Group] = None):
    g = group or _ensure_init()
    return jax.device_put(raw, NamedSharding(g.mesh, P()))
