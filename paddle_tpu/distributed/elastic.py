"""Elastic runtime: heartbeat watchdog, restart budget, log capture.

Reference: launch_utils.py:996-1118 — `TrainerProc` bookkeeping, the
`watch_local_trainers` poll loop, `workerlog.N` per-rank log files,
`terminate_local_procs` SIGTERM→grace→SIGKILL teardown — plus
`distributed/fleet/elastic/manager.py`'s ElasticManager (hung-worker
watchdog + bounded relaunch).

TPU-native additions over the reference watch loop:

- **heartbeats**: each rank gets `PADDLE_HEARTBEAT_FILE`; the trainer
  (TrainEpochRange per epoch, hapi `TerminateOnPreempt` per batch, or
  anything calling :func:`heartbeat`) touches it. A rank whose file goes
  stale for `PADDLE_WATCHDOG_TIMEOUT` seconds is *hung* (deadlocked
  collective, wedged host) — the reference only notices exits, so a hung
  rank stalls the pod forever.
- **escalation**: hung/failed ranks get SIGTERM, a
  `PADDLE_WATCHDOG_GRACE`-second window to snapshot, then SIGKILL.
- **restart budget**: at most `max_restarts` relaunches per
  `PADDLE_ELASTIC_WINDOW`-second rolling window, with exponential
  backoff (base `PADDLE_ELASTIC_BACKOFF`, cap 30s, ±50% jitter) so a
  crash-looping job backs off the coordinator instead of hammering it.
- **preemption notice**: SIGTERM/SIGINT to the manager is forwarded to
  every child (the cloud's 30s warning), children snapshot and exit, no
  relaunch is attempted, and the manager exits 143.
- **reshard notice** (ISSUE 11): with ``reshard="shrink"`` (or
  ``"shrink_expand"``; CLI ``--reshard``, env ``PADDLE_RESHARD_MODE``)
  the manager distinguishes *rank lost, quorum holds* from *world
  lost*: when a rank dies (or the watchdog puts it down) and at least
  ``PADDLE_RESHARD_QUORUM`` of the attempt's ranks survive, the dead
  rank is RETIRED instead of taking the job down — the manager appends
  a JSON notice line to every survivor's
  ``PADDLE_RESHARD_NOTICE_FILE`` and pokes it with SIGUSR1 (the same
  notice-channel pattern as the SIGTERM preemption protocol); survivors
  consume the notice at their next step boundary and reshard
  device-to-device (distributed/resharding.py). Below quorum — or with
  resharding off — the old semantics stand: teardown, budgeted
  relaunch, checkpoint reload. The expand half of ``shrink_expand`` is
  an in-process affair (a fresh OS rank cannot join a live
  jax.distributed world on this runtime): the launcher treats it as
  shrink and leaves re-absorption to jobs that inject returns in
  process.
- **embedded fleet monitor** (ISSUE 14): when an observability dir
  exists (``--log_dir`` or ``PADDLE_OBS_DIR``), a monitor thread at
  rank −1 tails every child's bus stream live — straggler ranking,
  online percentile digests, incident correlation
  (``observability/monitor.py``); kill attribution folds the active
  incident chain in, and the final incident/snapshot rows are flushed
  before the manager returns. ``PADDLE_MON=0`` disables.
- **embedded co-tenancy controller** (ISSUE 16): ``PADDLE_CTL=dryrun``
  (or ``controller="dryrun"``) starts the lend/reclaim state machine
  (``distributed/fleet_controller.py``) next to the monitor at
  rank −1. The launcher runs it journal-only — decisions, hysteresis,
  and the crash-recoverable ctl_lend/ctl_reclaim journal are real;
  actuation callbacks are not wired (training steps and serving
  engines live in the children; in-process co-tenants construct
  ``FleetController`` themselves with lend/reclaim callbacks).
- **live lend plane** (ISSUE 20): ``PADDLE_CTL=live`` wires the
  controller's :class:`~.fleet_controller.PhaseActuators` to a file
  protocol against the children (:class:`_LiveLendPlane`): a committed
  ``ctl_lend`` drives the lent dp row through depart (a role-carrying
  "lend" reshard notice — survivors shrink in place, the named rank
  reads its new job), deliver (the child loads the
  ``PADDLE_CTL_SERVE_CKPT`` quantized checkpoint, ack deadline
  ``PADDLE_CTL_PHASE_TIMEOUT_S``), and join (the child's serving
  mailbox comes up under ``PADDLE_CTL_SERVE_DIR``); ``ctl_reclaim``
  reverses it (drain marker → drained ack → leave → a "reclaim"
  notice rejoins the row, one ledger-attributed recompile). Every
  phase is its own fsync'd journal pair; a crash at any point recovers
  probe-or-rollback from the journal alone. A LENT rank dying while
  serving (the ``serve:lent_worker_crash`` fault) is a serving-plane
  event, not a training failure: the launcher journals a FORCED
  reclaim — ownership returns to the training plane, where the dead
  process then takes the standard rank-loss path.
"""
from __future__ import annotations

import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from . import comm_monitor  # stdlib-pure: safe for the launcher process

try:  # telemetry bus (stdlib-pure too); tolerate exotic standalone loads
    from ..observability import bus as _obs_bus
except ImportError:  # pragma: no cover - package always carries it
    _obs_bus = None

try:  # the live fleet monitor (ISSUE 14, stdlib-pure as well)
    from ..observability import monitor as _obs_monitor
except ImportError:  # pragma: no cover - package always carries it
    _obs_monitor = None

try:  # the train-serve co-tenancy controller (ISSUE 16, stdlib-pure)
    from . import fleet_controller as _fleet_ctl
except ImportError:  # pragma: no cover - package always carries it
    _fleet_ctl = None


def _emit(kind: str, **payload) -> None:
    """Launcher-side bus event (rank -1). Lands only when the operator
    exported PADDLE_OBS_DIR/PADDLE_OBS_BUS_FILE for the manager process;
    the per-rank child streams are provisioned independently in _spawn."""
    if _obs_bus is not None:
        _obs_bus.emit(kind, payload, rank=-1)

__all__ = ["ElasticManager", "RankProc", "heartbeat",
           "install_preempt_notice", "restore_preempt_notice", "HUNG_RC"]

_HEARTBEAT_ENV = "PADDLE_HEARTBEAT_FILE"
_WATCHDOG_ENV = "PADDLE_WATCHDOG_TIMEOUT"
_GRACE_ENV = "PADDLE_WATCHDOG_GRACE"
_BACKOFF_ENV = "PADDLE_ELASTIC_BACKOFF"
_WINDOW_ENV = "PADDLE_ELASTIC_WINDOW"
_LOGDIR_ENV = "PADDLE_LOG_DIR"
_RESHARD_MODE_ENV = "PADDLE_RESHARD_MODE"
_RESHARD_QUORUM_ENV = "PADDLE_RESHARD_QUORUM"
_RESHARD_NOTICE_ENV = "PADDLE_RESHARD_NOTICE_FILE"
_MON_ENV = "PADDLE_MON"
_CTL_ENV = "PADDLE_CTL"

#: exit code the manager reports when the watchdog had to put a rank down
HUNG_RC = 98
#: exit code after a propagated preemption notice (128 + SIGTERM)
PREEMPT_RC = 143


def heartbeat() -> None:
    """Touch this rank's heartbeat file (no-op outside the runner).

    Cheap enough to call per batch; the watchdog only compares mtimes.
    """
    path = os.environ.get(_HEARTBEAT_ENV)
    if not path:
        return
    try:
        with open(path, "a"):
            pass
        os.utime(path, None)
    except OSError:
        pass  # a lost heartbeat must never kill the trainer itself


def install_preempt_notice(on_notice: Callable[[], None]):
    """Install a SIGTERM handler that invokes `on_notice()` — the shared
    trainer-side half of the preemption protocol (TrainEpochRange and
    hapi.TerminateOnPreempt both use it). Returns the previous handler
    for :func:`restore_preempt_notice`, or None when not installable
    (non-main thread / restricted runtime)."""
    if threading.current_thread() is not threading.main_thread():
        return None

    def _handler(signum, frame):
        try:
            # the preemption notice is one of the flight recorder's dump
            # triggers: capture the collective stream before snapshotting
            comm_monitor.dump_flight_recorder("sigterm")
        except Exception:
            pass
        on_notice()

    try:
        return signal.signal(signal.SIGTERM, _handler)
    except (ValueError, OSError):
        return None


def restore_preempt_notice(old) -> None:
    if old is not None:
        signal.signal(signal.SIGTERM, old)


class RankProc:
    """One spawned rank (launch_utils.py TrainerProc analog)."""

    __slots__ = ("proc", "rank", "hb_path", "log_path", "log_file",
                 "ev_path", "guard_ev_path", "notice_path")

    def __init__(self, proc, rank, hb_path, log_path=None, log_file=None,
                 ev_path=None, guard_ev_path=None, notice_path=None):
        self.proc = proc
        self.rank = rank
        self.hb_path = hb_path
        self.log_path = log_path
        self.log_file = log_file
        self.ev_path = ev_path
        self.guard_ev_path = guard_ev_path
        self.notice_path = notice_path


class _LiveLendPlane:
    """The launcher side of the live lend plane (ISSUE 20): phase
    actuators driving CHILD processes over a file protocol, no shared
    memory with them.

    The contract per phase (all acks land in the lend dir the notice
    row names as ``ack_dir``; the launcher waits at most
    ``PADDLE_CTL_PHASE_TIMEOUT_S`` per phase, default 30 s):

    - **depart**: a role-carrying ``lend`` reshard notice goes to every
      live rank. Survivors fold it like a departure at their next step
      boundary (PR 11 — no relaunch); the NAMED rank stops training
      and acks ``rank<r>.departed``.
    - **deliver**: the lent rank loads the serving checkpoint the
      notice named (``PADDLE_CTL_SERVE_CKPT``, the PR-18
      ``load_quantized`` resident path) and acks ``rank<r>.delivered``
      (payload: its ``load_ms``). The deadline bounds a wedged load.
    - **join**: the rank's serving mailbox worker comes up under the
      notice's ``serve_dir`` (``PADDLE_CTL_SERVE_DIR``) and acks
      ``rank<r>.serving`` — the marker a router-side co-tenant polls
      before ``add_host``/``register_capacity`` admits traffic into
      the new worker.
    - **drain**: the launcher writes ``rank<r>.drain``; the worker
      stops taking new mailbox work, finishes what it holds (the PR-14
      zero-drop drain; PR-16 migrates what cannot finish) and acks
      ``rank<r>.drained``.
    - **leave**: serving teardown — the worker retires its mailbox and
      acks ``rank<r>.left``.
    - **rejoin**: a ``reclaim`` notice returns the row to the training
      mesh (survivors expand at a step boundary — the one
      ledger-attributed recompile); the rank acks ``rank<r>.rejoined``
      and the lend-dir state for it is cleared.

    ``probe``/``rollback`` close the crash loop: probe answers "is the
    rank alive AND past its serving ack" from the markers + the
    process table; rollback converges a half-done ladder to what the
    journal says — a failed lend re-sends the ``reclaim`` notice (a
    survivor that never consumed the lend nets the two rows out), a
    failed reclaim cancels the drain marker so the row stays serving.
    """

    __slots__ = ("mgr", "timeout", "ckpt", "serve_dir")

    def __init__(self, mgr: "ElasticManager"):
        self.mgr = mgr
        raw = os.environ.get("PADDLE_CTL_PHASE_TIMEOUT_S", "")
        try:
            self.timeout = float(raw) if raw.strip() else 30.0
        except ValueError:
            self.timeout = 30.0
        self.ckpt = os.environ.get("PADDLE_CTL_SERVE_CKPT") or None
        self.serve_dir = os.environ.get("PADDLE_CTL_SERVE_DIR") or None

    # -- file protocol ----------------------------------------------------
    def lend_dir(self) -> str:
        d = os.path.join(self.mgr._run_dir, "lend")
        os.makedirs(d, exist_ok=True)
        return d

    def _marker(self, rank: int, state: str) -> str:
        return os.path.join(self.lend_dir(), f"rank{rank}.{state}")

    def clear(self, rank: int) -> None:
        for state in ("departed", "delivered", "serving", "drain",
                      "drained", "left", "rejoined"):
            try:
                os.unlink(self._marker(rank, state))
            except OSError:
                pass

    def _live(self) -> List[RankProc]:
        return [rp for rp in self.mgr._procs if rp.proc.poll() is None]

    def _wait_ack(self, rank: int, state: str, phase: str) -> None:
        path = self._marker(rank, state)
        deadline = time.monotonic() + self.timeout
        while time.monotonic() < deadline:
            if os.path.exists(path):
                return
            rp = self.mgr._rank_proc(rank)
            if rp is None or rp.proc.poll() is not None:
                raise RuntimeError(
                    f"live lend {phase}: rank {rank} died before its "
                    f"{state} ack")
            time.sleep(0.02)
        raise TimeoutError(
            f"live lend {phase}: rank {rank} gave no {state} ack "
            f"within {self.timeout}s")

    def _notice_extra(self) -> dict:
        return {"ack_dir": self.lend_dir(), "ckpt": self.ckpt,
                "serve_dir": self.serve_dir}

    # -- the lend ladder --------------------------------------------------
    def depart(self, rank: int, samp) -> None:
        self.clear(rank)  # stale acks from a prior cycle must not
        # satisfy this ladder's waits
        self.mgr._notify_reshard("lend", [rank], self._live(),
                                 extra=self._notice_extra())
        self._wait_ack(rank, "departed", "depart")

    def deliver(self, rank: int, samp) -> None:
        # the load itself runs in the child (PR-18 load_quantized off
        # the resident .pdqparams); this side holds the DEADLINE — a
        # wedged weight load aborts the transition instead of leaving
        # the row neither training nor serving
        self._wait_ack(rank, "delivered", "deliver")

    def join(self, rank: int, samp) -> None:
        self._wait_ack(rank, "serving", "join")

    # -- the reclaim ladder -----------------------------------------------
    def drain(self, rank: int, samp) -> None:
        with open(self._marker(rank, "drain"), "w"):
            pass
        self._wait_ack(rank, "drained", "drain")

    def leave(self, rank: int, samp) -> None:
        self._wait_ack(rank, "left", "leave")

    def rejoin(self, rank: int, samp) -> None:
        self.mgr._notify_reshard("reclaim", [rank], self._live(),
                                 extra=self._notice_extra())
        self._wait_ack(rank, "rejoined", "rejoin")
        self.clear(rank)

    # -- crash loop -------------------------------------------------------
    def probe(self, rank: int) -> bool:
        rp = self.mgr._rank_proc(rank)
        return (rp is not None and rp.proc.poll() is None
                and os.path.exists(self._marker(rank, "serving"))
                and not os.path.exists(self._marker(rank, "left")))

    def rollback(self, verb: str, stage, completed, ranks) -> None:
        for rank in ranks:
            if verb == "lend":
                # converge to training ownership: the reclaim notice
                # undoes the lend for everyone — a survivor that never
                # consumed the lend row nets the pair out in order
                # (resharding folds events sequentially), the named
                # rank drops its serve role
                self.mgr._notify_reshard(
                    "reclaim", [rank], self._live(),
                    extra=self._notice_extra())
                self.clear(rank)
            else:
                # reclaim failed mid-ladder: the journal still says
                # LENT — cancel the drain so the row keeps serving
                try:
                    os.unlink(self._marker(rank, "drain"))
                except OSError:
                    pass

    def actuators(self):
        from .fleet_controller import PhaseActuators

        return PhaseActuators(
            depart=self.depart, deliver=self.deliver, join=self.join,
            drain=self.drain, leave=self.leave, rejoin=self.rejoin,
            probe=self.probe, rollback=self.rollback)


class ElasticManager:
    """Spawn this node's ranks and keep the job alive across failures.

    `envs` is one fully-populated environment dict per local rank (see
    launch.build_cluster_env); the manager adds `PADDLE_LAUNCH_ATTEMPT`
    and `PADDLE_HEARTBEAT_FILE` on top.
    """

    def __init__(self, script: str, script_args: List[str],
                 envs: List[Dict[str, str]], backend: Optional[str] = None,
                 max_restarts: int = 0,
                 watchdog_timeout: Optional[float] = None,
                 grace: Optional[float] = None,
                 backoff_base: Optional[float] = None,
                 backoff_cap: float = 30.0,
                 restart_window: Optional[float] = None,
                 log_dir: Optional[str] = None,
                 poll_interval: float = 0.1,
                 coll_timeout: Optional[float] = None,
                 reshard: Optional[str] = None,
                 reshard_quorum: Optional[float] = None,
                 monitor: Optional[bool] = None,
                 controller: Optional[str] = None):
        def _envf(name, default):
            raw = os.environ.get(name, "")
            return float(raw) if raw.strip() else default

        self.script = script
        self.script_args = list(script_args)
        self.envs = envs
        self.backend = backend
        self.max_restarts = int(max_restarts)
        self.watchdog_timeout = (
            watchdog_timeout if watchdog_timeout is not None
            else _envf(_WATCHDOG_ENV, 0.0))
        self.grace = grace if grace is not None else _envf(_GRACE_ENV, 10.0)
        self.backoff_base = (backoff_base if backoff_base is not None
                             else _envf(_BACKOFF_ENV, 0.5))
        self.backoff_cap = backoff_cap
        self.restart_window = (restart_window if restart_window is not None
                               else _envf(_WINDOW_ENV, 3600.0))
        self.log_dir = log_dir or os.environ.get(_LOGDIR_ENV) or None
        self.poll_interval = poll_interval
        self.coll_timeout = coll_timeout
        self.reshard = (reshard if reshard is not None
                        else os.environ.get(_RESHARD_MODE_ENV, "off")) \
            .strip().lower() or "off"
        if self.reshard not in ("off", "shrink", "shrink_expand"):
            raise ValueError(
                f"reshard={self.reshard!r}: want off|shrink|shrink_expand")
        self.reshard_quorum = (reshard_quorum if reshard_quorum is not None
                               else _envf(_RESHARD_QUORUM_ENV, 0.5))
        if monitor is None:
            monitor = os.environ.get(_MON_ENV, "1").strip().lower() \
                not in ("0", "false", "off")
        self.monitor_enabled = bool(monitor)
        #: the embedded live fleet monitor (rank −1, next to the
        #: watchdog — ISSUE 14); started at first spawn when an obs
        #: dir exists, so kill attribution can ask it for incident
        #: context and the incident rows land before the manager exits
        self.monitor = None
        self._mon_thread: Optional[threading.Thread] = None
        self._mon_stop = threading.Event()
        if controller is None:
            controller = os.environ.get(_CTL_ENV, "off")
        self.controller_mode = (controller or "off").strip().lower() or "off"
        if self.controller_mode not in ("off", "dryrun", "live"):
            raise ValueError(
                f"controller={self.controller_mode!r}: want "
                f"off|dryrun|live")
        if self.controller_mode == "live" and self.reshard == "off":
            raise ValueError(
                "controller='live' needs reshard='shrink'/"
                "'shrink_expand': the depart/rejoin phases ride the "
                "reshard notice channel")
        #: the embedded co-tenancy controller (ISSUE 16): rides next to
        #: the monitor at rank -1, consuming its serving aggregates.
        #: ``dryrun`` journals decisions without actuating; ``live``
        #: (ISSUE 20) wires the _LiveLendPlane phase actuators so a
        #: committed decision really migrates the rank between jobs
        self.controller = None
        self._ctl_thread: Optional[threading.Thread] = None
        self._ctl_stop = threading.Event()
        self._lend_plane = None
        self._run_dir = None          # heartbeat-file home, made lazily
        self._procs: List[RankProc] = []
        self._retired: List[RankProc] = []  # resharded-away ranks
        self._spawn_total = 0         # this attempt's quorum denominator
        self._restarts = deque()      # monotonic stamps of past relaunches
        self._preempted = False

    # -- spawning ---------------------------------------------------------
    def _spawn(self, attempt: int) -> None:
        if self._run_dir is None:
            self._run_dir = tempfile.mkdtemp(prefix="pdtpu_elastic_")
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
        # comm-monitor plumbing: a per-ATTEMPT sync dir (stale round files
        # from a previous incarnation must not satisfy fresh barriers), a
        # per-rank event file the kill attribution reads, and the dump
        # destination next to the workerlogs
        sync_dir = os.path.join(self._run_dir, f"collsync.{attempt}")
        os.makedirs(sync_dir, exist_ok=True)
        debug_dir = self.log_dir or self._run_dir
        # telemetry-bus home for the children (observability/bus.py):
        # next to the workerlogs so tools/timeline.py finds every rank's
        # stream beside the flight-recorder dumps. Only a durable
        # destination qualifies — the tmp run dir is removed at manager
        # exit, so without --log_dir (or an operator-exported
        # PADDLE_OBS_DIR riding in via the env dicts) the bus stays off.
        obs_dir = os.environ.get("PADDLE_OBS_DIR") or self.log_dir
        self._procs = []
        self._retired = []
        for env in self.envs:
            env = dict(env)
            if self.backend:
                # in the child's environment before it imports jax:
                # the only thing that keeps a cpu rank off the chip
                env["JAX_PLATFORMS"] = self.backend
            env["PADDLE_LAUNCH_ATTEMPT"] = str(attempt)
            rank = int(env.get("PADDLE_TRAINER_ID", "0"))
            hb = os.path.join(self._run_dir, f"hb.{rank}")
            env[_HEARTBEAT_ENV] = hb
            # pre-touch so the stale clock starts at spawn, not epoch 1
            with open(hb, "a"):
                pass
            os.utime(hb, None)
            ev = os.path.join(self._run_dir, f"collev.{rank}")
            with open(ev, "w"):
                pass  # fresh per attempt: attribution reflects THIS run
            env["PADDLE_COLL_EVENT_FILE"] = ev
            # the numerical guard's event stream (train_guard.py): same
            # JSONL contract, read for kill attribution alongside the
            # collective events
            gev = os.path.join(self._run_dir, f"guardev.{rank}")
            with open(gev, "w"):
                pass
            env["PADDLE_GUARD_EVENT_FILE"] = gev
            notice = None
            if self.reshard != "off":
                # per-attempt reshard-notice channel (resharding.py
                # consumes it at step boundaries after a SIGUSR1 poke)
                notice = os.path.join(
                    self._run_dir, f"reshard.notice.{attempt}.{rank}")
                with open(notice, "w"):
                    pass
                env[_RESHARD_NOTICE_ENV] = notice
            env["PADDLE_COLL_SYNC_DIR"] = sync_dir
            env.setdefault("PADDLE_COLL_DEBUG_DIR", debug_dir)
            if obs_dir:
                env.setdefault("PADDLE_OBS_DIR", obs_dir)
            if self.coll_timeout is not None:
                env["PADDLE_COLL_TIMEOUT"] = str(self.coll_timeout)
            log_path = log_file = None
            if self.log_dir:
                log_path = os.path.join(self.log_dir, f"workerlog.{rank}")
                log_file = open(log_path, "ab", buffering=0)
                log_file.write(
                    f"==== attempt {attempt} rank {rank} ====\n".encode())
            p = subprocess.Popen(
                [sys.executable, self.script] + self.script_args,
                env=env, stdout=log_file, stderr=log_file)
            self._procs.append(RankProc(p, rank, hb, log_path, log_file,
                                        ev_path=ev, guard_ev_path=gev,
                                        notice_path=notice))
        self._spawn_total = len(self._procs)
        self._start_monitor(obs_dir)
        self._start_controller(obs_dir)
        _emit("elastic_spawn", attempt=attempt,
              ranks=[rp.rank for rp in self._procs],
              pids=[rp.proc.pid for rp in self._procs],
              obs_dir=obs_dir)

    # -- embedded fleet monitor (ISSUE 14) --------------------------------
    def _start_monitor(self, obs_dir: Optional[str]) -> None:
        """Tail the children's bus streams from the launcher (rank −1,
        next to the watchdog): straggler ranking, percentile digests,
        and incident correlation DURING the run. One monitor for the
        whole job — relaunch attempts append to the same streams."""
        if (self.monitor is not None or not self.monitor_enabled
                or not obs_dir or _obs_monitor is None):
            return
        try:
            self.monitor = _obs_monitor.FleetMonitor(obs_dir, emit=True)
        except Exception:  # noqa: BLE001 — monitoring never blocks spawn
            self.monitor = None
            return

        def _loop():
            while not self._mon_stop.wait(self.monitor.poll_s):
                try:
                    self.monitor.poll()
                    self.monitor.maybe_snapshot()
                except Exception:  # noqa: BLE001 — keep tailing
                    pass

        self._mon_thread = threading.Thread(
            target=_loop, name="pdtpu-fleet-monitor", daemon=True)
        self._mon_thread.start()

    def _stop_monitor(self) -> None:
        """Final drain BEFORE the manager returns: the open incident is
        force-closed and written, so a failure in the job's last window
        still gets its `incident` row."""
        if self.monitor is None:
            return
        self._mon_stop.set()
        if self._mon_thread is not None:
            self._mon_thread.join(timeout=5.0)
        try:
            self.monitor.finalize()
        except Exception:  # noqa: BLE001 — diagnostics stay best-effort
            pass

    # -- embedded co-tenancy controller (ISSUE 16) ------------------------
    def _start_controller(self, obs_dir: Optional[str]) -> None:
        """Run the lend/reclaim state machine at rank -1, next to the
        monitor it feeds from. Every window samples the monitor's
        serving aggregates, the hysteresis policy decides, decisions
        journal to the launcher bus stream (crash-recoverable). In
        ``dryrun`` no actuation is wired — ownership changes are
        declared, not executed; in ``live`` (ISSUE 20) the
        _LiveLendPlane phase actuators drive the children through the
        depart/deliver/join (and drain/leave/rejoin) ladders for real.
        One controller per job; relaunch attempts keep the journal, so
        recovery re-derives lent state — and rolls half-done ladders
        back — instead of guessing."""
        if (self.controller is not None or self.controller_mode == "off"
                or not obs_dir or _fleet_ctl is None
                or self.monitor is None):
            return
        donors = sorted(rp.rank for rp in self._procs)
        actuators = None
        if self.controller_mode == "live":
            # ISSUE 20: wire the real phase ladder — a committed
            # decision now MOVES the rank between jobs, and the
            # controller's recovery can probe/rollback the children
            self._lend_plane = _LiveLendPlane(self)
            actuators = self._lend_plane.actuators()
        try:
            self.controller = _fleet_ctl.FleetController(
                obs_dir, monitor=self.monitor, donor_ranks=donors,
                actuators=actuators)
        except Exception:  # noqa: BLE001 — the controller never blocks spawn
            self.controller = None
            return

        def _loop():
            while not self._ctl_stop.wait(self.controller.cfg.window_s):
                try:
                    self.controller.window()
                except Exception:  # noqa: BLE001 — keep deciding
                    pass

        self._ctl_thread = threading.Thread(
            target=_loop, name="pdtpu-fleet-controller", daemon=True)
        self._ctl_thread.start()

    def _stop_controller(self) -> None:
        if self.controller is None:
            return
        self._ctl_stop.set()
        if self._ctl_thread is not None:
            self._ctl_thread.join(timeout=5.0)

    # -- teardown ---------------------------------------------------------
    def _kill_rank(self, rp: RankProc, why: str) -> None:
        """SIGTERM → grace → SIGKILL one rank."""
        if rp.proc.poll() is not None:
            return
        print(f"paddle_tpu.elastic: {why}; terminating rank {rp.rank} "
              f"(pid {rp.proc.pid}, grace {self.grace}s)",
              file=sys.stderr, flush=True)
        rp.proc.send_signal(signal.SIGTERM)
        try:
            rp.proc.wait(timeout=self.grace)
        except subprocess.TimeoutExpired:
            rp.proc.kill()
            rp.proc.wait()

    def _teardown(self, why: str) -> None:
        # signal everyone FIRST, then share one grace deadline — serial
        # per-rank waits would stretch teardown to N*grace and eat the
        # cloud's eviction window before later ranks could snapshot
        live = [rp for rp in self._procs if rp.proc.poll() is None]
        if live:
            print(f"paddle_tpu.elastic: {why}; terminating "
                  f"{len(live)} rank(s) (grace {self.grace}s)",
                  file=sys.stderr, flush=True)
            for rp in live:
                try:
                    rp.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
            deadline = time.monotonic() + self.grace
            for rp in live:
                try:
                    rp.proc.wait(max(deadline - time.monotonic(), 0))
                except subprocess.TimeoutExpired:
                    rp.proc.kill()
                    rp.proc.wait()
        for rp in self._procs + self._retired:
            if rp.log_file is not None:
                try:
                    rp.log_file.close()
                except OSError:
                    pass

    # -- kill attribution (comm_monitor event reader) ---------------------
    def _attribute(self, rp: RankProc, why: str) -> None:
        """Name the collective — or the numerical-guard verdict — behind
        a rank's death, when a monitor managed to write an event line
        before the end: a generic 'hung rank' becomes 'stalled in
        all_reduce(seq 5, ...)', a guard abort (rc=96) becomes
        'divergence: N consecutive bad steps (grads nonfinite, ...)'."""
        events = []
        for path in (rp.ev_path, rp.guard_ev_path):
            if path:
                events.extend(comm_monitor.read_events(path))
        # the embedded fleet monitor's incident context (ISSUE 14):
        # sitting next to the watchdog means the kill attribution sees
        # the cross-rank chain ("rank 3 recompile storm → dp collective
        # stall") for free — drain its streams once so events from the
        # dying rank's last seconds are in
        incident = None
        if self.monitor is not None:
            try:
                self.monitor.poll()
                incident = self.monitor.incident_context(rp.rank)
            except Exception:  # noqa: BLE001 — attribution best-effort
                incident = None
        if not events and not incident:
            return
        if events:
            ev = max(events, key=lambda e: e.get("time", 0.0))
            cause = ev.get("event", "?")
            what = (ev.get("detail") or ev.get("describe") or cause)
        else:
            cause, what = "incident", incident
        _emit("elastic_attribution", rank=rp.rank, why=why,
              cause=cause, detail=what, incident=incident)
        print(
            f"paddle_tpu.elastic: rank {rp.rank} {why} attributed to "
            f"{cause}: {what}"
            # when there were no monitor events, `what` already IS the
            # incident chain — don't print it twice
            + (f" [incident: {incident}]" if incident and events
               else ""),
            file=sys.stderr, flush=True)

    # -- reshard notice channel (quorum-holding rank loss) ----------------
    def _quorum_holds(self, n_alive: int) -> bool:
        if self.reshard == "off" or n_alive < 1:
            return False
        return (n_alive / max(self._spawn_total, 1)) >= self.reshard_quorum

    def _retire(self, rp: RankProc) -> None:
        """Drop a departed rank from the watch set without taking the
        job down (its workerlog closes at teardown like everyone's)."""
        self._procs.remove(rp)
        self._retired.append(rp)

    def _rank_proc(self, rank: int) -> Optional[RankProc]:
        for rp in self._procs:
            if rp.rank == rank:
                return rp
        return None

    def _notify_reshard(self, event: str, ranks: List[int],
                        survivors: List[RankProc],
                        extra: Optional[dict] = None) -> None:
        """Append one notice row to every survivor's notice file and
        poke it with SIGUSR1 (resharding.install_reshard_notice) — the
        step-boundary poller does the rest in-process. ``extra`` rides
        extra row fields (the live lend plane's ack_dir/ckpt/serve_dir
        — ISSUE 20)."""
        import json

        row = {"event": event, "ranks": ranks, "time": time.time(),
               "survivors": [s.rank for s in survivors]}
        if extra:
            row.update(extra)
        for rp in survivors:
            if rp.notice_path:
                try:
                    with open(rp.notice_path, "a") as f:
                        f.write(json.dumps(row) + "\n")
                except OSError:
                    pass
            # the poke is prompt-pickup only, and only for ranks whose
            # handler is armed (the .armed marker from
            # resharding.install_reshard_notice): to an un-armed child
            # — still importing, first compile — the default SIGUSR1
            # disposition is TERMINATION. Un-poked survivors still see
            # the notice at their next step-boundary file poll.
            if rp.notice_path and os.path.exists(
                    rp.notice_path + ".armed"):
                try:
                    rp.proc.send_signal(signal.SIGUSR1)
                except (OSError, AttributeError):
                    pass
        _emit("elastic_reshard_notice", event=event, ranks=ranks,
              survivors=[s.rank for s in survivors],
              quorum=self.reshard_quorum)
        print(f"paddle_tpu.elastic: rank(s) {ranks} {event}ed; quorum "
              f"holds ({len(survivors)}/{self._spawn_total}) — reshard "
              f"notice sent, job continues",
              file=sys.stderr, flush=True)

    # -- the watch loop (launch_utils.py:996-1118) ------------------------
    def _watch(self) -> int:
        rc = 0
        while True:
            alive = []
            failed = []
            for rp in self._procs:
                code = rp.proc.poll()
                if code is None:
                    alive.append(rp)
                elif code != 0:
                    failed.append((rp, code))
            for rp, code in failed:
                # a LENT rank dying is a serving-plane event (ISSUE 20,
                # the serve:lent_worker_crash fault): the row already
                # left the training mesh at depart, so survivors need
                # no new notice — journal the FORCED reclaim (ownership
                # back to the training plane, never half-lent) and let
                # the router's failover re-home its in-flight requests
                if (self.controller is not None
                        and rp.rank in self.controller.lent):
                    self._attribute(rp, f"lent worker death (rc={code})")
                    self._retire(rp)
                    if self._lend_plane is not None:
                        self._lend_plane.clear(rp.rank)
                    try:
                        self.controller.force_reclaim(
                            rp.rank, f"lent_worker_crash rc={code}")
                    except Exception:  # noqa: BLE001 — journal-only path
                        pass
                    continue
                # rank lost: an in-job event when the quorum holds and
                # resharding is on; a job failure otherwise
                if self._quorum_holds(len(alive)):
                    self._attribute(rp, f"departure (rc={code})")
                    self._retire(rp)
                    self._notify_reshard("depart", [rp.rank], alive)
                elif rc == 0:
                    rc = code  # first failure wins; tear the job down
                    self._attribute(rp, f"failure (rc={code})")
            if rc != 0 or not alive:
                break
            if self._preempted:
                # notice already forwarded by the signal handler; give
                # the children their grace window to snapshot + exit
                self._teardown("preemption notice")
                return PREEMPT_RC
            if self.watchdog_timeout > 0:
                now = time.time()
                for rp in alive:
                    try:
                        age = now - os.path.getmtime(rp.hb_path)
                    except OSError:
                        continue  # heartbeat file raced away; skip a beat
                    if age > self.watchdog_timeout:
                        _emit("elastic_watchdog_kill", rank=rp.rank,
                              stale_s=round(age, 1),
                              timeout_s=self.watchdog_timeout)
                        self._kill_rank(
                            rp, f"rank {rp.rank} heartbeat stale "
                                f"{age:.1f}s > {self.watchdog_timeout}s")
                        # a rank wedged in a collective stops heartbeating
                        # too: its monitor's event line says WHERE
                        self._attribute(rp, "watchdog kill")
                        survivors = [s for s in alive if s is not rp]
                        if self._quorum_holds(len(survivors)):
                            # a hung rank is put down, then treated as a
                            # departure: survivors reshard, no relaunch
                            self._retire(rp)
                            self._notify_reshard("depart", [rp.rank],
                                                 survivors)
                        else:
                            rc = HUNG_RC
                        break
                if rc != 0:
                    break
            time.sleep(self.poll_interval)
        self._teardown("peer failure" if rc else "job done")
        return rc  # 0 here means every rank exited clean (even post-notice)

    # -- restart policy ---------------------------------------------------
    def _backoff_delay(self, n_recent: int) -> float:
        """Exponential in the number of recent restarts, capped, with
        ±50% jitter so restarting hosts don't stampede the coordinator."""
        base = min(self.backoff_cap,
                   self.backoff_base * (2.0 ** max(n_recent - 1, 0)))
        return base * (0.5 + random.random())

    def _budget_left(self) -> bool:
        now = time.monotonic()
        while self._restarts and now - self._restarts[0] > self.restart_window:
            self._restarts.popleft()
        return len(self._restarts) < self.max_restarts

    # -- signals ----------------------------------------------------------
    def _on_notice(self, signum, frame):
        self._preempted = True
        for rp in self._procs:
            if rp.proc.poll() is None:
                try:
                    rp.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass

    def _install_handlers(self):
        old = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                old[sig] = signal.signal(sig, self._on_notice)
        except ValueError:
            pass  # not the main thread; the caller owns signal routing
        return old

    # -- the job ----------------------------------------------------------
    def run(self) -> int:
        old_handlers = self._install_handlers()
        attempt = 0
        try:
            while True:
                self._spawn(attempt)
                rc = self._watch()
                if self._preempted:
                    _emit("elastic_preempt", attempt=attempt, rc=rc)
                    # the notice wins even over a clean rank exit: the
                    # host is going away, so report "interrupted" (143)
                    # and let the next incarnation's restore() decide
                    # whether anything is actually left to do
                    return rc or PREEMPT_RC
                if rc == 0:
                    return 0
                if not self._budget_left():
                    print(
                        f"paddle_tpu.elastic: restart budget exhausted "
                        f"({self.max_restarts} per "
                        f"{self.restart_window:.0f}s); giving up rc={rc}",
                        file=sys.stderr, flush=True)
                    return rc
                self._restarts.append(time.monotonic())
                delay = self._backoff_delay(len(self._restarts))
                _emit("elastic_relaunch", attempt=attempt, rc=rc,
                      delay_s=round(delay, 2),
                      restarts_left=self.max_restarts - len(self._restarts))
                print(
                    f"paddle_tpu.elastic: attempt {attempt} failed rc={rc}; "
                    f"relaunching in {delay:.2f}s "
                    f"({self.max_restarts - len(self._restarts)} restarts "
                    f"left in window)", file=sys.stderr, flush=True)
                time.sleep(delay)
                if self._preempted:
                    # notice arrived during the backoff nap: don't burn
                    # the eviction window on a doomed respawn
                    return PREEMPT_RC
                attempt += 1
        finally:
            self._stop_controller()  # last decision journals first
            self._stop_monitor()  # incident rows land BEFORE exit
            self._teardown("manager exit")
            for sig, h in old_handlers.items():
                signal.signal(sig, h)
            if self._run_dir is not None:
                shutil.rmtree(self._run_dir, ignore_errors=True)
