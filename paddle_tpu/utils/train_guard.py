"""Numerical guardrails for the compiled training step.

The reference framework's numerical tripwire is ``FLAGS_check_nan_inf``
(platform/flags.cc:44 -> CheckVarHasNanOrInf): a per-op, host-syncing
debug flag that only exists in eager mode. The fused ``TrainStep`` XLA
program — the hot path actual training runs through — had zero
protection: one overflowed step silently poisons every donated parameter
buffer in HBM, and the first symptom is a NaN loss thousands of steps
later. At pod scale this is the dominant non-hardware failure mode
(MLPerf-on-pods, PAPERS.md); PR 1/2 built process- and comms-level
rescue (elastic relaunch, collective flight recorder) with no numerical
counterpart.

This module is the numerical counterpart. Three pieces:

- **in-graph sentinel** (``grad_health`` / ``update_guard_state``, used
  by ``jit.TrainStep`` and ``fleet.LocalSGDStep``): every compiled step
  also computes a tiny health word — ``isfinite(loss)``, a single fused
  square-sum reduction over all grads (one extra read; NaN/Inf anywhere
  propagates into the global grad-norm), optionally
  ``isfinite(new_params)`` — and when the word trips, the step becomes a
  no-op via ``jnp.where`` masking: params and optimizer state pass
  through unchanged (donation preserved), the fp16 loss scaler counts a
  bad step and backs off. The guard's policy counters (consecutive bad
  steps, loss EWMA, totals) ride the program as a tiny f32 carry (not
  donated — the host monitor's deferred read must outlive the next
  dispatch), so
  the host never syncs per step.
- **host monitor** (:class:`TrainGuard`): reads the device guard state
  every ``PADDLE_GUARD_SYNC_EVERY`` steps through an async prefetch
  (``copy_to_host_async`` now, read one interval later — a blocking
  read would stall the dispatch queue behind the device). Skipped steps are no-ops, so a bounded observation lag loses
  nothing. Past ``PADDLE_GUARD_MAX_SKIPS`` consecutive bad steps the
  monitor *rescues*: restore the last CRC-verified ``auto_checkpoint``
  generation (which PR-this also carries scaler + guard state through),
  or — mode ``abort`` — emit a machine-readable event and exit with
  :data:`GUARD_ABORT_RC` so the ElasticManager attributes the kill,
  exactly like a collective timeout.
- **attribution capture**: the monitor keeps a small ring of recent step
  records (RNG key + input/label arrays); on the first observed bad
  step it dumps the faulting step's bundle (params, batch, key) to
  ``PADDLE_GUARD_DUMP_DIR`` so ``tools/replay_step.py`` can re-execute
  it eagerly under ``FLAGS_check_nan_inf`` and name the first op that
  produced the NaN — "loss is NaN" becomes a file:op diagnosis.

Knobs (all documented in the README "Training guardrails" table)::

    PADDLE_GUARD_MODE          off | skip (default) | abort
    PADDLE_GUARD_MAX_SKIPS     consecutive bad steps before rescue (8)
    PADDLE_GUARD_SYNC_EVERY    host observation interval, steps (4)
    PADDLE_GUARD_CHECK_PARAMS  1 = also isfinite-check updated params
    PADDLE_GUARD_SPIKE_FACTOR  loss > factor * EWMA counts as divergence
                               (0 = spike detection off)
    PADDLE_GUARD_EWMA          loss EWMA decay (0.9)
    PADDLE_GUARD_SPIKE_WARMUP  healthy steps before spikes count (20)
    PADDLE_GUARD_EVENT_FILE    JSONL event stream (set by the launcher)
    PADDLE_GUARD_DUMP_DIR      where replay bundles land (off when unset)
"""
from __future__ import annotations

import os
import sys
import time
import weakref
import zlib
from collections import deque
from typing import Dict, List, Optional

__all__ = [
    "TrainGuard", "GuardDivergenceError", "GUARD_ABORT_RC", "GUARD_LEN",
    "guard_mode", "init_guard_state", "grad_health", "update_guard_state",
    "mask_step", "emit_event", "set_rescue_target",
]

_MODE_ENV = "PADDLE_GUARD_MODE"
_MAX_SKIPS_ENV = "PADDLE_GUARD_MAX_SKIPS"
_SYNC_ENV = "PADDLE_GUARD_SYNC_EVERY"
_CHECK_PARAMS_ENV = "PADDLE_GUARD_CHECK_PARAMS"
_SPIKE_ENV = "PADDLE_GUARD_SPIKE_FACTOR"
_EWMA_ENV = "PADDLE_GUARD_EWMA"
_WARMUP_ENV = "PADDLE_GUARD_SPIKE_WARMUP"
_EVENT_ENV = "PADDLE_GUARD_EVENT_FILE"
_DUMP_ENV = "PADDLE_GUARD_DUMP_DIR"

#: exit code of a guard abort (97 = collective timeout, 98 = launcher
#: watchdog verdict; 96 = the trainer's own numerical verdict)
GUARD_ABORT_RC = 96

#: guard-state vector layout (f32[GUARD_LEN], threaded through the step):
#: 0 consec_bad  1 total_skips  2 total_spikes  3 loss_ewma
#: 4 last_gnorm  5 last_health_bits  6 healthy_steps  7 last_loss
#: 8 gnorm_ewma  9 reserved
GUARD_LEN = 10

#: health-word bits
HEALTH_LOSS = 1      # loss nonfinite
HEALTH_GRAD = 2      # some gradient nonfinite (via the fused norm)
HEALTH_PARAM = 4     # some updated parameter nonfinite
HEALTH_SPIKE = 8     # finite, but loss spiked past factor * EWMA
HEALTH_GNORM = 16    # finite, but grad norm spiked past factor * EWMA


class GuardDivergenceError(RuntimeError):
    """Raised in ``skip`` mode when the consecutive-bad-step budget is
    exhausted and no auto_checkpoint rescue target is registered."""


def guard_mode() -> str:
    mode = os.environ.get(_MODE_ENV, "skip").strip().lower() or "skip"
    if mode not in ("off", "skip", "abort"):
        raise ValueError(
            f"{_MODE_ENV}={mode!r}: want one of off|skip|abort")
    return mode


def _envi(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    return int(raw) if raw.strip() else default


def _envf(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    return float(raw) if raw.strip() else default


# ---------------------------------------------------------------------------
# the pure, in-graph half (shared by TrainStep and LocalSGDStep)
# ---------------------------------------------------------------------------


def init_guard_state():
    """Fresh device guard-state vector (all zeros)."""
    import jax.numpy as jnp

    return jnp.zeros((GUARD_LEN,), jnp.float32)


def grad_health(loss, grads, new_params=None, check_params=None):
    """The sentinel reduction: (ok, health_bits, gnorm), all traced.

    ``gnorm`` is the global gradient norm sqrt(sum g^2) in f32 — ONE
    fused reduction pass over the grads; any NaN/Inf gradient element
    propagates into it, so ``isfinite(gnorm^2)`` doubles as the
    all-grads finite check without a second read. (A finite grad large
    enough to overflow f32 when squared, ~1e19, reads as nonfinite —
    at that magnitude the step is divergent either way.)
    """
    import jax.numpy as jnp

    if check_params is None:
        check_params = _envi(_CHECK_PARAMS_ENV, 0) != 0
    loss32 = jnp.asarray(loss, jnp.float32)
    loss_ok = jnp.isfinite(loss32).all()
    gs = [g for g in grads if g is not None]
    if gs:
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in gs)
        grad_ok = jnp.isfinite(sq)
        gnorm = jnp.sqrt(jnp.where(grad_ok, sq, 0.0))
    else:
        grad_ok = jnp.asarray(True)
        gnorm = jnp.asarray(0.0, jnp.float32)
    bits = (jnp.where(loss_ok, 0, HEALTH_LOSS)
            + jnp.where(grad_ok, 0, HEALTH_GRAD))
    if check_params and new_params is not None:
        p_ok = jnp.stack([
            jnp.isfinite(p).all() if jnp.issubdtype(p.dtype, jnp.inexact)
            else jnp.asarray(True)
            for p in new_params
        ]).all()
        bits = bits + jnp.where(p_ok, 0, HEALTH_PARAM)
    ok = bits == 0
    return ok, bits.astype(jnp.float32), gnorm


def update_guard_state(state, ok, bits, gnorm, loss):
    """Pure policy-counter update (traced; rides the step's carry).

    Spike detection (``PADDLE_GUARD_SPIKE_FACTOR`` > 0, after
    ``PADDLE_GUARD_SPIKE_WARMUP`` healthy steps seeded the EWMAs):

    - a finite **grad norm** above ``factor * gnorm_EWMA`` is masked
      like a nonfinite step (``ok_apply`` False). The loss can only
      reveal an exploded update one step AFTER it applied — the grad
      norm reveals it *before*, which is what keeps params (and the
      next auto_checkpoint generation) clean;
    - a finite **loss** above ``factor * loss_EWMA`` still applies
      (masking on a trailing indicator would skip the wrong step) but
      counts against the same consecutive-bad budget, so a divergence
      that never goes nonfinite still reaches the rescue path.

    Returns (new_state, ok_apply) — the caller masks with ok_apply.
    """
    import jax.numpy as jnp

    factor = _envf(_SPIKE_ENV, 0.0)
    decay = _envf(_EWMA_ENV, 0.9)
    warmup = _envi(_WARMUP_ENV, 20)
    (consec, t_skip, t_spike, ewma, _, prev_bits, healthy, _,
     g_ewma, _spare) = tuple(state)
    loss32 = jnp.asarray(loss, jnp.float32)
    if factor > 0.0:
        warmed = healthy >= warmup
        # the > 0 guards keep an unseeded EWMA (fresh start, or state
        # restored from a snapshot without one) from flagging everything
        spike = ok & warmed & (jnp.abs(ewma) > 0.0) \
            & (loss32 > factor * jnp.abs(ewma))
        g_spike = ok & warmed & (g_ewma > 0.0) \
            & (gnorm > factor * g_ewma)
    else:
        spike = jnp.asarray(False)
        g_spike = jnp.asarray(False)
    ok_apply = ok & ~g_spike
    bad = (~ok_apply) | spike
    consec = jnp.where(bad, consec + 1, 0.0)
    t_skip = t_skip + jnp.where(ok_apply, 0.0, 1.0)
    t_spike = t_spike + jnp.where(spike, 1.0, 0.0)
    good = ok_apply & ~spike
    seeded = healthy > 0
    ewma = jnp.where(
        good,
        jnp.where(seeded, decay * ewma + (1.0 - decay) * loss32, loss32),
        ewma,
    )
    g_ewma = jnp.where(
        good,
        jnp.where(seeded, decay * g_ewma + (1.0 - decay) * gnorm, gnorm),
        g_ewma,
    )
    healthy = healthy + jnp.where(good, 1.0, 0.0)
    bits = (bits + jnp.where(spike, float(HEALTH_SPIKE), 0.0)
            + jnp.where(g_spike, float(HEALTH_GNORM), 0.0))
    # the bits slot is sticky-bad: it names the most recent UNHEALTHY
    # step's health word, so a lazy observer still sees what tripped
    bits = jnp.where(bad, bits, prev_bits)
    new_state = jnp.stack([
        consec, t_skip, t_spike, ewma, gnorm, bits, healthy,
        jnp.where(jnp.isfinite(loss32), loss32, jnp.asarray(-1.0)),
        g_ewma, _spare,
    ])
    return new_state, ok_apply


def mask_step(ok, new_tree, old_tree):
    """Select new-vs-old leafwise on the traced ``ok`` scalar — the
    skip-and-rescue no-op: identical output layout/sharding, so buffer
    donation is preserved and a healthy step's values are bitwise what
    they would have been without the guard."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree
    )


# ---------------------------------------------------------------------------
# event stream (read by ElasticManager for kill attribution)
# ---------------------------------------------------------------------------


def emit_event(kind: str, **fields) -> None:
    """Emit one guard event through the telemetry bus
    (observability/bus.py). The legacy flat-format line still lands on
    ``PADDLE_GUARD_EVENT_FILE`` when the launcher set it (the
    ElasticManager's kill-attribution reader is unchanged); the unified
    schema row additionally lands on the per-rank bus stream when
    ``PADDLE_OBS_DIR``/``PADDLE_OBS_BUS_FILE`` is configured."""
    from ..observability import bus as _bus

    _bus.emit(kind, fields, step=fields.get("step"),
              legacy_env=_EVENT_ENV)


# ---------------------------------------------------------------------------
# rescue-target registry (auto_checkpoint announces itself here)
# ---------------------------------------------------------------------------

_rescue_ref = None
_active_guards: "weakref.WeakSet" = weakref.WeakSet()


def set_rescue_target(target) -> None:
    """Register the TrainEpochRange whose last-good generation a guard
    rollback restores (weakly held; cleared by passing None)."""
    global _rescue_ref
    _rescue_ref = None if target is None else weakref.ref(target)


def _rescue_target():
    return _rescue_ref() if _rescue_ref is not None else None


def divergence_active() -> bool:
    """Is any live guard inside a bad-step streak? auto_checkpoint asks
    before its periodic save, so a spiking-but-finite epoch (whose
    updates DID apply) is never committed as a 'last-good' generation —
    the snapshot a later rollback restores must predate the divergence.

    Only guards that actually STEPPED since the previous check are
    consulted (a retired step object kept alive by a stray reference
    must not veto another run's snapshots), and the read is
    side-effect-free: it syncs the pending device state but never runs
    the rescue policy — that belongs to the owning step's own observe().
    One device sync per consulted guard; called at epoch boundaries,
    not per step."""
    streak = False
    for g in list(_active_guards):
        if g.closed or not g._stepped_since_check:
            continue
        g._stepped_since_check = False
        g._sync_pending()
        if g._last[0] > 0:
            streak = True
    return streak


# ---------------------------------------------------------------------------
# the host monitor
# ---------------------------------------------------------------------------


def _key_bits(key):
    """Raw uint32 bits of an RNG key (typed or legacy array form)."""
    if key is None:
        return None
    import numpy as np

    try:
        import jax

        return np.asarray(jax.random.key_data(key))
    except Exception:  # noqa: BLE001 — legacy uint32[2] keys
        return np.asarray(key)


class _StepRecord:
    __slots__ = ("step", "key", "inputs", "labels")

    def __init__(self, step, key, inputs, labels):
        self.step = step
        self.key = key
        self.inputs = inputs
        self.labels = labels


class TrainGuard:
    """Host-side divergence monitor for one compiled step object.

    The step calls :meth:`capture` before dispatch (ring-buffers the RNG
    key + batch refs for replay) and :meth:`observe` after, handing over
    the new device guard-state array. ``observe`` syncs only every
    ``sync_every`` steps, through a one-interval async prefetch, and
    returns ``"rollback"`` when it restored a checkpoint (the step must
    then refresh its device carries from the restored host state).
    """

    def __init__(self, mode: Optional[str] = None,
                 max_skips: Optional[int] = None,
                 sync_every: Optional[int] = None,
                 model=None):
        self.mode = mode or guard_mode()
        self.max_skips = (max_skips if max_skips is not None
                          else _envi(_MAX_SKIPS_ENV, 8))
        self.sync_every = max(
            sync_every if sync_every is not None else _envi(_SYNC_ENV, 4),
            1)
        self._model_ref = weakref.ref(model) if model is not None else None
        # step-metrics sampler (observability/metrics.py): rides THIS
        # guard's sync cadence — its records cost no device reads beyond
        # the async prefetch the guard already pays for
        from ..observability.metrics import StepMetricsSampler

        self._sampler = StepMetricsSampler()
        self._step = 0
        self._ring: deque = deque(maxlen=2 * self.sync_every + 4)
        self._pending = None     # (step, state_array) async-prefetched
        self._last = [0.0] * GUARD_LEN   # newest host-read state
        self._last_step = -1
        self._reported_bad = 0.0  # total_skips+spikes already evented
        self._just_restored = False
        self._stepped_since_check = False
        self.closed = False       # set when this guard gave its verdict
        self.rollbacks = 0
        self.dumped: List[str] = []
        #: owner hook, invoked right after a rollback restored the
        #: checkpoint — the compiled step refreshes its device carries
        #: (guard-state vector, LocalSGD re-stacks replicas) here, so a
        #: rollback triggered from ANY sync point (observe, flush,
        #: divergence_active) leaves the step consistent
        self._on_rollback = None
        _active_guards.add(self)

    # -- persistence (rides the auto_checkpoint extras) -------------------
    def state_dict(self) -> Dict:
        return {
            "total_skips": float(self._last[1]),
            "total_spikes": float(self._last[2]),
            "loss_ewma": float(self._last[3]),
            "healthy_steps": float(self._last[6]),
            "gnorm_ewma": float(self._last[8]),
            "rollbacks": int(self.rollbacks),
        }

    def set_state_dict(self, state: Dict) -> None:
        self._last = [0.0] * GUARD_LEN
        self._last[1] = float(state.get("total_skips", 0.0))
        self._last[2] = float(state.get("total_spikes", 0.0))
        self._last[3] = float(state.get("loss_ewma", 0.0))
        self._last[6] = float(state.get("healthy_steps", 0.0))
        self._last[8] = float(state.get("gnorm_ewma", 0.0))
        self.rollbacks = int(state.get("rollbacks", 0))
        self._reported_bad = self._last[1] + self._last[2]
        self._pending = None
        self._just_restored = True

    def restored_device_state(self):
        """Device guard-state vector seeded from the restored counters:
        consec_bad resets (a rescue forgives the streak); totals and the
        loss/gnorm EWMA baselines carry from the snapshot (a zero,
        never-seeded EWMA is guarded against in update_guard_state)."""
        import jax.numpy as jnp

        return jnp.asarray(
            [0.0, self._last[1], self._last[2], self._last[3], 0.0, 0.0,
             self._last[6], 0.0, self._last[8], 0.0], jnp.float32)

    # -- per-step hooks ----------------------------------------------------
    def capture(self, key, inputs, labels) -> None:
        """Ring-buffer this step's replay seed (device refs; nothing is
        copied to host unless a bundle is actually dumped)."""
        self._step += 1
        self._sampler.tick(inputs)   # host ints off static shapes
        if os.environ.get(_DUMP_ENV):
            self._ring.append(
                _StepRecord(self._step, key, tuple(inputs), tuple(labels)))

    def observe(self, guard_state) -> Optional[str]:
        """Hand over the step's new device guard state. Returns None,
        ``"rollback"`` (checkpoint restored — refresh device carries), or
        raises/exits per mode."""
        self._stepped_since_check = True
        if self._step % self.sync_every != 0:
            return None
        prev = self._pending
        self._pending = (self._step, guard_state)
        try:
            guard_state.copy_to_host_async()
        except AttributeError:
            pass  # non-jax array (tests) or backend without async copy
        if prev is None:
            return None
        step, arr = prev
        import numpy as np

        self._last = [float(v) for v in np.asarray(arr)]
        self._last_step = step
        # the host read just landed: the step-metrics record reuses its
        # floats (plus wall-clock deltas) — no additional device access
        self._sampler.sample(step, self._last)
        return self._policy(step)

    def _sync_pending(self) -> None:
        """Pull the pending device state to the host (no policy)."""
        if self._pending is None:
            return
        import numpy as np

        step, arr = self._pending
        self._pending = None
        self._last = [float(v) for v in np.asarray(arr)]
        self._last_step = step

    def flush(self) -> Optional[str]:
        """Synchronously evaluate the newest handed-over state (tests /
        end-of-run checks; observe() is the zero-stall path)."""
        if self._pending is None:
            return None
        self._sync_pending()
        return self._policy(self._last_step)

    # -- policy ------------------------------------------------------------
    def _policy(self, step: int) -> Optional[str]:
        consec = self._last[0]
        total_bad = self._last[1] + self._last[2]
        new_bad = total_bad - self._reported_bad
        if new_bad > 0:
            self._reported_bad = total_bad
            bundle = self._dump_bundle(step)
            emit_event(
                "guard_skip", step=step, consec=int(consec),
                total_skips=int(self._last[1]),
                total_spikes=int(self._last[2]),
                health_bits=int(self._last[5]), gnorm=self._last[4],
                loss=self._last[7], loss_ewma=self._last[3],
                bundle=bundle,
                detail=self._describe(step),
            )
            print(f"paddle_tpu.train_guard: {self._describe(step)}",
                  file=sys.stderr, flush=True)
            # capture-on-anomaly: the first observed bad step arms a
            # bounded device-trace window over the NEXT steps (no-op
            # without a configured trace destination; at most
            # PADDLE_OBS_TRACE_MAX windows per process)
            if os.environ.get("PADDLE_OBS_TRACE_ON_TRIP",
                              "1").strip().lower() not in ("0", "false",
                                                           "off"):
                from .. import profiler as _prof

                _prof.arm_trace(reason="guard_trip")
        if consec < self.max_skips:
            return None
        # budget exhausted: rescue
        detail = (f"divergence: {int(consec)} consecutive bad steps "
                  f"(budget {self.max_skips}) at step ~{step}; "
                  + self._describe(step))
        if self.mode == "abort":
            emit_event("guard_abort", step=step, consec=int(consec),
                       health_bits=int(self._last[5]),
                       gnorm=self._last[4], loss=self._last[7],
                       detail=detail)
            print(f"paddle_tpu.train_guard: {detail}; aborting "
                  f"rc={GUARD_ABORT_RC}", file=sys.stderr, flush=True)
            os._exit(GUARD_ABORT_RC)
        target = _rescue_target()
        if target is None:
            self.closed = True   # verdict given; drop out of the
            #                      divergence_active consultation set
            raise GuardDivergenceError(
                detail + " — no auto_checkpoint range registered to roll "
                "back to (iterate TrainEpochRange, or set "
                "PADDLE_GUARD_MODE=abort to hand the rank to the elastic "
                "launcher)")
        self._just_restored = False
        restored = target.restore()
        self.rollbacks += 1
        if not self._just_restored:
            # guard not carried by the snapshot's extras: keep the
            # cumulative totals as the new reporting baseline
            self._reported_bad = self._last[1] + self._last[2]
        # in-flight pre-restore states must not re-trigger the budget
        self._pending = None
        self._last[0] = 0.0
        if self._on_rollback is not None:
            self._on_rollback()
        emit_event("guard_rollback", step=step, consec=int(consec),
                   restored_epoch=getattr(target, "_restored_epoch", None),
                   detail=detail)
        print(f"paddle_tpu.train_guard: {detail}; restored last-good "
              f"snapshot (next epoch {restored})",
              file=sys.stderr, flush=True)
        return "rollback"

    def _describe(self, step: int) -> str:
        bits = int(self._last[5])
        what = [w for b, w in ((HEALTH_LOSS, "loss nonfinite"),
                               (HEALTH_GRAD, "grads nonfinite"),
                               (HEALTH_PARAM, "params nonfinite"),
                               (HEALTH_SPIKE, "loss spike"),
                               (HEALTH_GNORM, "grad-norm spike"))
                if bits & b] or ["healthy"]
        return (f"step ~{step}: {', '.join(what)} "
                f"(consec {int(self._last[0])}, gnorm {self._last[4]:.3g}, "
                f"loss {self._last[7]:.6g}, ewma {self._last[3]:.6g})")

    # -- replay-bundle dump ------------------------------------------------
    def _dump_bundle(self, step: int) -> Optional[str]:
        """Write the first-bad step's replay bundle (best effort: the
        ring holds the last ~2 sync intervals; the oldest record at or
        after the first bad step serves, since skipped steps leave the
        params the replay needs untouched)."""
        dump_dir = os.environ.get(_DUMP_ENV)
        if not dump_dir or not self._ring:
            return None
        consec = int(self._last[0])
        first_bad = max(self._last_step - consec + 1, 1) if consec \
            else self._last_step
        rec = None
        for r in self._ring:
            if r.step >= first_bad:
                rec = r
                break
        if rec is None:
            rec = self._ring[-1]
        model = self._model_ref() if self._model_ref is not None else None
        try:
            import numpy as np

            from ..framework import io as fio

            ins = [np.asarray(x) for x in rec.inputs]
            labs = [np.asarray(y) for y in rec.labels]
            fp = 0
            for a in ins + labs:
                fp = zlib.crc32(np.ascontiguousarray(a).tobytes(), fp)
            bundle = {
                "step": rec.step, "time": time.time(),
                "health_bits": int(self._last[5]),
                "gnorm": self._last[4], "loss": self._last[7],
                "fingerprint": fp & 0xFFFFFFFF,
                "key_data": _key_bits(rec.key),
                "inputs": ins, "labels": labs,
            }
            if model is not None:
                bundle["state"] = {
                    k: np.asarray(v._data)
                    for k, v in model.state_dict().items()
                }
            os.makedirs(dump_dir, exist_ok=True)
            path = os.path.join(
                dump_dir,
                f"guard_step{rec.step:08d}.rank"
                f"{os.environ.get('PADDLE_TRAINER_ID', '0')}.pdbundle")
            fio.save(bundle, path)
            self.dumped.append(path)
            return path
        except Exception as e:  # noqa: BLE001 — diagnostics stay best-effort
            print(f"paddle_tpu.train_guard: bundle dump failed: {e}",
                  file=sys.stderr, flush=True)
            return None
