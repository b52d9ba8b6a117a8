"""Periodic step-metrics records on the guard's async-host-read cadence.

The numerical guard (utils/train_guard.py) already pulls a tiny device
state vector to the host every ``PADDLE_GUARD_SYNC_EVERY`` steps through
a one-interval async prefetch — the ONLY recurring device→host read the
training loop makes. Step metrics piggyback on exactly that read: when
the guard's deferred host copy lands, the sampler combines

- the already-hosted guard floats (last loss, loss/gnorm EWMAs, skip
  totals — no new device read),
- host wall-clock deltas between sync points (dispatch-side step time:
  with the pipeline full this converges to true device step time),
- per-step example/token counts taken from STATIC input shapes at
  capture time (host ints, no sync),
- best-effort device memory stats from the runtime allocator
  (``Device.memory_stats()`` — a host query of the allocator's
  counters, not a device program sync; None off-TPU),

into one ``step_metrics`` bus row. Zero new per-step host syncs by
construction — the cadence test asserts the device-read count is
bitwise unchanged vs a guard-only run.

``PADDLE_OBS_STEP_METRICS=0`` disables the records (the guard cadence
itself is untouched). With the guard off (``PADDLE_GUARD_MODE=off``)
there is no host-read cadence to ride, so no records are produced —
turn the guard on to get step metrics; that is the design contract, not
a limitation (a metrics-only cadence would ADD the sync the guard
already paid for).
"""
from __future__ import annotations

import os
import time
from typing import Optional

from . import bus

__all__ = ["StepMetricsSampler", "step_metrics_enabled", "device_memory",
           "DecodeMetricsSampler", "decode_metrics_enabled"]

_ENABLE_ENV = "PADDLE_OBS_STEP_METRICS"
_DECODE_ENABLE_ENV = "PADDLE_OBS_DECODE_METRICS"


def step_metrics_enabled() -> bool:
    v = os.environ.get(_ENABLE_ENV, "1").strip().lower()
    return v not in ("0", "false", "off")


def decode_metrics_enabled() -> bool:
    v = os.environ.get(_DECODE_ENABLE_ENV, "1").strip().lower()
    return v not in ("0", "false", "off")


def device_memory() -> Optional[dict]:
    """Allocator counters of the first local device (bytes_in_use /
    peak_bytes_in_use), or None when the backend doesn't report them
    (the CPU client's ``memory_stats()`` is None). A runtime bookkeeping
    query — no dispatch, no device sync."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    return {
        k: int(stats[k])
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }


class StepMetricsSampler:
    """Owned by a TrainGuard; fed per-step counters at capture time and
    flushed at each completed host read.

    ``tick`` is on the per-step path: integer adds on static shape
    attributes only. ``sample`` runs once per sync interval with the
    guard state ALREADY on the host.
    """

    def __init__(self):
        self.enabled = step_metrics_enabled()
        self._t_last: Optional[float] = None
        self._step_last = 0
        self._examples = 0
        self._tokens = 0
        self._grad_comm: Optional[dict] = None
        self._q_matmul: Optional[dict] = None
        self._moment_bytes: Optional[dict] = None

    def set_grad_comm(self, info: Optional[dict]) -> None:
        """Static grad-comm accounting (dtype + bytes-on-wire of one
        reduction hop, quantized payload + scales — ISSUE 10), computed
        once by TrainStep from static shapes; riding every row costs
        zero device reads."""
        self._grad_comm = dict(info) if info else None

    def set_quant_bytes(self, q_matmul: Optional[dict],
                        moment_bytes: Optional[dict]) -> None:
        """Static quantized-compute accounting (ISSUE 19): resident
        matmul-weight bytes under the QAT policy and Adam-moment bytes
        under quantized_moments — same once-at-construction, static-
        shape contract as set_grad_comm. Rows only grow when a policy is
        armed (reduction_x > 1), keeping the all-knobs-off row
        byte-identical."""
        self._q_matmul = (
            dict(q_matmul)
            if q_matmul and q_matmul.get("reduction_x", 1.0) != 1.0
            else None
        )
        self._moment_bytes = (
            dict(moment_bytes)
            if moment_bytes and moment_bytes.get("reduction_x", 1.0) != 1.0
            else None
        )

    def tick(self, inputs) -> None:
        """Per-step accounting from static input shapes (host ints)."""
        if not self.enabled:
            return
        x = inputs[0] if inputs else None
        shape = getattr(x, "shape", None)
        if not shape:
            return
        n = int(shape[0])
        self._examples += n
        if len(shape) >= 2:
            self._tokens += n * int(shape[1])

    def sample(self, step: int, guard_last) -> None:
        """Emit one ``step_metrics`` row for the window ending at
        ``step`` (the guard's newest host-read state vector rides in
        ``guard_last`` as plain floats)."""
        if not self.enabled or not bus.enabled():
            return
        now = time.perf_counter()
        t0, s0 = self._t_last, self._step_last
        self._t_last, self._step_last = now, step
        examples, tokens = self._examples, self._tokens
        self._examples = self._tokens = 0
        if t0 is None or step <= s0:
            return  # first window: no baseline to difference against
        dt = now - t0
        nsteps = step - s0
        payload = {
            "steps": nsteps,
            "step_ms": round(dt / nsteps * 1e3, 3),
            "loss": float(guard_last[7]),
            "loss_ewma": float(guard_last[3]),
            "gnorm": float(guard_last[4]),
            "gnorm_ewma": float(guard_last[8]),
            "consec_bad": int(guard_last[0]),
            "total_skips": int(guard_last[1]),
            "total_spikes": int(guard_last[2]),
        }
        if dt > 0:
            if examples:
                payload["examples_per_sec"] = round(examples / dt, 2)
            if tokens:
                payload["tokens_per_sec"] = round(tokens / dt, 1)
        if self._grad_comm:
            payload["grad_comm"] = self._grad_comm
        if self._q_matmul:
            payload["q_matmul"] = self._q_matmul
        if self._moment_bytes:
            payload["moment_bytes"] = self._moment_bytes
        mem = device_memory()
        if mem:
            payload["device_memory"] = mem
        bus.emit("step_metrics", payload, step=step)


class DecodeMetricsSampler:
    """Serving-side telemetry on the engine's READBACK cadence
    (ISSUE 9 satellite).

    Same zero-new-per-step-sync discipline as :class:`StepMetricsSampler`:
    the continuous-batching engine already pulls one stacked token block
    plus the done mask to the host every ``PADDLE_SERVE_SYNC_EVERY``
    decode steps (its stop-condition check); ``decode_metrics`` rows are
    built from exactly those host values and wall-clock deltas — nothing
    here reads a device array, so enabling the records changes the
    decode loop's transfer count by zero (asserted in
    tests/test_serving.py). ``PADDLE_OBS_DECODE_METRICS=0`` disables.

    Rows:
      ``decode_metrics``  per readback window: decode steps, emitted
        tokens, tokens/sec over the window wall clock, inflight slots,
        queue depth; round 13 adds TTFT of the requests that reached
        their first token inside the window (submit -> first token:
        the SLO the router schedules against) and the paged block-pool
        gauges (blocks in use / total, cumulative freed, deferred
        admissions) — all host-side values the engine already holds at
        its readback, so the transfer count stays bitwise unchanged
        (the counted-np.asarray assert covers the grown row);
      ``decode_request``  per completed request: generated tokens,
        end-to-end latency, prefill share, TTFT, per-token mean.
    """

    def __init__(self):
        self.enabled = decode_metrics_enabled()
        self._windows = 0

    def window(self, *, steps: int, tokens: int, wall_s: float,
               inflight: int, queue_depth: int, ttft_ms=None,
               blocks_in_use=None, blocks_total=None, blocks_freed=None,
               admit_deferred=None, prefix_hits=None,
               prefix_blocks_shared=None, cow_copies=None,
               adapters_resident=None) -> None:
        if not self.enabled or not bus.enabled():
            return
        self._windows += 1
        payload = {
            "steps": int(steps),
            "tokens": int(tokens),
            "inflight_slots": int(inflight),
            "queue_depth": int(queue_depth),
        }
        if wall_s > 0:
            payload["tokens_per_sec"] = round(tokens / wall_s, 1)
            payload["step_ms"] = round(wall_s / max(steps, 1) * 1e3, 3)
        if ttft_ms:  # requests admitted this window (host wall clocks)
            payload["ttft_ms"] = round(max(ttft_ms), 3)
            payload["ttft_ms_mean"] = round(
                sum(ttft_ms) / len(ttft_ms), 3)
        if blocks_total:  # paged pool occupancy/eviction gauges
            payload["blocks_in_use"] = int(blocks_in_use or 0)
            payload["blocks_total"] = int(blocks_total)
            payload["block_occupancy"] = round(
                (blocks_in_use or 0) / blocks_total, 4)
            payload["blocks_freed"] = int(blocks_freed or 0)
        if admit_deferred:
            payload["admit_deferred"] = int(admit_deferred)
        # round-18 multi-tenant gauges — cumulative host counters the
        # engine already holds at its readback (None = feature off, the
        # key is omitted so pre-18 rows stay byte-identical)
        if prefix_hits is not None:
            payload["prefix_hits"] = int(prefix_hits)
            payload["prefix_blocks_shared"] = int(
                prefix_blocks_shared or 0)
            payload["cow_copies"] = int(cow_copies or 0)
        if adapters_resident is not None:
            payload["adapters_resident"] = int(adapters_resident)
        bus.emit("decode_metrics", payload, step=self._windows)

    def request_done(self, *, rid, tokens: int, latency_ms: float,
                     prefill_ms: float, ttft_ms=None,
                     trace_id=None) -> None:
        if not self.enabled or not bus.enabled():
            return
        payload = {
            "rid": rid,
            "tokens": int(tokens),
            "latency_ms": round(latency_ms, 3),
            "prefill_ms": round(prefill_ms, 3),
            "ms_per_token": round(latency_ms / max(tokens, 1), 3),
        }
        if ttft_ms is not None:
            payload["ttft_ms"] = round(ttft_ms, 3)
        if trace_id is not None:
            # the request's terminal span: timeline/monitor stitch it to
            # the router_submit/admit/prefill spans by this id
            payload["trace_id"] = trace_id
        bus.emit("decode_request", payload, step=self._windows)

    # -- request-scoped spans (ISSUE 14) -----------------------------------
    def span(self, name: str, *, trace_id, rid=None, **extra) -> None:
        """One engine-phase span row for a traced request (admission,
        prefill, prefill_chunk, retire). Host-side values only — the
        engine calls this at points where it already holds the numbers
        (submit, activate, collect), so tracing adds zero device
        reads. No-op for untraced requests (``trace_id`` None)."""
        if not self.enabled or not bus.enabled() or trace_id is None:
            return
        payload = dict(extra)
        if rid is not None:
            payload["rid"] = rid
        bus.emit_span(name, trace_id, payload, step=self._windows)

    def window_span(self, trace_ids, *, steps: int) -> None:
        """One row per readback window naming every traced inflight
        request (the decode-window phase) — row count scales with
        windows, not tokens or requests, the same cadence contract as
        ``decode_metrics``."""
        if not self.enabled or not bus.enabled():
            return
        ids = [t for t in trace_ids if t is not None]
        if not ids:
            return
        bus.emit("span", {"name": "decode_window", "trace_ids": ids,
                          "steps": int(steps)}, step=self._windows)


# ---------------------------------------------------------------------------
# routed-expert load: device counters read at the engine's readback
# ---------------------------------------------------------------------------

_expert_load: dict = {}


def record_expert_load(loads: dict) -> None:
    """Keep the host copy of a routed model's counters
    (`serving.LatentMoELM.expert_load()`), as the serving engine reads
    them with each readback. The counters are running totals on the
    device, so the newest reading replaces the last."""
    _expert_load.clear()
    _expert_load.update({int(k): v for k, v in loads.items()})


def expert_load() -> dict:
    """{block index: [2, held + 1] int array} — per routed layer, the
    assignments that fell on each expert held here and, in the last
    column, those routed to experts not held; row 0 counted in prefill
    programs, row 1 in decode steps. Process-wide and readable after the
    engine and its model are gone, as `ledger.compile_seconds()` is;
    empty when no routed model was served."""
    return dict(_expert_load)


_selected_keys: dict = {}


def record_selected_keys(keys: dict) -> None:
    """Keep the host copy of a sparse-attention model's counters
    (`serving.SparseMoELM.selected_keys()`), read with each readback
    beside the expert load; running totals, so the newest reading
    replaces the last."""
    _selected_keys.clear()
    _selected_keys.update({int(k): v for k, v in keys.items()})


def selected_keys() -> dict:
    """{block index: [2, 2] int64 array} — per learned-sparse-attention
    layer, the (query, key) pairs visible and the pairs its indexer
    selected; row 0 counted in prefill programs, row 1 in decode steps.
    Process-wide and readable after the engine is gone, as
    :func:`expert_load` is; empty when no such model was served."""
    return dict(_selected_keys)


# ---------------------------------------------------------------------------
# sampler engagement: which side of `sampling.sample`'s branch a decode
# step took, counted from host values at the engine's readback
# ---------------------------------------------------------------------------

_sampler_steps = {"greedy": 0, "sampling": 0}


def record_sampler_steps(greedy: int, sampling: int) -> None:
    """Add one readback window's decode steps: ``greedy`` steps in which
    every active request had ``temperature <= 0`` (the sampler returned
    the argmax and ran no filter), ``sampling`` steps in which at least
    one drew. Counted by `InferenceEngine.turn` from the requests it
    holds — no device read."""
    _sampler_steps["greedy"] += int(greedy)
    _sampler_steps["sampling"] += int(sampling)


def sampler_steps() -> dict:
    """{"greedy": n, "sampling": n} — running totals of the decode steps
    every engine of this process dispatched, by the side of the
    sampler's branch their active requests call for. Process-wide and
    readable after the engine is gone, as :func:`expert_load` is."""
    return dict(_sampler_steps)


# ---------------------------------------------------------------------------
# K/V append engagement: which way each `cache_update` call was lowered,
# counted where the call is traced
# ---------------------------------------------------------------------------

_kv_append_routes = {"kernel": 0, "scatter": 0, "fused": 0}


def record_kv_append_route(route: str) -> None:
    """Count one K or V row write of a cache: ``kernel`` when a
    `nn.functional.attention.cache_update` call became the in-place
    `kv_append` Pallas kernel, ``scatter`` when it kept XLA's write
    (prefill, speculative steps, quantized and paged caches, a sharded
    cache, the CPU), ``fused`` when `cached_append_attention` wrote the
    row inside the `decode_append_attention` kernel that attends over
    it. Called as the write is traced, so a compiled step counts once
    however often it runs."""
    _kv_append_routes[route] += 1


def kv_append_routes() -> dict:
    """{"kernel": n, "scatter": n, "fused": n} — running totals of the
    K and V row writes traced in this process, by the way each was
    lowered: a `DecodeStep` over a plain float cache on the chip adds
    2 x layers to ``fused`` (and nothing to ``kernel``: its writes are
    folded into the attention), a `PrefillStep` 2 x layers to
    ``scatter``. Process-wide, as :func:`sampler_steps` is."""
    return dict(_kv_append_routes)


# ---------------------------------------------------------------------------
# decode attention engagement: which way each `cached_attention` call was
# lowered, counted where the call is traced
# ---------------------------------------------------------------------------

_cached_attention_routes = {"kernel": 0, "dense": 0}


def record_cached_attention_route(route: str) -> None:
    """Count one decode attention read of a cache: ``kernel`` when a
    `nn.functional.attention.cached_attention` call became the
    `decode_attention` Pallas kernel that stops at each slot's live
    length, or a `cached_append_attention` call the
    `decode_append_attention` kernel that also writes the step's rows,
    ``dense`` when it kept XLA's form over the whole capacity (prefill,
    speculative steps, quantized and paged caches, a sharded cache, the
    CPU). Called as the read is traced, so a compiled step counts once
    however often it runs."""
    _cached_attention_routes[route] += 1


def cached_attention_routes() -> dict:
    """{"kernel": n, "dense": n} — running totals of the decode
    attention reads traced in this process, by the way each was lowered:
    a `DecodeStep` over a plain float cache on the chip adds ``layers``
    to ``kernel`` (one `decode_append_attention` a layer), a
    `PrefillStep` as many to ``dense``.
    Process-wide, as :func:`kv_append_routes` is."""
    return dict(_cached_attention_routes)


# ---------------------------------------------------------------------------
# dead slots: the decode kernel that skips them, counted where it is traced,
# and the slot-steps it saw live and dead, counted at the engine's collect
# ---------------------------------------------------------------------------

_masked_kernels = [0]
_decode_slot_steps = {"live": 0, "dead": 0}


def record_masked_kernel() -> None:
    """Count one `cached_append_attention` call that became the
    `decode_append_attention` kernel with the step's ``live`` mask, which
    moves no tile for a dead slot. Called as the call is traced."""
    _masked_kernels[0] += 1


def masked_kernels() -> int:
    """Running total of the masked decode kernels traced in this process:
    a `DecodeStep` of a model that takes ``live`` over a plain float
    cache on the chip adds ``layers``; every other route adds nothing.
    `jit.DecodeStep` reads it around its trace to learn whether its
    program skips dead slots."""
    return _masked_kernels[0]


def record_decode_slot_steps(live: int, dead: int) -> None:
    """Add one readback window's slot-steps of a decode program whose
    attention skips dead slots: ``live`` emitted a token, ``dead`` the
    -1 of a slot that held no request when the step began. Counted by
    `InferenceEngine.turn` from the token block it has read — no device
    read."""
    _decode_slot_steps["live"] += int(live)
    _decode_slot_steps["dead"] += int(dead)


def decode_slot_steps() -> dict:
    """{"live": n, "dead": n} — running totals of the slot-steps of every
    engine's decode steps in this process whose attention took the masked
    kernel: how often a step skipped a slot, and how often it attended
    one. Zero where no step took it (another route, the CPU). Readable
    after the engine is gone, as :func:`expert_load` is."""
    return dict(_decode_slot_steps)


# ---------------------------------------------------------------------------
# state-space layers: which shape of call each mixer was traced as
# ---------------------------------------------------------------------------

_ssm_routes = {"scan": 0, "step": 0}


def record_ssm_route(route: str) -> None:
    """Count one state-space mixer call (`nn.functional.ssm.mixer`):
    ``scan`` for the chunked SSD form (a prompt or a chunk), ``step`` for
    the one-token recurrence (a decode step). Called as the call is
    traced, so a compiled step counts once however often it runs."""
    _ssm_routes[route] += 1


def ssm_routes() -> dict:
    """{"scan": n, "step": n} — running totals of the state-space mixer
    calls traced in this process: a `PrefillStep` of a hybrid model adds
    its mixer layers to ``scan``, a `DecodeStep` as many to ``step``.
    Process-wide, as :func:`kv_append_routes` is."""
    return dict(_ssm_routes)
