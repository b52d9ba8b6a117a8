"""Observability plane tests (ISSUE 8): unified bus schema + compat
aliases, step-metrics cadence (zero extra host syncs), recompile
ledger + storm detector, timeline merge, trace-window
arm/disarm."""
import json
import os

import numpy as np
import pytest

import jax

from paddle_tpu.observability import bus, ledger, metrics

_OBS_KNOBS = (
    "PADDLE_OBS_DIR", "PADDLE_OBS_BUS_FILE", "PADDLE_OBS_STEP_METRICS",
    "PADDLE_OBS_STORM_N",
    "PADDLE_OBS_TRACE_AT_STEP", "PADDLE_OBS_TRACE_STEPS",
    "PADDLE_OBS_TRACE_DIR", "PADDLE_OBS_TRACE_MAX",
    "PADDLE_OBS_TRACE_ON_TRIP",
    "PADDLE_GUARD_MODE", "PADDLE_GUARD_SYNC_EVERY",
    "PADDLE_GUARD_EVENT_FILE", "PADDLE_GUARD_MAX_SKIPS",
    "PADDLE_COLL_EVENT_FILE", "PADDLE_FAULT_SPEC",
)


@pytest.fixture
def obs_env(monkeypatch):
    """Clean observability state: knobs scrubbed, bus step counter and
    ledger totals zeroed, trace window disarmed."""
    from paddle_tpu import profiler
    from paddle_tpu.utils import fault_injection

    for k in _OBS_KNOBS:
        monkeypatch.delenv(k, raising=False)
    bus.reset()
    ledger.reset()
    profiler._reset_trace_state()
    fault_injection.reset()
    yield monkeypatch
    os.environ.pop("PADDLE_FAULT_SPEC", None)
    fault_injection.reset()
    profiler._reset_trace_state()
    bus.reset()


def _mk_step(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep

    paddle.seed(seed)
    m = nn.Linear(4, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    return m, TrainStep(m, lambda o, y: ((o - y) ** 2).mean(), opt)


_X = np.arange(32, dtype=np.float32).reshape(8, 4) / 32.0
_Y = np.ones((8, 4), np.float32)


# ---------------------------------------------------------------------------
# bus schema
# ---------------------------------------------------------------------------


class TestBusSchema:
    def test_round_trip(self, obs_env, tmp_path):
        f = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", f)
        obs_env.setenv("PADDLE_TRAINER_ID", "3")
        bus.set_step(17)
        bus.emit("unit_test", {"a": 1, "b": "x"})
        bus.emit("explicit_step", {"c": 2.5}, step=42)
        rows = bus.read_stream(f)
        assert [r["kind"] for r in rows] == ["unit_test", "explicit_step"]
        r = rows[0]
        assert r["v"] == bus.SCHEMA_VERSION
        assert r["step"] == 17          # inherited from set_step
        assert r["rank"] == 3
        assert isinstance(r["time"], float)
        assert r["payload"] == {"a": 1, "b": "x"}
        assert rows[1]["step"] == 42

    def test_off_means_no_file(self, obs_env, tmp_path):
        assert not bus.enabled()
        bus.emit("ghost", {"x": 1})
        assert list(tmp_path.iterdir()) == []

    def test_torn_line_tolerated(self, obs_env, tmp_path):
        f = tmp_path / "bus.jsonl"
        f.write_text(json.dumps({"v": 1, "kind": "ok", "time": 1.0,
                                 "rank": 0, "step": 1, "payload": {}})
                     + "\n" + '{"v": 1, "kind": "torn')
        assert [r["kind"] for r in bus.read_stream(str(f))] == ["ok"]

    def test_obs_dir_per_rank_naming(self, obs_env, tmp_path):
        obs_env.setenv("PADDLE_OBS_DIR", str(tmp_path))
        obs_env.setenv("PADDLE_TRAINER_ID", "2")
        bus.emit("hello", {})
        bus.emit("from_launcher", {}, rank=-1)
        streams = bus.rank_streams(str(tmp_path))
        assert set(streams) == {2, -1}
        assert streams[2][0]["kind"] == "hello"
        assert streams[-1][0]["kind"] == "from_launcher"


class TestCompatAliases:
    def test_guard_legacy_stream_unchanged(self, obs_env, tmp_path):
        """guard events land in the OLD flat format on
        PADDLE_GUARD_EVENT_FILE and in the unified schema on the bus."""
        from paddle_tpu.distributed import comm_monitor
        from paddle_tpu.utils import train_guard

        legacy = str(tmp_path / "guardev")
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_GUARD_EVENT_FILE", legacy)
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        train_guard.emit_event("guard_skip", step=5, detail="unit")
        old = comm_monitor.read_events(legacy)  # the attribution reader
        assert old == [pytest.approx(old[0])]
        assert old[0]["event"] == "guard_skip"
        assert old[0]["step"] == 5 and old[0]["detail"] == "unit"
        assert "payload" not in old[0]          # flat legacy shape
        new = bus.read_stream(busf)
        assert new[0]["kind"] == "guard_skip" and new[0]["step"] == 5
        assert new[0]["payload"]["detail"] == "unit"

    def test_guard_legacy_only_without_bus(self, obs_env, tmp_path):
        from paddle_tpu.utils import train_guard

        legacy = str(tmp_path / "guardev")
        obs_env.setenv("PADDLE_GUARD_EVENT_FILE", legacy)
        train_guard.emit_event("guard_abort", step=9)
        assert json.loads(open(legacy).read())["event"] == "guard_abort"

    def test_comm_monitor_both_streams(self, obs_env, tmp_path):
        from paddle_tpu.distributed import comm_monitor

        legacy = str(tmp_path / "collev")
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_COLL_EVENT_FILE", legacy)
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        comm_monitor.reset()
        mon = comm_monitor.CommMonitor(rank=1, world=2, timeout=0.0)
        rec = mon.record("all_reduce", 0, "dp", 2, (4, 4), "float32")
        mon._write_event("coll_timeout", rec, extra={"timeout_s": 5.0})
        old = comm_monitor.read_events(legacy)
        assert old[0]["event"] == "coll_timeout"
        assert old[0]["op"] == "all_reduce"       # flat, as before
        assert old[0]["timeout_s"] == 5.0
        new = bus.read_stream(busf)
        assert new[0]["kind"] == "coll_timeout"
        assert new[0]["rank"] == 1
        assert new[0]["payload"]["op"] == "all_reduce"
        comm_monitor.reset()


# ---------------------------------------------------------------------------
# step metrics on the guard cadence
# ---------------------------------------------------------------------------


class TestStepMetrics:
    def test_records_on_guard_cadence(self, obs_env, tmp_path):
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        obs_env.setenv("PADDLE_GUARD_SYNC_EVERY", "2")
        _, step = _mk_step()
        for _ in range(8):
            step(_X, _Y)
        rows = [r for r in bus.read_stream(busf)
                if r["kind"] == "step_metrics"]
        # syncs at steps 2,4,6,8; reads land one interval late and the
        # first completed read only seeds the wall-clock baseline -> the
        # windows ending at steps 4 and 6 are the ones recorded
        assert len(rows) == 2
        assert [r["step"] for r in rows] == [4, 6]
        p = rows[-1]["payload"]
        assert p["steps"] == 2
        assert p["step_ms"] > 0
        assert p["examples_per_sec"] > 0
        assert np.isfinite(p["loss"]) and np.isfinite(p["loss_ewma"])
        assert p["total_skips"] == 0

    def test_zero_extra_host_syncs(self, obs_env, tmp_path):
        """THE cadence contract: enabling step metrics changes the
        number of device->host array reads by exactly zero vs the
        guard-only run (the record reuses the guard's prefetched
        state)."""
        obs_env.setenv("PADDLE_GUARD_SYNC_EVERY", "2")

        def count_reads(metrics_on, seed):
            if metrics_on:
                obs_env.setenv("PADDLE_OBS_BUS_FILE",
                               str(tmp_path / f"bus{seed}.jsonl"))
                obs_env.setenv("PADDLE_OBS_STEP_METRICS", "1")
            else:
                obs_env.delenv("PADDLE_OBS_BUS_FILE", raising=False)
                obs_env.setenv("PADDLE_OBS_STEP_METRICS", "0")
            _, step = _mk_step(seed=seed)
            x, y = _X, _Y
            step(x, y)  # compile outside the counted window
            counted = {"n": 0}
            real = np.asarray

            def counting(a, *args, **kw):
                if isinstance(a, jax.Array):
                    counted["n"] += 1
                return real(a, *args, **kw)

            obs_env.setattr(np, "asarray", counting)
            try:
                for _ in range(8):
                    step(x, y)
            finally:
                obs_env.setattr(np, "asarray", real)
            return counted["n"]

        base = count_reads(False, seed=0)
        with_metrics = count_reads(True, seed=1)
        assert with_metrics == base
        # and the metrics run actually produced records
        rows = [r for r in bus.read_stream(str(tmp_path / "bus1.jsonl"))
                if r["kind"] == "step_metrics"]
        assert rows

    def test_disabled_by_knob(self, obs_env, tmp_path):
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        obs_env.setenv("PADDLE_OBS_STEP_METRICS", "0")
        obs_env.setenv("PADDLE_GUARD_SYNC_EVERY", "1")
        _, step = _mk_step()
        for _ in range(4):
            step(_X, _Y)
        kinds = {r["kind"] for r in bus.read_stream(busf)}
        assert "step_metrics" not in kinds
        assert "recompile" in kinds     # the rest of the bus still works

    def test_device_memory_best_effort(self):
        m = metrics.device_memory()
        assert m is None or isinstance(m, dict)  # None on CPU


# ---------------------------------------------------------------------------
# recompile ledger
# ---------------------------------------------------------------------------


class TestRecompileLedger:
    def test_miss_vs_hit_and_fingerprint_diff(self, obs_env, tmp_path):
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        f = ledger.instrument(jax.jit(lambda x: x * 2), "unit")
        import jax.numpy as jnp

        f(jnp.ones((8,)))
        f(jnp.ones((8,)))               # hit: no new row
        f(jnp.ones((9,)))               # forced reshape: miss
        rows = [r for r in bus.read_stream(busf)
                if r["kind"] == "recompile"]
        assert len(rows) == 2
        assert f.compiles == 2
        assert ledger.compile_count() == 2
        p = rows[1]["payload"]
        assert p["label"] == "unit" and p["ordinal"] == 2
        assert p["compile_wall_s"] >= 0
        # the reshape is NAMED in the fingerprint diff
        assert any("float32[8]" in c and "float32[9]" in c
                   for c in p["changed"]), p["changed"]

    def test_storm_detector_names_changing_field(self, obs_env, tmp_path):
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        obs_env.setenv("PADDLE_OBS_STORM_N", "3")
        f = ledger.instrument(jax.jit(lambda x: x + 1), "stormy")
        import jax.numpy as jnp

        for n in (4, 5, 6, 7):          # a shape that wobbles per call
            f(jnp.ones((n,)))
        storms = [r for r in bus.read_stream(busf)
                  if r["kind"] == "recompile_storm"]
        assert storms, "no storm record after 4 distinct-shape compiles"
        p = storms[0]["payload"]
        assert p["label"] == "stormy"
        assert any("args[0]" in c for c in p["changing_fields"])
        assert "signature keeps changing" in p["detail"]

    def test_backend_compile_row_from_the_one_listener(self, obs_env,
                                                       tmp_path):
        """With the bus on, the ledger's listener writes a
        ``backend_compile`` row for a compile outside any ledgered call."""
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        import jax.numpy as jnp

        jax.jit(lambda x: x * 3 - 1)(jnp.ones((5,)))
        rows = [r["payload"] for r in bus.read_stream(busf)
                if r["kind"] == "backend_compile"]
        assert rows, "no backend_compile row"
        assert rows[-1]["key"].endswith("backend_compile_duration")
        assert rows[-1]["seconds"] >= 0

    def test_train_step_single_compile(self, obs_env, tmp_path):
        """The real TrainStep compiles exactly once over repeated
        same-shape steps (the out_shardings pinning contract) — and the
        ledger proves it."""
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        _, step = _mk_step()
        for _ in range(4):
            step(_X, _Y)
        rows = [r for r in bus.read_stream(busf)
                if r["kind"] == "recompile"]
        assert len(rows) == 1
        assert rows[0]["payload"]["label"] == "TrainStep"

    def test_train_step_batch_wobble_recompiles(self, obs_env, tmp_path):
        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        _, step = _mk_step()
        step(_X, _Y)
        step(_X[:4], _Y[:4])            # last-partial-batch shape
        rows = [r for r in bus.read_stream(busf)
                if r["kind"] == "recompile"]
        assert len(rows) == 2
        changed = rows[1]["payload"]["changed"]
        assert any("8,4" in c and "4,4" in c for c in changed), changed

    def test_diff_fingerprints_names_dtype_and_new(self):
        a = [("args[0]", "float32[4]"), ("args[1]", "int32[2]")]
        b = [("args[0]", "bfloat16[4]"), ("args[2]", "int32[1]")]
        lines = ledger.diff_fingerprints(a, b)
        joined = "\n".join(lines)
        assert "float32[4] -> bfloat16[4]" in joined
        assert "(gone)" in joined and "(new)" in joined


# ---------------------------------------------------------------------------
# timeline merge
# ---------------------------------------------------------------------------


def _write_rank_stream(d, rank, rows):
    with open(os.path.join(d, f"telemetry.rank{rank}.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


class TestTimeline:
    def _synthetic_dir(self, tmp_path):
        d = str(tmp_path)
        os.makedirs(d, exist_ok=True)
        t0 = 1000.0

        def row(rank, kind, step, dt, payload):
            return {"v": 1, "kind": kind, "step": step, "time": t0 + dt,
                    "rank": rank, "payload": payload}

        _write_rank_stream(d, 0, [
            row(0, "recompile", 1, 0.5,
                {"label": "TrainStep", "ordinal": 1,
                 "compile_wall_s": 0.4, "fingerprint": [], "changed": []}),
            row(0, "step_metrics", 4, 1.0,
                {"steps": 4, "step_ms": 10.0, "loss": 2.0,
                 "tokens_per_sec": 1000.0}),
            row(0, "step_metrics", 8, 2.0,
                {"steps": 4, "step_ms": 12.0, "loss": 1.9,
                 "tokens_per_sec": 900.0}),
        ])
        _write_rank_stream(d, 1, [
            row(1, "step_metrics", 4, 1.1,
                {"steps": 4, "step_ms": 30.0, "loss": 2.0,
                 "tokens_per_sec": 400.0}),
            row(1, "guard_skip", 6, 1.5,
                {"detail": "grads nonfinite", "consec": 1}),
        ])
        with open(os.path.join(d, "comm_dump.rank1.json"), "w") as f:
            json.dump({"rank": 1, "world": 2, "reason": "timeout",
                       "records": [
                           {"seq": 1, "op": "all_reduce", "group": 0,
                            "nranks": 2, "shape": [4], "dtype": "float32",
                            "rank": 1, "site": "x.py:1",
                            "status": "done", "t_start": t0 + 1.2,
                            "t_done": t0 + 1.4},
                       ]}, f)
        return d

    def test_merge_chrome_trace_and_summary(self, tmp_path):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "timeline", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "tools", "timeline.py"))
        timeline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(timeline)

        d = self._synthetic_dir(tmp_path)
        streams, dumps, trace, lines = timeline.merge(d)
        assert set(streams) == {0, 1}
        assert set(dumps) == {1}
        evs = trace["traceEvents"]
        pids = {e.get("pid") for e in evs}
        assert {0, 1} <= pids
        # counter tracks for step metrics, duration slices for compiles
        # and collectives
        assert any(e["ph"] == "C" and e["pid"] == 0 for e in evs)
        assert any(e["ph"] == "X" and "compile" in e["name"]
                   for e in evs)
        assert any(e["ph"] == "X" and e["name"] == "all_reduce"
                   and e["dur"] == pytest.approx(0.2e6) for e in evs)
        assert any(e["ph"] == "i" and e["name"] == "guard_skip"
                   for e in evs)
        text = "\n".join(lines)
        # slowest rank named; guard trip counted; recompile accounted
        assert "slowest ranks: rank 1 (30.00ms)" in text
        assert "guard events: 1" in text
        report0 = [l for l in lines if l.strip().startswith("0")][0]
        assert "1" in report0  # one recompile on rank 0

    def test_multitenant_summary_lines(self, tmp_path):
        """ISSUE 18: the prefix-cache / disagg / adapter-residency
        summary renders from the CUMULATIVE decode_metrics counters
        (last row per stream) plus the disagg_prefill spans."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "timeline", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "tools", "timeline.py"))
        timeline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(timeline)

        d = str(tmp_path / "obs")
        os.makedirs(d, exist_ok=True)
        t0 = 1000.0

        def row(rank, kind, step, dt, payload):
            return {"v": 1, "kind": kind, "step": step, "time": t0 + dt,
                    "rank": rank, "payload": payload}

        _write_rank_stream(d, 0, [
            row(0, "decode_metrics", 1, 1.0,
                {"steps": 4, "tokens": 9, "inflight_slots": 2,
                 "queue_depth": 0, "prefix_hits": 1,
                 "prefix_blocks_shared": 2, "cow_copies": 0,
                 "adapters_resident": 3}),
            row(0, "decode_metrics", 2, 2.0,
                {"steps": 4, "tokens": 9, "inflight_slots": 2,
                 "queue_depth": 0, "prefix_hits": 3,
                 "prefix_blocks_shared": 6, "cow_copies": 1,
                 "adapters_resident": 3}),
            row(0, "decode_request", 2, 2.1,
                {"rid": "a", "tokens": 8, "latency_ms": 5.0,
                 "prefill_ms": 1.0, "ms_per_token": 0.6}),
            row(0, "decode_request", 2, 2.2,
                {"rid": "b", "tokens": 8, "latency_ms": 5.0,
                 "prefill_ms": 0.2, "ms_per_token": 0.6}),
            row(0, "decode_request", 2, 2.3,
                {"rid": "c", "tokens": 8, "latency_ms": 5.0,
                 "prefill_ms": 0.2, "ms_per_token": 0.6}),
            row(0, "decode_request", 2, 2.4,
                {"rid": "d", "tokens": 8, "latency_ms": 5.0,
                 "prefill_ms": 0.2, "ms_per_token": 0.6}),
            row(0, "span", 1, 0.5,
                {"name": "disagg_prefill", "trace_id": "t1",
                 "rid": "a", "prefill_host": 0, "to_host": 0,
                 "blocks": 2, "bytes": 4096, "ctx": 16,
                 "dur_ms": 3.0}),
        ])
        _, _, _, lines = timeline.merge(d)
        text = "\n".join(lines)
        # the LAST (cumulative) row counts, not the sum of rows
        assert ("prefix cache: 3 hit(s) (75% of 4 request(s)), "
                "6 block prefill(s) saved, 1 CoW cop(ies)") in text
        assert "disaggregated prefill: 1 handoff(s)" in text
        assert "adapters resident: rank 0=3" in text

    def test_cli_end_to_end(self, tmp_path):
        import subprocess
        import sys

        d = self._synthetic_dir(tmp_path / "obs")
        out = str(tmp_path / "trace.json")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "timeline.py"),
             d, "--out", out],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "chrome trace" in r.stdout
        assert "slowest ranks" in r.stdout
        trace = json.load(open(out))
        assert trace["traceEvents"]

    def test_empty_dir_rc(self, tmp_path):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "timeline.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 1


class TestMultiRankDryrun:
    """Acceptance pin (ISSUE 8): a REAL multi-rank run through the
    elastic launcher leaves per-rank bus streams next to the workerlogs
    (launcher-provisioned PADDLE_OBS_DIR), and tools/timeline.py merges
    them into a chrome trace + summary. The ranks load the bus
    standalone (no jax import) so this is launcher-speed, not
    interpreter-startup-speed."""

    CHILD = '''
import importlib.util, os, sys, time

spec = importlib.util.spec_from_file_location(
    "obs_bus", os.path.join(sys.argv[1], "paddle_tpu", "observability",
                            "bus.py"))
bus = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bus)
assert bus.enabled(), "launcher did not provision PADDLE_OBS_DIR"
rank = int(os.environ["PADDLE_TRAINER_ID"])
for s in (4, 8):
    bus.set_step(s)
    bus.emit("step_metrics", {"steps": 4, "step_ms": 10.0 + 5 * rank,
                              "loss": 2.0, "tokens_per_sec": 1000.0})
if rank == 1:
    bus.emit("guard_skip", {"detail": "grads nonfinite", "consec": 1},
             step=6)
'''

    def test_launch_then_timeline(self, obs_env, tmp_path):
        import importlib.util
        import textwrap

        from paddle_tpu.distributed.launch import launch

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "child.py"
        script.write_text(textwrap.dedent(self.CHILD))
        log_dir = str(tmp_path / "logs")
        rc = launch(str(script), [repo], nproc_per_node=2,
                    backend="cpu", log_dir=log_dir)
        assert rc == 0
        # every rank produced its stream where the launcher pointed it
        assert os.path.exists(
            os.path.join(log_dir, "telemetry.rank0.jsonl"))
        assert os.path.exists(
            os.path.join(log_dir, "telemetry.rank1.jsonl"))
        spec = importlib.util.spec_from_file_location(
            "timeline", os.path.join(repo, "tools", "timeline.py"))
        timeline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(timeline)
        streams, _, trace, lines = timeline.merge(log_dir)
        # round 14: the launcher's EMBEDDED fleet monitor adds its own
        # rank −1 stream next to the per-rank ones
        assert set(streams) == {0, 1, -1}
        assert {e.get("pid") for e in trace["traceEvents"]} >= {0, 1}
        text = "\n".join(lines)
        assert "slowest ranks: rank 1" in text
        assert "guard events: 1" in text
        # ...and the guard trip was folded into an incident row before
        # the manager returned (the live-detection acceptance pin)
        incs = [r for r in streams[-1] if r["kind"] == "incident"]
        assert incs and "rank 1 guard_skip" in \
            incs[-1]["payload"]["chain"]


# ---------------------------------------------------------------------------
# capture-on-anomaly trace windows
# ---------------------------------------------------------------------------


@pytest.fixture
def fake_tracer(obs_env):
    """Recorded stand-ins for jax.profiler.start/stop_trace (a real
    XPlane capture is heavyweight and CPU-noisy)."""
    calls = {"start": [], "stop": 0}
    obs_env.setattr(jax.profiler, "start_trace",
                    lambda d, **kw: calls["start"].append(d))
    orig_stop = jax.profiler.stop_trace
    obs_env.setattr(jax.profiler, "stop_trace",
                    lambda: calls.__setitem__("stop", calls["stop"] + 1))
    yield calls
    del orig_stop


class TestTraceWindow:
    def test_arm_count_down_disarm(self, obs_env, tmp_path, fake_tracer):
        from paddle_tpu import profiler

        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        obs_env.setenv("PADDLE_OBS_TRACE_DIR", str(tmp_path / "tr"))
        assert profiler.arm_trace(steps=2, reason="unit")
        assert profiler.trace_window_state()["remaining"] == 2
        # second arm while one is pending: refused
        assert not profiler.arm_trace(steps=2)
        profiler.step_boundary(5)       # opens the window
        assert len(fake_tracer["start"]) == 1
        assert "step5" in fake_tracer["start"][0]
        profiler.step_boundary(6)       # second covered dispatch
        assert fake_tracer["stop"] == 0  # step 6's dispatch is INSIDE
        profiler.step_boundary(7)       # past the window -> stop
        assert fake_tracer["stop"] == 1
        assert profiler.trace_window_state() is None
        kinds = [r["kind"] for r in bus.read_stream(busf)]
        assert kinds == ["trace_armed", "trace_captured"]
        cap = bus.read_stream(busf)[1]["payload"]
        assert cap["first_step"] == 5 and cap["last_step"] == 6

    def test_budget_limits_windows(self, obs_env, tmp_path, fake_tracer):
        from paddle_tpu import profiler

        obs_env.setenv("PADDLE_OBS_TRACE_DIR", str(tmp_path / "tr"))
        obs_env.setenv("PADDLE_OBS_TRACE_MAX", "1")
        assert profiler.arm_trace(steps=1)
        profiler.step_boundary(1)       # opens; step 1 is the window
        assert fake_tracer["stop"] == 0
        profiler.step_boundary(2)       # closes BEFORE step 2 dispatch
        assert fake_tracer["stop"] == 1
        # budget spent: a second window is refused
        assert not profiler.arm_trace(steps=1)

    def test_no_destination_no_arm(self, obs_env, fake_tracer):
        from paddle_tpu import profiler

        assert not profiler.arm_trace(steps=2)
        profiler.step_boundary(1)
        assert not fake_tracer["start"]

    def test_env_arm_at_step(self, obs_env, tmp_path, fake_tracer):
        from paddle_tpu import profiler

        obs_env.setenv("PADDLE_OBS_TRACE_DIR", str(tmp_path / "tr"))
        obs_env.setenv("PADDLE_OBS_TRACE_AT_STEP", "3")
        obs_env.setenv("PADDLE_OBS_TRACE_STEPS", "2")
        for s in (1, 2):
            profiler.step_boundary(s)
        assert not fake_tracer["start"]
        profiler.step_boundary(3)       # arms AND opens at step 3
        assert len(fake_tracer["start"]) == 1
        assert "step3" in fake_tracer["start"][0]
        profiler.step_boundary(4)       # steps 3-4 are the window
        profiler.step_boundary(5)       # past it -> stop
        assert fake_tracer["stop"] == 1

    def test_guard_trip_arms_window(self, obs_env, tmp_path, fake_tracer):
        """The integration contract: an injected NaN step trips the
        guard, the trip arms the window, the NEXT steps are captured."""
        from paddle_tpu.utils import fault_injection

        busf = str(tmp_path / "bus.jsonl")
        obs_env.setenv("PADDLE_OBS_BUS_FILE", busf)
        obs_env.setenv("PADDLE_OBS_TRACE_DIR", str(tmp_path / "tr"))
        obs_env.setenv("PADDLE_OBS_TRACE_STEPS", "2")
        obs_env.setenv("PADDLE_GUARD_SYNC_EVERY", "1")
        os.environ["PADDLE_FAULT_SPEC"] = "grad:nan:2"
        fault_injection.reset()
        _, step = _mk_step()
        for _ in range(8):
            step(_X, _Y)
        assert fake_tracer["start"], "guard trip never armed the window"
        assert fake_tracer["stop"] == 1
        kinds = [r["kind"] for r in bus.read_stream(busf)]
        assert "trace_armed" in kinds and "trace_captured" in kinds
        armed = [r for r in bus.read_stream(busf)
                 if r["kind"] == "trace_armed"][0]
        assert armed["payload"]["reason"] == "guard_trip"

    def test_trip_arming_disabled_by_knob(self, obs_env, tmp_path,
                                          fake_tracer):
        from paddle_tpu.utils import fault_injection

        obs_env.setenv("PADDLE_OBS_TRACE_DIR", str(tmp_path / "tr"))
        obs_env.setenv("PADDLE_OBS_TRACE_ON_TRIP", "0")
        obs_env.setenv("PADDLE_GUARD_SYNC_EVERY", "1")
        os.environ["PADDLE_FAULT_SPEC"] = "grad:nan:2"
        fault_injection.reset()
        _, step = _mk_step()
        for _ in range(5):
            step(_X, _Y)
        assert not fake_tracer["start"]
