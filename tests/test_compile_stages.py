"""The compile ledger's stage records (`observability.ledger.
compile_stages`) and the benchmark's five readers of set-up built on them
(`benchmarks/metrics/setup_*.py`)."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu
from paddle_tpu.observability import ledger

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import find  # noqa: E402

_DURATIONS = ("trace", "lower", "xla", "cache_read")


@pytest.fixture
def clean_ledger():
    ledger.reset()
    yield
    ledger.reset()


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache in a fresh directory (the forced
    CPU harness turns it off)."""
    from jax._src import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    was_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was_dir)
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _union_s(recs):
    spans = sorted((r[1], r[2]) for r in recs)
    total, end = 0, None
    for s, e in spans:
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def _stages(label=None, any_label=False):
    return [r for r in ledger.compile_stages()
            if any_label or r[3] == label]


def test_a_fresh_ledgered_jit_records_trace_lower_and_xla(clean_ledger):
    step = ledger.jit(lambda x: jnp.sin(x) * 2 + 1, "StageProbe")
    step(jnp.ones((6,)))
    mine = _stages("StageProbe")
    assert {"trace", "lower", "xla"} <= {r[0] for r in mine}
    for stage, start, end, _ in mine:
        assert start <= end


def test_an_eager_operation_is_recorded_without_a_label(clean_ledger):
    x = jnp.ones((3, 1, 4, 1, 5, 9, 2))
    jax.lax.cos(x)
    recs = _stages(any_label=True)
    assert any(r[0] == "xla" for r in recs)
    assert all(r[3] is None for r in recs)


def test_a_cache_hit_is_a_read_and_no_xla(clean_ledger, persistent_cache):
    fn = ledger.jit(lambda x: jnp.tanh(x) @ x.T, "CacheProbe")
    x = jnp.ones((4, 4))
    fn(x)
    first = [r[0] for r in _stages("CacheProbe")]
    assert "cache_miss" in first and "xla" in first
    assert "cache_hit" not in first
    jax.clear_caches()
    ledger.reset()
    fn(x)
    second = [r[0] for r in _stages("CacheProbe")]
    assert second.count("cache_request") == 1
    assert second.count("cache_hit") == 1
    assert "cache_read" in second
    assert "xla" not in second and "cache_miss" not in second
    assert "trace" in second and "lower" in second


def test_nested_jit_union_is_within_the_compiling_call(clean_ledger):
    kernel = jax.jit(lambda x: jnp.tanh(x) * 2)
    step = ledger.jit(lambda x: kernel(x) + kernel(x * 3), "NestedProbe")
    x = jnp.ones((7,))
    t0 = time.perf_counter_ns()
    step(x)
    t1 = time.perf_counter_ns()
    mine = [r for r in _stages("NestedProbe") if r[0] in _DURATIONS]
    assert mine
    assert all(t0 <= r[1] and r[2] <= t1 for r in mine)
    assert _union_s(mine) <= (t1 - t0) / 1e9


def test_cache_hit_calls_add_no_record(clean_ledger):
    step = ledger.jit(lambda x: x * x - 1, "HotProbe")
    x = jnp.ones((5,))
    step(x)
    n = len(ledger.compile_stages())
    for _ in range(100):
        step(x)
    assert len(ledger.compile_stages()) == n


def test_the_records_stay_within_their_bound(clean_ledger):
    for _ in range(ledger.STAGE_RECORDS_MAX + 5):
        ledger._on_count("/jax/compilation_cache/cache_misses")
    recs = ledger.compile_stages()
    assert len(recs) == ledger.STAGE_RECORDS_MAX
    assert recs[-1][0] == "cache_miss"


def test_threads_record_and_read_at_once(clean_ledger):
    """Compiles may fire their events from several threads while a
    compiling call labels its records and a reader copies them."""
    import threading

    writers, per = 16, 1000
    errors = []

    def write():
        try:
            for _ in range(per):
                ledger._on_count("/jax/compilation_cache/cache_misses")
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def read():
        try:
            for _ in range(50):
                ledger.compile_stages()
                ledger._label_since(0, "Reader")
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = ([threading.Thread(target=write) for _ in range(writers)]
               + [threading.Thread(target=read) for _ in range(2)])
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(ledger.compile_stages()) == writers * per


# --- the readers, on a synthetic set-up: T_START 100 s, setup_s 10 s -------

_S = 1_000_000_000
_READERS = ("setup_lower_s", "setup_xla_compile_s", "setup_cache_read_s",
            "setup_cache_hit_pct", "setup_outside_compile_s")


def _rec(stage, start, end=None):
    end = start if end is None else end
    return [stage, int(start * _S), int(end * _S), None]


_SYNTHETIC = [
    _rec("lower", 99.5, 100.5),          # straddles T_START: 0.5 s counts
    _rec("trace", 101, 103), _rec("lower", 102, 104),
    _rec("cache_request", 105.1), _rec("xla", 105, 106),
    _rec("cache_request", 106.1), _rec("cache_hit", 106.15),
    _rec("cache_request", 106.2), _rec("cache_hit", 106.25),
    _rec("cache_hit", 106.3), _rec("cache_read", 106.5, 107),
    _rec("cache_request", 109.4),
    _rec("xla", 109.5, 110.5),           # straddles the end: 0.5 s counts
    # after set-up (the reference's compiles): left out
    _rec("trace", 111, 115), _rec("cache_request", 112),
    _rec("cache_request", 112.05), _rec("xla", 112, 113),
    _rec("cache_hit", 112.1), _rec("cache_read", 112.2, 112.5),
]


@pytest.fixture
def synthetic_setup(clean_ledger, monkeypatch):
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                        raising=False)
    monkeypatch.setattr(paddle_tpu, "import_seconds", 0.5)
    return {"setup_s": 10.0}


def _read(name, ctx):
    return find.load("metrics", name).read(ctx)


def test_the_readers_keep_to_setup(synthetic_setup):
    ledger._stages.extend([list(r) for r in _SYNTHETIC])
    ctx = synthetic_setup
    assert _read("setup_lower_s", ctx) == pytest.approx(3.5)
    assert _read("setup_xla_compile_s", ctx) == pytest.approx(1.5)
    assert _read("setup_cache_read_s", ctx) == pytest.approx(0.5)
    assert _read("setup_cache_hit_pct", ctx) == pytest.approx(75.0)
    # 10 - 0.5 import - (0.5 + 3 + 1 + 0.5 + 0.5) of compile records
    assert _read("setup_outside_compile_s", ctx) == pytest.approx(4.0)


def test_the_readers_are_none_without_records(synthetic_setup):
    for name in _READERS:
        assert _read(name, synthetic_setup) is None, name
    ledger._stages.extend([list(r) for r in _SYNTHETIC[-6:]])
    for name in _READERS:
        assert _read(name, synthetic_setup) is None, name


def test_no_cache_request_reads_none_not_zero(synthetic_setup):
    ledger._stages.extend([_rec("trace", 101, 102), _rec("xla", 102, 103)])
    assert _read("setup_cache_hit_pct", synthetic_setup) is None
    assert _read("setup_xla_compile_s", synthetic_setup) == pytest.approx(1)
    # a stage that never fired in set-up reads 0, not None: a warm run
    # compiles nothing, and its line still carries the metric
    assert _read("setup_cache_read_s", synthetic_setup) == 0.0


def test_a_program_without_stage_records_reads_none(synthetic_setup,
                                                    monkeypatch):
    ledger._stages.extend([list(r) for r in _SYNTHETIC])
    monkeypatch.delattr(ledger, "compile_stages")
    for name in _READERS:
        assert _read(name, synthetic_setup) is None, name


def test_a_warm_setup_reads_zero_compile_not_none(synthetic_setup):
    # every program read from the persistent cache: no `xla` record, yet
    # the line has to carry setup_xla_compile_s, at 0
    ledger._stages.extend([
        _rec("trace", 101, 102), _rec("lower", 102, 103),
        _rec("cache_request", 103.1), _rec("cache_hit", 103.2),
        _rec("cache_read", 103.1, 104)])
    ctx = synthetic_setup
    assert _read("setup_xla_compile_s", ctx) == 0.0
    assert _read("setup_cache_hit_pct", ctx) == pytest.approx(100.0)
    assert _read("setup_cache_read_s", ctx) == pytest.approx(0.9)
    assert _read("setup_lower_s", ctx) == pytest.approx(2.0)
    assert _read("setup_outside_compile_s", ctx) == pytest.approx(6.6)
