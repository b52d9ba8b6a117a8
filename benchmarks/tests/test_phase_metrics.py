"""The names and phases of PR 26 on a small recorded serving trace: two
whole turns of `gpt2-medium.chat`'s traced window on the v5e (my chip
run, PR 26; the file's `_about` says how it was cut), and the readers of
`phase_lib` on it and on a trace from before the program had spans."""
import gzip
import json
import os

import pytest

import metrics
import phase_lib
import trace_reduce as tr

_DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with gzip.open(os.path.join(_DATA, name), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def planes():
    return _load("chat_two_turns_v5e.json.gz")


@pytest.fixture(scope="module")
def reduced(planes):
    return tr.reduce(planes, chips=1, kind="serve")


def test_programs_are_found_by_name(planes, reduced):
    mods = planes["/device:TPU:0"]["XLA Modules"]
    count = {}
    for name, _, _ in mods:
        count[name.split("(")[0]] = count.get(name.split("(")[0], 0) + 1
    # two turns of 16 decode steps; four admissions, each one prefill and
    # one insert; 742 launches in all, most of them eager operations
    assert count["jit_DecodeStep"] == 32
    assert count["jit_PrefillStep"] == count["jit_CacheInsert"] == 4
    assert len(mods) == 742
    assert "jit__step_fn" not in count and "jit__insert_fn" not in count
    p = reduced["programs"]
    # `whole_launches` leaves out the first and the last inside the window
    assert p["decode_step"]["launches"] == 30
    assert p["prefill_step"]["launches"] == p["cache_insert"]["launches"] == 2
    assert p["launches"]["launches"] == 740
    assert p["decode_step"]["all_device_s"] == pytest.approx(1.92340927)
    assert "prefix_fetch" not in p          # the contiguous cache has none


def _gaps_inside(planes, span_name):
    """By hand: seconds of the device's idle gaps (over 2 ns, as the file
    keeps them) whose middle lies inside a host span of that name."""
    host, = planes["/host:CPU"].values()
    turns = [(s, s + d) for n, s, d in host if n == "bench.turn"]
    # the window runs from the first turn's start to the last one's end
    lo, hi = min(turns)[0], max(turns)[1]
    ops = [(lo, lo)] + sorted((s, s + d) for _, s, d in
                              planes["/device:TPU:0"]["XLA Ops"]) + [(hi, hi)]
    spans = [(s, s + d) for n, s, d in host if n == span_name]
    total = 0.0
    for (_, end), (start, _) in zip(ops, ops[1:]):
        mid = (end + start) / 2
        if start > end and any(a <= mid <= b for a, b in spans):
            total += start - end
    return total / 1e9


def test_gaps_bear_the_phase_the_host_was_in(planes, reduced):
    idle = dict(reduced["breakdown"]["idle_gaps"])
    # all but 0.57 ms of the 0.234 s the device idled lies under a phase
    assert idle.pop("bench.turn") == pytest.approx(0.000567184)
    assert all(k.startswith("bench.turn > engine.") for k in idle)
    assert sum(idle.values()) / (reduced["window_s"] - reduced["busy_s"]) \
        == pytest.approx(0.9976, abs=1e-4)
    # the fresh batch-1 cache of an admission is the largest item
    assert max(idle, key=idle.get) == "bench.turn > engine.slot_cache"
    # the reducer labels the 1,000 longest gaps; the few-ns gaps between
    # the runs of one decode step that it leaves out add under 2 us
    for phase in ("slot_cache", "first_token", "readback"):
        assert idle[f"bench.turn > engine.{phase}"] == pytest.approx(
            _gaps_inside(planes, f"engine.{phase}"), abs=2e-6)


def test_readers_on_the_recorded_trace(reduced):
    ctx = {"trace": reduced}
    w = 2.186081579                                   # window_s
    assert reduced["window_s"] == pytest.approx(w)
    # slot_cache + first_token + insert + prefill + first_token_read +
    # admit (no chunked prefill in this cell), over the window
    admission = (0.124439211 + 0.043018954 + 0.026211594 + 0.011606444
                 + 0.004585491 + 0.000785211)
    assert metrics.reader("idle_admission_pct.chat")(ctx) == pytest.approx(
        100 * admission / w)                          # 9.636 %
    # collect + readback + turn_tail
    collect = 0.012231954 + 0.010568279 + 0.000300708
    assert metrics.reader("idle_collect_pct.chat")(ctx) == pytest.approx(
        100 * collect / w)                            # 1.057 %
    assert metrics.reader("launches_per_decode_step.chat")(ctx) \
        == pytest.approx(740 / 30)
    for cell in ("chat", "batch"):                    # one reader, two names
        assert metrics.reader(f"idle_admission_pct.{cell}")(ctx) \
            + metrics.reader(f"idle_collect_pct.{cell}")(ctx) \
            <= 100 * (1 - reduced["busy_s"] / w)


def test_readers_find_nothing_without_spans():
    """A trace from before PR 26 (the recorded training steps: no
    `engine.*` span, no serving program) and a run that was not traced."""
    old = tr.reduce(_load("train_two_steps_v5e.json.gz"), chips=1,
                    kind="train")
    for ctx in ({"trace": old}, {"trace": None}, {}):
        for name in ("idle_admission_pct.chat", "idle_admission_pct.batch",
                     "idle_collect_pct.chat", "idle_collect_pct.batch",
                     "launches_per_decode_step.chat",
                     "launches_per_decode_step.batch"):
            assert metrics.reader(name)(ctx) is None, (name, ctx.keys())
    # decode steps named the old way: the old rules still find them, the
    # new `launches` rule counts every module beside them
    mods = [("jit__step_fn(1)", 10.0 * i, 5.0) for i in range(8)]
    mods += [("jit_broadcast_in_dim(2)", 10.0 * i + 6, 1.0) for i in range(8)]
    got = tr.classify_programs(mods, tr.load_names()["programs"]["serve"])
    assert len(got["decode_step"]) == 8 and len(got["launches"]) == 16


def test_counters_of_the_program():
    import paddle_tpu
    from paddle_tpu.observability import ledger

    assert phase_lib.import_seconds({}) == paddle_tpu.import_seconds > 0
    assert phase_lib.compile_seconds({}) == ledger.compile_seconds()
