"""The trace reducer on a small recorded trace: two whole steps of
`gpt2-medium.train` on the v5e (my chip run, PR 25), kept as the plain
lists `trace_reduce.read_planes` gives."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

_DATA = os.path.join(os.path.dirname(__file__), "data",
                     "train_two_steps_v5e.json.gz")


@pytest.fixture(scope="module")
def planes():
    with gzip.open(_DATA, "rt") as f:
        return json.load(f)


def test_union_and_gaps():
    iv = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert tr.union_length(iv) == 22
    assert tr.gaps(iv, 0, 40) == [(12, 20), (30, 40)]
    assert tr.union_length([]) == 0


def test_split_op_tells_kernels_apart():
    name, sig = tr.split_op(
        "%attention__flash.88 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)},"
        " f32[128,1024,128]{2,1,0:T(8,128)S(1)}) custom-call(bf16[128,1024,"
        "64]{2,1,0} %bitcast.2545), custom_call_target=\"tpu_custom_call\"")
    assert name == "attention__flash"
    assert tr.first_shape(sig) == (2, [128, 1024, 64])


def test_recorded_trace(planes):
    r = tr.reduce(planes, chips=1, kind="train")
    # two whole launches of the one TrainStep program, 259.4 ms each
    step = r["programs"]["train_step"]
    assert step["launches"] == 2
    assert step["device_s"] / 2 == pytest.approx(0.25937, rel=1e-3)
    # busy is the union of the device's operation intervals
    assert r["busy_s"] == pytest.approx(0.51924, rel=1e-4)
    assert r["busy_s"] <= r["window_s"]
    k = {n: (len(v), sum(x["dur_s"] for x in v))
         for n, v in r["kernels"].items()}
    # 24 layers: one flash forward, one dQ and one dK/dV kernel a layer
    # a step; the LayerNorm kernels have no rule (PERF.md says why)
    assert k["flash_fwd"][0] == 48 and k["flash_bwd"][0] == 96
    assert sorted(k) == ["flash_bwd", "flash_fwd"]
    assert k["flash_fwd"][1] == pytest.approx(0.042669, rel=1e-3)
    assert k["flash_bwd"][1] == pytest.approx(0.151453, rel=1e-3)
    assert r["breakdown"]["device_ops"][0][0] == "attention__flash"
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_program_rules_pick_by_launch_count():
    mods = [("jit__step_fn(1)", i * 10.0, 5.0) for i in range(16)]
    mods += [("jit__step_fn(2)", 200.0, 50.0), ("jit__insert_fn(3)", 300., 1.)]
    rules = tr.load_names()["programs"]["serve"]
    got = tr.classify_programs(mods, rules)
    assert len(got["decode_step"]) == 16
    assert got["prefill_step"] == [(200.0, 50.0)]
    assert got["cache_insert"] == [(300.0, 1.0)]


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        tr.reduce({"/host:CPU": {"python3": [("bench.step", 0.0, 1.0)]}},
                  1, "train")
