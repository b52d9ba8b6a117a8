"""The traffic generator: the same seed gives the same schedule, every
seed the same work, and every length lies inside its clips."""
import numpy as np
import pytest

import traffic as T


def _key(sched):
    return [(r["due"], r["max_new"], r["prompt"].tobytes()) for r in sched]


def test_same_seed_same_schedule_and_large_seeds():
    mix = T.load_mix("chat")
    a = T.schedule(mix, 3_000_000_019, 30, 50257)
    b = T.schedule(mix, 3_000_000_019, 30, 50257)
    c = T.schedule(mix, 5, 30, 50257)
    assert _key(a) == _key(b) and _key(a) != _key(c)


def test_every_seed_offers_the_same_work():
    for name in ("chat", "batch"):
        mix = dict(T.load_mix(name), order="seeded")
        a = T.schedule(mix, 1, 30, 50257)
        b = T.schedule(mix, 2, 30, 50257)
        assert sorted(len(r["prompt"]) for r in a) == \
            sorted(len(r["prompt"]) for r in b)
        assert sorted(r["max_new"] for r in a) == \
            sorted(r["max_new"] for r in b)
        # the same arrival gaps (the last one runs to the window's end)
        assert np.allclose(sorted(np.diff([r["due"] for r in a] + [30.0])),
                           sorted(np.diff([r["due"] for r in b] + [30.0])))


def test_lengths_and_arrivals_inside_their_bounds():
    for name in ("chat", "batch"):
        mix = dict(T.load_mix(name), order="seeded")
        sched = T.schedule(mix, 7, 30, 50257)
        pl, ol = mix["prompt_len"], mix["output_len"]
        for r in sched:
            assert pl["min"] <= len(r["prompt"]) <= pl["max"]
            assert ol["min"] <= r["max_new"] <= ol["max"]
            assert len(r["prompt"]) + r["max_new"] <= mix["max_total"]
            assert 0 <= r["due"] < 30
            assert 0 <= r["prompt"].min() and r["prompt"].max() < 50257
        due = [r["due"] for r in sched]
        assert due == sorted(due)
    chat = T.load_mix("chat")
    n = len(T.schedule(chat, 7, 30, 50257))
    assert n == round(chat["arrivals"]["rate_per_s"] * 30)
    med = np.median([len(r["prompt"]) for r in T.schedule(chat, 7, 30, 50257)])
    assert abs(med - chat["prompt_len"]["median"]) <= 4


@pytest.mark.parametrize("change,error,says", [
    ({"arrivals": {"process": "bursty", "rate_per_s": 8.0, "burst": 4}},
     FileNotFoundError, r"generators/arrivals/bursty\.py"),
    ({"prompt_len": {"dist": "fixed", "value": 64, "min": 16, "max": 768}},
     FileNotFoundError, r"generators/lengths/fixed\.py"),
    ({"schedule": "sessions"},
     FileNotFoundError, r"generators/schedules/sessions\.py"),
    ({"order": "shuffled"}, ValueError, "unknown order"),
])
def test_a_mix_the_generator_does_not_know_is_refused(change, error, says):
    """A process, a distribution or a schedule with no file says which
    file it looked for, so that a mix can bring it."""
    with pytest.raises(error, match=says):
        T.schedule(dict(T.load_mix("chat"), **change), 1, 30, 50257)


def test_train_batches_rows_all_differ():
    mix = T.load_mix("train")
    mix = dict(mix, batch=4, seq=32)
    a = np.asarray(T.train_batches(mix, 11, 3, 503))
    b = np.asarray(T.train_batches(mix, 11, 3, 503))
    assert a.shape == (3, 4, 33) and (a == b).all()
    rows = a.reshape(12, 33)
    assert len({r.tobytes() for r in rows}) == 12


def test_fixed_order_changes_only_the_ids():
    mix = T.load_mix("chat")
    assert mix["order"] == "fixed"
    a = T.schedule(mix, 1, 30, 50257)
    b = T.schedule(mix, 2, 30, 50257)
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in b]
    assert _key(a) != _key(b)
    seeded = dict(mix, order="seeded")
    c = T.schedule(seeded, 1, 30, 50257)
    assert [len(r["prompt"]) for r in c] != [len(r["prompt"]) for r in a]
