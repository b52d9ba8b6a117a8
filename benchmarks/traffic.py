"""The one traffic generator: a mix file's parameters and a seed give a
schedule of requests (or a feed of training batches).

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps — the stratified quantiles of the mix's distributions — with
other token ids, and in another order unless the mix fixes the order, so
that two seeds offer the same work and differ only in how it falls.

The parts are found by the names the mix gives them (`find.load`): the
arrival process, each length's distribution, and the schedule that puts
them together. A mix that needs another brings it as a new file under
`generators/`.
"""
from __future__ import annotations

import json
import os

import numpy as np

import find

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    """The mix file `traffic/<name>.json`, found by name."""
    with open(os.path.join(_HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def quantiles(n: int) -> np.ndarray:
    """The middles of `n` equal strata of (0, 1): a generator that takes
    its values at these gives every seed the same multiset."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths of the distribution `generators/lengths/<dist>.py`."""
    return find.load("generators/lengths", spec["dist"]).lengths(spec, n)


def arrivals(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds), in arrival order, of the process
    `generators/arrivals/<process>.py`."""
    return find.load("generators/arrivals", spec["process"]).due(
        spec, seconds, rng)


def withdraws_at_close(mix: dict) -> bool:
    """Whether the mix's arrival process offers more than a window can
    finish, so that what is still queued at the close is withdrawn."""
    process = find.load("generators/arrivals", mix["arrivals"]["process"])
    return bool(getattr(process, "withdraw_at_close", False))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{due, prompt, max_new}] in arrival order, all from the seed, by
    `generators/schedules/<schedule>.py`; a mix that names none gets
    `standard`."""
    name = mix.get("schedule", "standard")
    return find.load("generators/schedules", name).schedule(
        mix, seed, seconds, vocab)


def train_batches(mix: dict, seed: int, n: int, vocab: int):
    """`n` batches of token ids [n, batch, seq + 1], every row different,
    made on the device in one call."""
    import jax

    import weights as W

    key = jax.random.fold_in(W.seed_key(seed), 0x7ac)
    return jax.jit(lambda k: jax.random.randint(
        k, (n, int(mix["batch"]), int(mix["seq"]) + 1), 0, vocab,
        dtype="int32"))(key)
