"""The one traffic generator: a mix file's parameters and a seed give a
schedule of requests (or a feed of training batches).

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps — the stratified quantiles of the mix's distributions — with
other token ids, and in another order unless the mix fixes the order, so
that two seeds offer the same work and differ only in how it falls.
"""
from __future__ import annotations

import json
import os
import statistics

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    """The mix file `traffic/<name>.json`, found by name."""
    with open(os.path.join(_HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths: the stratified quantiles of the distribution, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds), in arrival order."""
    if spec["process"] == "all_at_zero":
        return np.zeros(int(spec["count"]))
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    n = max(int(round(spec["rate_per_s"] * seconds)), 1)
    gaps = -np.log1p(-_quantiles(n))[rng.permutation(n)]
    return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{due, prompt, max_new}] in arrival order, all from the seed. A
    mix's `order` is `seeded` (every seed another order of the same
    lengths and gaps) or `fixed`: every seed the same lengths at the same
    due times (the order drawn once, from `order_seed`) and only the
    token ids from the seed, for a tail over few requests, which the
    order alone moves by more than a change to the program would."""
    rng = np.random.default_rng(int(seed))
    if mix["order"] not in ("seeded", "fixed"):
        raise ValueError(f"unknown order {mix['order']!r}")
    order = rng if mix["order"] == "seeded" \
        else np.random.default_rng(int(mix["order_seed"]))
    due = arrivals(mix["arrivals"], seconds, order)
    n = len(due)
    plen = lengths(mix["prompt_len"], n)[order.permutation(n)]
    olen = lengths(mix["output_len"], n)[order.permutation(n)]
    cap = int(mix["max_total"])
    out = []
    for i in range(n):
        p, o = int(plen[i]), int(olen[i])
        if p + o > cap:
            o = cap - p
        out.append({"due": float(due[i]),
                    "prompt": rng.integers(0, vocab, size=p, dtype=np.int32),
                    "max_new": o})
    return out


def train_batches(mix: dict, seed: int, n: int, vocab: int):
    """`n` batches of token ids [n, batch, seq + 1], every row different,
    made on the device in one call."""
    import jax

    import weights as W

    key = jax.random.fold_in(W.seed_key(seed), 0x7ac)
    return jax.jit(lambda k: jax.random.randint(
        k, (n, int(mix["batch"]), int(mix["seq"]) + 1), 0, vocab,
        dtype="int32"))(key)
