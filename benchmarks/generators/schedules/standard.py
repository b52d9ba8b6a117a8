"""The schedule of a mix that names no other: independent requests, each
with a prompt of uniform ids, a prompt length and an output length drawn
from the mix's distributions, due as the mix's arrival process says."""
import numpy as np

import traffic


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
    due = traffic.arrivals(mix["arrivals"], seconds, order)
    n = len(due)
    plen = traffic.lengths(mix["prompt_len"], n)[order.permutation(n)]
    olen = traffic.lengths(mix["output_len"], n)[order.permutation(n)]
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
