"""The comparison that decides `correct`: numbers compared, each beside
its limit. The limits of a cell live in `limits/<workload>.json`, set
from the readings PERF.md records."""
from __future__ import annotations

import json
import math
import os
import statistics

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload: str, rehearse: bool = False) -> dict:
    """The cell's limits. The builder's CPU rehearsal runs a toy size in
    other arithmetic, so it reads the file's `_rehearse` group instead."""
    with open(os.path.join(_HERE, "limits", f"{workload}.json")) as f:
        limits = json.load(f)
    return limits["_rehearse"] if rehearse else limits


def compared(values: dict, limits: dict) -> list:
    """[{name, value, limit}] for every number the cell's limits file
    names; a limit whose number the run did not read is an error."""
    out = []
    for name, limit in limits.items():
        if name.startswith("_"):      # notes beside the limits
            continue
        if name not in values:
            raise KeyError(f"the cell's limits file names {name!r}, which "
                           f"this run did not read: it has {sorted(values)}")
        out.append({"name": name, "value": float(values[name]),
                    "limit": float(limit)})
    if not out:
        raise KeyError("the cell's limits file holds no limit")
    return out


def verdict(rows: list) -> bool:
    return all(math.isfinite(r["value"]) and r["value"] <= r["limit"]
               for r in rows)


def report(rows: list, correct: bool, stream) -> None:
    for r in rows:
        flag = "ok" if (math.isfinite(r["value"])
                        and r["value"] <= r["limit"]) else "OVER"
        print(f"compared {r['name']}: {r['value']:.6g} "
              f"(limit {r['limit']:.6g}) {flag}", file=stream)
    print(f"correct: {str(correct).lower()}", file=stream, flush=True)


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """The worst leaf's gap between the program's norm and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Returns (gap, leaf)."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, med)
        if not gap <= worst:       # also catches nan
            worst, where = gap, name
    return worst, where


def dead_leaves(ref_grad: dict) -> set:
    """Leaves whose gradient is nought to rounding in the reference (under
    a thousandth of the median leaf's): Adam moves them by round-off
    alone, so their change is not compared."""
    med = statistics.median(ref_grad.values())
    return {n for n, g in ref_grad.items() if g < 1e-3 * med}


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [...], "grad": {leaf: norm}, "delta": {...}}"""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"])
    skip = dead_leaves(ref["grad"])
    delta_gap, delta_leaf = worst_leaf_gap(prog["delta"], ref["delta"], skip)
    return {"values": {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
                       "delta_norm_gap": delta_gap},
            "where": {"grad_norm_gap": grad_leaf,
                      "delta_norm_gap": delta_leaf,
                      "dead_leaves": sorted(skip)}}
