"""Per-metric readers, found by the metric's name in BENCHMARK.json.

`metrics/<name>.py` defines `read(ctx)`, which returns the metric's value
or None where it finds nothing to read; the harness then leaves the
metric out of the line. `ctx` is the run's context: the harness's clocks
and counters, and in a traced run the reduced trace under `ctx["trace"]`.
"""
from __future__ import annotations

import find


def reader(name: str):
    return find.load("metrics", name).read


def read_all(entries: list, workload: str, ctx: dict) -> dict:
    """{name: {"value", "unit"}} for the metrics of `entries` that this
    cell reports and whose reader found something."""
    out = {}
    for m in entries:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = reader(m["name"])(ctx)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
