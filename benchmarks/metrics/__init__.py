"""Per-metric readers, found by the metric's name in BENCHMARK.json.

`metrics/<name>.py` defines `read(ctx)`, which returns the metric's value
or None where it finds nothing to read; the harness then leaves the
metric out of the line. `ctx` is the run's context: the harness's clocks
and counters, and in a traced run the reduced trace under `ctx["trace"]`.
"""
from __future__ import annotations

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name: str):
    path = os.path.join(_HERE, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"BENCHMARK.json names the metric {name!r} but "
            f"benchmarks/metrics/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
