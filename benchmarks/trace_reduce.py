"""From the profiler's trace (`*.xplane.pb`) to numbers.

What a trace looks like on this chip today (looked at by hand, PR 25):
one plane a chip, `/device:TPU:<i>`, with the lines `XLA Modules` (one
event a launch of a jitted program, named `jit_<function>(<fingerprint>)`)
and `XLA Ops` (one event an HLO instruction; the event's name is the
instruction's text, `%<name>.<n> = <result shapes> <opcode>(...)`). A
Pallas kernel is a `custom-call` instruction named after the innermost
`jax.named_scope` around it (`attention__flash`, `layer_norm__fused`),
so kernels that share a scope are told apart by their result shapes. The
host's threads are lines of the plane `/host:CPU`; the `TraceAnnotation`
spans of the harness and the `TraceMe` spans of JAX's own dispatch
(`PjitFunction(...)`, `DevicePut`) sit on the line of the Python thread. The
Python tracer is off: it made stopping the profiler take 27 s.

The map from trace names to kernels and programs is data:
`trace_names/*.json`, all files merged, so a PR that gives kernels and
steps stable names adds a file and edits none.
"""
from __future__ import annotations

import glob
import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1, "f64": 8,
             "s64": 8}


def load_names() -> dict:
    out = {"kernels": [], "programs": {}}
    for path in sorted(glob.glob(os.path.join(_HERE, "trace_names",
                                              "*.json"))):
        with open(path) as f:
            part = json.load(f)
        out["kernels"] += part.get("kernels", [])
        for kind, rows in part.get("programs", {}).items():
            out["programs"].setdefault(kind, []).extend(rows)
    return out


def split_op(text: str):
    """An `XLA Ops` event name -> (instruction name without its number,
    result signature)."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    sig = rest.split(" custom-call(")[0] if " custom-call(" in rest \
        else rest.split(" ", 1)[0]
    return name, sig


def first_shape(sig: str):
    """(itemsize, dims) of the first array of a result signature."""
    m = _SHAPE.search(sig)
    if not m:
        return None
    dims = [int(x) for x in m.group(2).split(",") if x]
    return _ITEMSIZE.get(m.group(1)), dims


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle gaps (start, end) of [lo, hi] not covered by intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def read_planes(path: str):
    """The trace as plain lists: {plane: {line: [(name, start_ns, dur_ns)]}}
    for the device planes and the host plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not (_DEVICE.match(plane.name) or plane.name == "/host:CPU"):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
        out[plane.name] = lines
    return out


def classify_programs(modules, rules):
    """Module events -> {program: [(start, dur)]} by the kind's rules. A
    rule picks the modules whose name matches `module`; `pick` chooses
    among the fingerprints that match: `all`, `most_launches` (the one
    fingerprint launched most often) or `rest` (the others)."""
    by_print = {}
    for name, start, dur in modules:
        by_print.setdefault(name, []).append((start, dur))
    out = {}
    for rule in rules:
        rx = re.compile(rule["module"])
        match = {n: v for n, v in by_print.items() if rx.search(n)}
        if not match:
            continue
        pick = rule.get("pick", "all")
        top = max(match, key=lambda n: len(match[n]))
        if pick == "most_launches":
            chosen = [top]
        elif pick == "rest":
            chosen = [n for n in match if n != top]
        else:
            chosen = list(match)
        evs = sorted(e for n in chosen for e in match[n])
        if evs:
            out.setdefault(rule["program"], []).extend(evs)
    return out


def whole_launches(evs, lo, hi):
    """Launches that lie inside the traced window with room to spare: the
    first and the last may be cut by the window's edges."""
    inside = [(s, d) for s, d in evs if s > lo and s + d < hi]
    return inside[1:-1] if len(inside) >= 4 else inside


def host_spans(host_lines):
    """The spans of the harness's own thread, the line that holds its
    `bench.*` annotations, as (names, starts, ends) arrays."""
    import numpy as np

    rows = [(name, s, s + d) for evs in host_lines.values()
            if any(n.startswith("bench.") for n, _, _ in evs)
            for name, s, d in evs if d > 0]
    return ([r[0] for r in rows], np.array([r[1] for r in rows], float),
            np.array([r[2] for r in rows], float))


def host_label(spans, t):
    """What the host was doing at time t: inside which of the harness's
    `bench.*` calls, and there the outermost span below it (the jitted
    call or eager operation the program had dispatched)."""
    import numpy as np

    names, starts, ends = spans
    over = np.flatnonzero((starts <= t) & (ends >= t))
    if not over.size:
        return "(no host span)"
    by_len = sorted(over, key=lambda i: starts[i] - ends[i])   # longest first
    bench = [k for k, i in enumerate(by_len)
             if names[i].startswith("bench.")]
    if not bench:
        return names[by_len[0]]
    inner = by_len[bench[-1] + 1:]          # what the innermost call holds
    call = names[by_len[bench[-1]]]
    return f"{call} > {names[inner[0]]}" if inner else call


def reduce(planes: dict, chips: int, kind: str, names: dict = None) -> dict:
    names = names or load_names()
    dev = sorted((p for p in planes if _DEVICE.match(p)),
                 key=lambda p: int(_DEVICE.match(p).group(1)))[:chips]
    if not dev:
        raise RuntimeError("the trace holds no /device:TPU plane")
    # the window: from the first to the last thing the device or the
    # harness did (its `bench.*` spans), which leaves out the profiler's
    # own starting and stopping
    every = [(s, s + d) for p in dev for evs in planes[p].values()
             for _, s, d in evs]
    every += [(s, s + d) for evs in planes.get("/host:CPU", {}).values()
              for n, s, d in evs if n.startswith("bench.")]
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    busy = []
    for p in dev:
        ops = planes[p].get("XLA Ops", [])
        busy.append(union_length((s, s + d) for _, s, d in ops if d > 0))
    busy_s = sum(busy) / len(busy) / 1e9
    if busy_s <= 0:
        raise RuntimeError("no operation ran on the device in the trace")

    d0 = planes[dev[0]]
    programs = classify_programs(d0.get("XLA Modules", []),
                                 names["programs"].get(kind, []))
    prog_out = {}
    for prog, evs in programs.items():
        whole = whole_launches(evs, lo, hi)
        prog_out[prog] = {
            "launches": len(whole),
            "device_s": sum(d for _, d in whole) / 1e9,
            "all_device_s": sum(d for _, d in evs) / 1e9}

    kern = {}
    by_op = {}
    seen = {}     # an instruction's text -> (name, its kernel rule, shape)
    for text, s, d in d0.get("XLA Ops", []):
        if text not in seen:
            name, sig = split_op(text)
            rule = next((r for r in names["kernels"]
                         if re.search(r["name"], name)
                         and re.search(r.get("signature", ""), sig)), None)
            seen[text] = (name, rule, first_shape(sig) if rule else None)
        name, rule, shape = seen[text]
        by_op[name] = by_op.get(name, 0.0) + d
        if rule is not None:
            kern.setdefault(rule["kernel"], []).append(
                {"rule": rule, "dur_s": d / 1e9, "shape": shape})
    # `while` and `conditional` hold other instructions: keep the leaves
    leaves = {n: v for n, v in by_op.items()
              if n not in ("while", "conditional", "call")}
    top_ops = sorted(leaves.items(), key=lambda kv: -kv[1])[:10]

    ops0 = [(s, s + d) for _, s, d in d0.get("XLA Ops", []) if d > 0]
    idle = {}
    host = host_spans(planes.get("/host:CPU", {}))
    longest = sorted(gaps(ops0, lo, hi), key=lambda g: g[0] - g[1])[:1000]
    for s, e in longest:
        label = host_label(host, (s + e) / 2)
        idle[label] = idle.get(label, 0.0) + (e - s)
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s, "window_s": (hi - lo) / 1e9,
        "programs": prog_out, "kernels": kern,
        "breakdown": {
            "device_ops": [[n, v / 1e9] for n, v in top_ops],
            "idle_gaps": [[n, v / 1e9] for n, v in top_idle]},
    }


def reduce_file(path: str, chips: int, kind: str) -> dict:
    return reduce(read_planes(path), chips, kind)


if __name__ == "__main__":
    import sys

    r = reduce_file(sys.argv[1], 1, sys.argv[2])
    r["kernels"] = {k: {"calls": len(v), "device_s": sum(x["dur_s"] for x in v)}
                    for k, v in r["kernels"].items()}
    print(json.dumps(r, indent=1))
