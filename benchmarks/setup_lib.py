"""What the readers of set-up's compile records share.

`observability.ledger.compile_stages()` holds one record `(stage,
start_ns, end_ns, label)` for every trace, lowering, XLA compile and
persistent-cache read of every program the process made, eager ones
included, and one of no length for each request, hit and miss of the
persistent cache, on `time.perf_counter_ns()`: the clock of `run.py`'s
`T_START`, from which `setup_s` is measured. Set-up is `[T_START,
T_START + setup_s]`, and each record is clipped to it: the reference
compiles a great deal once the window has closed, before the readers
run. Records nest (a jit called inside another's trace is traced inside
it), so a stage's seconds are the union of its records, never their sum.
Every function returns None where the program keeps no such records, as
a program from before the ledger kept them does not, or set-up holds
none of them.
"""
from __future__ import annotations

import sys

#: the stages whose records have a length
DURATIONS = ("trace", "lower", "xla", "cache_read")


def _t_start():
    """`run.py`'s `T_START`: the process's start on `perf_counter`."""
    for name in ("__main__", "run"):
        t = getattr(sys.modules.get(name), "T_START", None)
        if isinstance(t, float):
            return t
    return None


def records(ctx):
    """The ledger's records clipped to set-up, or None."""
    from paddle_tpu.observability import ledger

    read = getattr(ledger, "compile_stages", None)
    t0, setup_s = _t_start(), ctx.get("setup_s")
    if read is None or t0 is None or setup_s is None:
        return None
    lo, hi = int(t0 * 1e9), int((t0 + setup_s) * 1e9)
    out = []
    for stage, start, end, label in read(until_ns=hi):
        start, end = max(start, lo), min(end, hi)
        if start <= end:
            out.append((stage, start, end, label))
    return out


def union_s(ctx, stages):
    """Seconds of set-up covered by a record of one of `stages`: 0 where
    set-up holds records of other stages only (a warm run compiles
    nothing), None where it holds none at all."""
    recs = records(ctx)
    if not recs:
        return None
    spans = sorted((s, e) for stage, s, e, _ in recs if stage in stages)
    if not spans:
        return 0.0
    total, cur_s, cur_e = 0, *spans[0]
    for s, e in spans[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return (total + cur_e - cur_s) / 1e9


def count(ctx, stage):
    """How often `stage` fired in set-up; None without records."""
    recs = records(ctx)
    if recs is None:
        return None
    return sum(1 for r in recs if r[0] == stage)
