"""What the readers of the program's own spans and counters share (PR 26).

The program marks the phases of `InferenceEngine.turn` with
`paddle_tpu.profiler.phase` (`engine.<phase>`), flat siblings under the
harness's `bench.turn`, so `trace_reduce.host_label` names an idle gap of
the device `bench.turn > engine.<phase>`. The readers below sum the listed
gaps (`breakdown.idle_gaps`, the ten largest labels) of a group of phases.
Every function returns None where the program has no such span, program
or counter, as a program from before PR 26 has not.
"""
from __future__ import annotations

#: what the host does for one request before it decodes
ADMISSION = ("prefill_chunk", "admit", "slot_cache", "prefill",
             "first_token", "insert", "first_token_read")
#: what the host does once a turn after the decode steps are dispatched
COLLECT = ("readback", "collect", "turn_tail")


def idle_under_pct(ctx, phases):
    """Share of the traced window in which the device sat idle while the
    host was inside one of the engine's `phases`."""
    t = ctx.get("trace")
    if not t:
        return None
    labels = {f"bench.turn > engine.{p}" for p in phases}
    found = [s for label, s in t["breakdown"]["idle_gaps"]
             if label in labels]
    if not found:
        return None
    return 100.0 * sum(found) / t["window_s"]


def launches_per_decode_step(ctx):
    """Every program the window launched, eager operations included, over
    the decode steps among them."""
    progs = (ctx.get("trace") or {}).get("programs", {})
    every, decode = progs.get("launches"), progs.get("decode_step")
    if not every or not decode or not decode["launches"]:
        return None
    return every["launches"] / decode["launches"]


def compile_seconds(ctx):
    from paddle_tpu.observability import ledger

    read = getattr(ledger, "compile_seconds", None)
    return None if read is None else read()


def import_seconds(ctx):
    import paddle_tpu

    return getattr(paddle_tpu, "import_seconds", None)
