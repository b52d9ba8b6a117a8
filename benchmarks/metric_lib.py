"""What the per-metric readers share: percentiles, and the reduction from
a traced run's kernels and programs to roofline and utilization shares.
What a count needs of the architecture it asks of `ctx["family"]`, the
family of the cell's configuration. Every function returns None where it
finds nothing to read."""
from __future__ import annotations

import numpy as np

import counters


def p95(xs):
    return float(np.percentile(xs, 95)) if len(xs) else None


def peak(ctx):
    return counters.peaks(ctx["device"]["kind"])


def chips(ctx) -> int:
    return int(ctx["cell"]["chips"])


# -- training ---------------------------------------------------------------


def train_tokens_per_s(ctx):
    w = ctx["window"]
    return w["tokens"] / w["window_s"]


def mfu_train(ctx):
    per_token = ctx["family"].train_flops_per_token(ctx["sizes"],
                                                    ctx["seq"])
    return 100.0 * per_token * train_tokens_per_s(ctx) / (
        chips(ctx) * peak(ctx)["flops_per_s"])


def program_ms(ctx, program):
    p = (ctx.get("trace") or {}).get("programs", {}).get(program)
    if not p or not p["launches"]:
        return None
    return p["device_s"] / p["launches"] * 1e3


def device_idle_pct(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_roofline(ctx, kernel):
    """Share of its roofline a kernel reached over all its calls in the
    traced window: the least time the chip could take for the calls'
    operations and bytes, counted from the configuration's shapes, over
    the kernel's summed device time."""
    calls = (ctx.get("trace") or {}).get("kernels", {}).get(kernel)
    if not calls:
        return None
    pk = peak(ctx)
    b, h, seq, dh = ctx["family"].flash_call_shape(
        ctx["sizes"], ctx["batch"], ctx["seq"])
    least = spent = 0.0
    for c in calls:
        rule = c["rule"]
        itemsize = (c["shape"] or (None,))[0] or 2
        spent += c["dur_s"]
        if rule["counter"] == "flash_fwd":
            fl, by = counters.flash_fwd(b, h, seq, dh, itemsize)
        elif rule["counter"] == "flash_bwd":
            # dQ and dK/dV are two kernels of one backward: the dK/dV call
            # carries the backward's count, the dQ call only its time
            if rule.get("part") != "dkv":
                continue
            fl, by = counters.flash_bwd(b, h, seq, dh, itemsize)
        else:
            raise KeyError(f"unknown counter {rule['counter']!r}")
        least += counters.roofline_seconds(fl, by, pk)[0]
    return 100.0 * least / spent if spent > 0 else None


# -- serving ----------------------------------------------------------------


def serve_tokens_per_s(ctx):
    sv = ctx["serve"]
    return sv["tokens_at_close"] / sv["closed"]


def mfu_serve(ctx):
    sv = ctx["serve"]
    flops = ctx["family"].serve_flops(ctx["sizes"], **sv["flops"])
    return 100.0 * flops / (sv["closed"] * chips(ctx)
                            * peak(ctx)["flops_per_s"])


def slot_occupancy_pct(ctx):
    occ = ctx["serve"]["occupancy"]
    return 100.0 * float(np.mean(occ)) if occ else None


def prefill_share_pct(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    progs = t["programs"]
    pre = sum(progs[p]["all_device_s"] for p in ("prefill_step",
                                                 "cache_insert")
              if p in progs)
    return 100.0 * pre / t["busy_s"] if pre else None


def decode_hbm_roofline(ctx):
    ms = program_ms(ctx, "decode_step")
    t = ctx.get("trace")
    if ms is None:
        return None
    on, off = t["host_window"]
    live = [x[3] for x in ctx["pump"]["turns"] if on <= x[0] <= off]
    if not live:
        return None
    nbytes = ctx["family"].decode_step_bytes(ctx["sizes"],
                                             float(np.mean(live)))
    return 100.0 * (nbytes / peak(ctx)["hbm_bytes_per_s"]) / (ms / 1e3)
