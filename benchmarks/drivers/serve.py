"""The serving cells: `serving.InferenceEngine` under an open-loop pump.

One thread. Before every `engine.turn` the pump submits each request that
is due; it never waits for a reply before sending the next. Every time it
reads is its own clock around the engine's public calls: a request's first
token is "on the host" when the turn that produced it returns, which is
when a caller of this engine can first read it (`progress()`), and its
last token when the turn that finished it returns.
"""
from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

import checks
import device as device_mod
import find
import tracing
import traffic

#: how long past the close a due answer is waited for; in a traced run
#: counted from the return of the profiler's stop, which is the harness's
#: own time and not the engine's (it took 25-35 s of the 60, PERF.md)
_DRAIN_S = 60.0

#: the mix's sizes under `--rehearse`
TOY = {
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.7,
                   "min": 4, "max": 64},
    "output_len": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                   "min": 4, "max": 40},
    "max_total": 128, "engine": {"slots": 4, "max_length": 128}}


_T0 = time.perf_counter()     # `run` sets it to the process's start


def log(msg: str) -> None:
    print(f"[serve +{time.perf_counter() - _T0:.1f}s] {msg}", file=sys.stderr,
          flush=True)


def build(cfg: dict, mix: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import comm
    from paddle_tpu.serving import InferenceEngine

    paddle.seed(0)
    comm.set_hybrid_mesh(None)
    lm = find.family(cfg).serving_model(cfg, mix, seed)
    # the mix's `engine` group is the engine's own keyword arguments:
    # slots and max_length always, and whichever of block_size,
    # pool_blocks, prefill_chunk, prefix_cache, sync_every a mix sets
    engine = InferenceEngine(lm, **mix["engine"])
    return {"lm": lm, "engine": engine}


def warm(prog: dict, sched: list, mix: dict, vocab: int) -> dict:
    """Compile the shapes this cell's traffic reaches and no others: one
    request in each prefill bucket its prompt lengths fall in, run to the
    end, which also compiles the insert and the decode program."""
    from paddle_tpu.serving import Request
    from paddle_tpu.serving.engine import bucket_for

    engine = prog["engine"]
    cap = int(mix["engine"]["max_length"])
    buckets = sorted({bucket_for(len(r["prompt"]), cap) for r in sched})
    rng = np.random.default_rng(0)
    for b in buckets:
        n = min(b, cap - 2)
        engine.submit(Request(rng.integers(0, vocab, size=n), max_new_tokens=2))
    engine.run()
    got = (engine._decode.compiles, engine._prefill.compiles)
    if got != (1, len(buckets)):
        raise RuntimeError(
            f"warm-up compiled DecodeStep {got[0]}x and PrefillStep "
            f"{got[1]}x for buckets {buckets}: expected 1 and "
            f"{len(buckets)}")
    return {"buckets": buckets}


def pump(engine, sched: list, seconds: float, *, withdraw_at_close: bool,
         tracer=None) -> dict:
    """Offer the schedule for `seconds`, then wait for what is due.
    Returns the per-request clocks and the per-turn samples."""
    from paddle_tpu.serving import Request

    clock = time.perf_counter
    slots = engine.slots
    n = len(sched)
    rec = [{"due": r["due"], "n0": len(r["prompt"]), "want": r["max_new"],
            "submit": None, "first": None, "last": None,
            "tokens": None, "at_close": 0} for r in sched]
    rid_of = {}
    results = {}
    seen_done = set()
    turns = []       # (t_end, inflight, queue_depth, live_kv, tokens_total)
    finished_tokens = 0
    nxt = 0
    closed = drain_from = None
    t0 = clock()
    while True:
        now = clock() - t0
        if tracer is not None:
            tracer.poll(now)
        while nxt < n and sched[nxt]["due"] <= now:
            r = sched[nxt]
            req = Request(r["prompt"], max_new_tokens=r["max_new"])
            with tracing.annotate("bench.submit"):
                engine.submit(req)
            rec[nxt]["submit"] = clock() - t0
            rid_of[req.rid] = nxt
            nxt += 1
        if closed is None and now >= seconds and nxt >= n:
            closed = now
            prog = engine.progress()
            for rid, i in rid_of.items():
                if rec[i]["tokens"] is not None:
                    rec[i]["at_close"] = len(rec[i]["tokens"])
                elif rid in prog:
                    rec[i]["at_close"] = len(prog[rid])
            if withdraw_at_close:
                for rid, toks in prog.items():
                    if not toks and rec[rid_of[rid]]["first"] is None:
                        engine.cancel(rid)
                        rec[rid_of[rid]]["withdrawn"] = True
            drain_from = closed
            if tracer is not None:
                tracer.finish(now)
                drain_from = clock() - t0
        busy = engine.queue_depth() or engine.inflight()
        if closed is not None and (not busy or now > drain_from + _DRAIN_S):
            break
        if not busy:
            wait = (sched[nxt]["due"] - now) if nxt < n else (seconds - now)
            time.sleep(min(max(wait, 0.0), 0.001))
            continue
        with tracing.annotate("bench.turn"):
            engine.turn(results)
        t = clock() - t0
        prog = engine.progress()
        live = 0
        running = 0
        for rid, toks in prog.items():
            i = rid_of[rid]
            if toks:
                live += rec[i]["n0"] + len(toks)
                running += len(toks)
                if rec[i]["first"] is None:
                    rec[i]["first"] = t
        for rid, res in results.items():
            if rid in seen_done:
                continue
            seen_done.add(rid)
            i = rid_of[rid]
            rec[i]["tokens"] = list(res.tokens)
            rec[i]["last"] = t
            rec[i]["engine_ttft_ms"] = res.ttft_ms
            rec[i]["engine_prefill_ms"] = res.prefill_ms
            if rec[i]["first"] is None:
                rec[i]["first"] = t
            finished_tokens += len(res.tokens)
        turns.append((t, engine.inflight() / slots, engine.queue_depth(),
                      live, finished_tokens + running))
    end = clock() - t0
    return {"rec": rec, "turns": turns, "closed": closed, "end": end,
            "drain_s": end - drain_from}


def summarize(p: dict, sizes: dict, kv_bytes_per_token: int) -> dict:
    """The pump's clocks -> the numbers the metric readers read."""
    rec, closed = p["rec"], p["closed"]
    offered = [r for r in rec if not r.get("withdrawn")]
    worst = p["end"]
    ttft, tpot, late, qwait = [], [], [], []
    unfinished = wrong = 0
    for r in offered:
        late.append((r["submit"] - r["due"]) * 1e3)
        if r["tokens"] is None:
            unfinished += 1
            ttft.append((worst - r["due"]) * 1e3)
            continue
        ttft.append((r["first"] - r["due"]) * 1e3)
        qwait.append(late[-1] + r["engine_ttft_ms"] - r["engine_prefill_ms"])
        n = len(r["tokens"])
        if n != r["want"] or min(r["tokens"]) < 0 \
                or max(r["tokens"]) >= sizes["vocab"]:
            wrong += 1
        if n > 1:
            # tokens reach the host a readback at a time: those that came
            # with the first count as tokens, and add no time
            tpot.append((r["last"] - r["first"]) / (n - 1) * 1e3)
    # work inside the window [0, closed]
    tokens_at_close = max((t[4] for t in p["turns"] if t[0] <= closed),
                          default=0)
    pre_pairs = pre_tok = dec_pairs = dec_tok = 0
    for r in rec:
        m = r["at_close"]
        if m <= 0:
            continue
        n0 = r["n0"]
        pre_tok += n0
        pre_pairs += n0 * (n0 + 1) // 2
        dec_tok += m - 1
        dec_pairs += (m - 1) * n0 + m * (m - 1) // 2
    in_window = [t for t in p["turns"] if t[0] <= closed]
    return {
        # the cached bytes of the tokens the active slots hold
        "live_kv_bytes_mean": kv_bytes_per_token * float(
            np.mean([t[3] for t in in_window])) if in_window else None,
        "offered": len(offered), "unfinished": unfinished, "wrong": wrong,
        "ttft_ms": ttft, "tpot_ms": tpot, "lateness_ms": late,
        "queue_wait_ms": qwait, "closed": closed,
        "tokens_at_close": tokens_at_close,
        "occupancy": [t[1] for t in in_window],
        "flops": {"prefill_pairs": pre_pairs, "prefill_tokens": pre_tok,
                  "decode_pairs": dec_pairs, "decode_tokens": dec_tok},
    }


def pick_sample(rec: list, k: int, seed: int) -> list:
    """`k` finished requests drawn from the seed, the longest among them."""
    done = [i for i, r in enumerate(rec) if r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda i: rec[i]["n0"] + len(rec[i]["tokens"]))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(int(seed) + 0x5e7)
    take = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[j] for j in take]


def _gap_numbers(g, spread) -> dict:
    """`token_gap_pow4` is the number compared: the mean fourth power of
    the served tokens' gaps, a widest gap softened over all tokens (PERF.md
    says why the widest gap alone does not tell the program from its
    control). The others are logged beside it."""
    return {"token_gap_pow4": float((g.astype(np.float64) ** 4).mean()),
            "token_gap_max": float(g.max()), "token_gap_mean": float(g.mean()),
            "token_gap_rel_max": float((g / spread).max()),
            "not_argmax_share": float((g > 0).mean()),
            "tokens_compared": int(g.size),
            "logit_std": float(spread.mean())}


def gaps_of_sample(cfg, mix, seed, sched, rec, sample,
                   control=None) -> tuple:
    """Run the reference once over each sampled prompt with its served
    tokens; how far below the reference's best each served token lies.
    Returns the program's numbers and, with `control`, the same numbers
    of the token that the lower precision puts first at each position."""
    family = find.family(cfg)
    params = family.make(cfg, seed, form="stacked")
    cap = int(mix["engine"]["max_length"])
    g, c, s = [], [], []
    for i in sample:
        gap, cgap, spread = family.served_gaps(
            params, sched[i]["prompt"], rec[i]["tokens"], cfg=cfg,
            pad_to=cap, control=control)
        g.append(gap), c.append(cgap), s.append(spread)
    g, c, s = np.concatenate(g), np.concatenate(c), np.concatenate(s)
    return _gap_numbers(g, s), _gap_numbers(c, s) if control else None


def free(prog: dict) -> None:
    import jax

    engine = prog["engine"]
    leaves = jax.tree_util.tree_leaves(engine._state.astuple())
    leaves += [p._data for p in prog["lm"].parameters()]
    for a in leaves:
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()
    prog.clear()
    gc.collect()


def run(cell, cfg, mix, args, device, t_start, control=None):
    from paddle_tpu.observability import ledger

    global _T0
    _T0 = t_start
    family = find.family(cfg)
    s = family.sizes(cfg)
    sched = traffic.schedule(mix, args.seed, args.seconds, s["vocab"])
    log("imports and schedule done")
    prog = build(cfg, mix, args.seed)
    log("engine built")
    warmed = warm(prog, sched, mix, s["vocab"])
    compiles = ledger.compile_count()
    log(f"set-up done: {len(sched)} requests, buckets {warmed['buckets']}")
    tracer = tracing.Tracer(args) if args.trace else None
    setup_s = time.perf_counter() - t_start
    p = pump(prog["engine"], sched, args.seconds,
             withdraw_at_close=traffic.withdraws_at_close(mix),
             tracer=tracer)
    compiled_in_window = ledger.compile_count() - compiles
    peak = device_mod.memory_peak(int(cell["chips"]))
    free(prog)
    log(f"window and drain done after {p['end']:.1f} s, the drain "
        f"{p['drain_s']:.1f} s of {_DRAIN_S:.0f}"
        + (f", counted from the return of the profiler's stop; profiler "
           f"start/stop took {tracer.stall_s}" if tracer else ""))
    summ = summarize(p, s, family.kv_bytes_per_token(s))
    log(f"live K/V, mean over the window's turns: "
        f"{summ['live_kv_bytes_mean']} bytes")
    sample = pick_sample(p["rec"], int(mix["check_requests"]), args.seed)
    limits = checks.load_limits(cell["name"], args.rehearse)
    values, low = gaps_of_sample(cfg, mix, args.seed, sched, p["rec"],
                                 sample, control=control)
    values["wrong_answers"] = summ["wrong"]
    values["compiles_in_window"] = compiled_in_window
    log("observed " + json.dumps(values))
    compared = checks.compared(values, limits)
    ctx = {"setup_s": setup_s, "serve": summ, "sizes": s, "pump": p,
           "slots": int(mix["engine"]["slots"]), "observed": values}
    if low is not None:
        # the reference in the program's place answers every token and
        # compiles nothing in a window
        low.update(wrong_answers=0, compiles_in_window=0)
        ctx["control"] = {"observed": low,
                          "compared": checks.compared(low, limits)}
    extra = {"memory_peak_bytes": peak}
    if tracer is not None:
        ctx["trace"] = tracer.reduce(chips=int(cell["chips"]), kind="serve")
        ctx["breakdown"] = ctx["trace"]["breakdown"]
        extra.update(busy_s=ctx["trace"]["busy_s"],
                     window_s=ctx["trace"]["window_s"])
    return {"ctx": ctx, "attempted": summ["offered"],
            "failed": summ["unfinished"], "compared": compared,
            "device": extra}


def control(cell, cfg, mix, args) -> None:
    """Builder-only: one short window at the cell's own load, then the
    program's gaps and the lower precision's at the same positions, each
    judged under the cell's own limits: the control has to come out as
    not correct."""
    import metric_lib

    out = run(cell, cfg, mix, args, None, time.perf_counter(),
              control=mix["control"])
    ctx = out["ctx"]
    verdicts = {"program": checks.verdict(out["compared"])
                and out["failed"] == 0,
                mix["control"]: checks.verdict(ctx["control"]["compared"])}
    print("-- the program", file=sys.stderr)
    checks.report(out["compared"], verdicts["program"], sys.stderr)
    print(f"-- {mix['control']} in the program's place", file=sys.stderr)
    checks.report(ctx["control"]["compared"], verdicts[mix["control"]],
                  sys.stderr)
    sv = ctx["serve"]
    print(json.dumps({
        "seed": args.seed, "workload": cell["name"], "correct": verdicts,
        "program": ctx["observed"], mix["control"]: ctx["control"]["observed"],
        "window": {"requests": sv["offered"], "unfinished": sv["unfinished"],
                   "ttft_p95_ms": metric_lib.p95(sv["ttft_ms"]),
                   "tpot_p95_ms": metric_lib.p95(sv["tpot_ms"]),
                   "serve_tokens_per_s": metric_lib.serve_tokens_per_s(ctx),
                   "live_kv_bytes_mean": sv["live_kv_bytes_mean"]}}),
        flush=True)


def sweep(cell, cfg, mix, args, device) -> None:
    """Builder-only: offer each rate of `--sweep r1,r2,...` for
    `--seconds` to one engine and print what came of it: the knee is the
    highest rate the engine sustains without a growing backlog."""
    family = find.family(cfg)
    s = family.sizes(cfg)
    rates = [float(r) for r in args.sweep.split(",")]
    prog = build(cfg, mix, args.seed)
    top = dict(mix, arrivals={"process": "poisson", "rate_per_s": max(rates)})
    warm(prog, traffic.schedule(top, args.seed, args.seconds, s["vocab"]),
         mix, s["vocab"])
    for rate in rates:
        m = dict(mix, arrivals={"process": "poisson", "rate_per_s": rate})
        sched = traffic.schedule(m, args.seed, args.seconds, s["vocab"])
        p = pump(prog["engine"], sched, args.seconds,
                 withdraw_at_close=False)
        for rid in list(prog["engine"].progress()):
            prog["engine"].cancel(rid)     # what this rate left behind
        summ = summarize(p, s, family.kv_bytes_per_token(s))
        at_close = [t for t in p["turns"] if t[0] <= p["closed"]]
        half = [t for t in at_close if t[0] >= p["closed"] / 2]
        q = lambda xs, f: float(np.percentile(xs, f)) if xs else None
        print(json.dumps({
            "rate": rate, "offered": summ["offered"],
            "unfinished": summ["unfinished"],
            "queue_at_close": at_close[-1][2] if at_close else None,
            "queue_mean_2nd_half": float(np.mean([t[2] for t in half]))
            if half else None,
            "occupancy_mean": float(np.mean(summ["occupancy"])),
            "drain_s": p["end"] - p["closed"],
            "ttft_p50": q(summ["ttft_ms"], 50),
            "ttft_p95": q(summ["ttft_ms"], 95),
            "tpot_p50": q(summ["tpot_ms"], 50),
            "tpot_p95": q(summ["tpot_ms"], 95),
            "tokens_per_s": summ["tokens_at_close"] / p["closed"],
            "turn_ms_mean": float(np.mean(np.diff([t[0] for t in at_close])))
            * 1e3 if len(at_close) > 2 else None,
        }), flush=True)
