"""The training cells: `jit.TrainStep` over the family's model.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first `check_steps` steps through the window's own call and
feed, reads what `correct` compares, and hands that same object to the
window. The reference follows those steps once the window has closed,
the memory peak has been read and the program's state is freed.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

import checks
import device as device_mod
import find
import tracing
import traffic

#: steps in flight before the host waits for the oldest: keeps the device
#: fed and gives every step an end on the host's clock
_IN_FLIGHT = 2
#: batches made before the window; the feed cycles if a window outlasts it
_POOL = 512

#: the mix's sizes under `--rehearse`
TOY = {"batch": 4, "seq": 64}


_T0 = time.perf_counter()     # `run` sets it to the process's start


def log(msg: str) -> None:
    print(f"[train +{time.perf_counter() - _T0:.1f}s] {msg}", file=sys.stderr,
          flush=True)


def build(cfg: dict, mix: dict, seed: int):
    """The program: the family's model, the optimizer and the compiled
    step, loaded with the seed's weights. Returns a dict so that `free`
    can drop it all."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import comm, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    strategy = DistributedStrategy()
    strategy.amp = mix["amp"] == "bfloat16"
    fleet.init(is_collective=True, strategy=strategy)
    # fleet.init lets dp fill every visible device; a one-chip cell is a
    # one-chip program on any host
    comm.set_hybrid_mesh(None)
    model = find.family(cfg).training_model(cfg, mix, seed)
    o = mix["optimizer"]
    opt = fleet.distributed_optimizer(optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model["parameters"]))
    step = TrainStep(model["layer"], model["loss"], opt)
    return {"model": model, "step": step}


def _norms(family, named: dict, base: dict = None) -> dict:
    """{leaf: L2 norm} of the arrays (or of their distance from `base`),
    a fused leaf as the family splits it, in one jitted call."""
    import jax
    import jax.numpy as jnp

    def f(xs, ys):
        if ys is not None:
            xs = {n: x.astype(jnp.float32) - ys[n] for n, x in xs.items()}
        return {n: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
                for n, x in family.split_fused(xs).items()}

    return {n: float(v) for n, v in jax.jit(f)(named, base).items()}


def first_steps(prog, call, feed, cfg, mix, seed):
    """Drive the first `check_steps` steps through the window's own call
    and read what is compared: each loss, the first gradient's norm a
    leaf (from AdamW's first moment after one step: m1 = (1 - beta1) g),
    and the norm of every leaf's change after the steps."""
    family = find.family(cfg)
    step = prog["step"]
    names = [prog["model"]["names"][id(p)] for p in step._p_objs]
    losses, grad = [], None
    for i in range(int(mix["check_steps"])):
        losses.append(float(call(*feed(i)).numpy()))
        if i == 0:
            m1 = step.opt._functional_state(step._p_objs)["moment1"]
            scale = 1.0 / (1.0 - mix["optimizer"]["beta1"])
            grad = {n: v * scale
                    for n, v in _norms(family, dict(zip(names, m1))).items()}
    delta = _norms(family, {n: p._data for n, p in zip(names, step._p_objs)},
                   family.make(cfg, seed))
    return {"losses": losses, "grad": grad, "delta": delta}


def window(call, feed, start: int, seconds: float, tokens_per_step: int,
           annotate, tracer=None):
    """Steps from `start` on for `seconds`; returns what the end-to-end
    metric needs. The rate is all tokens of all steps over the whole
    window, which ends when the last step's loss is on the host."""
    clock = time.perf_counter
    pending, ends = [], []
    t0 = clock()
    i = start
    while True:
        now = clock() - t0
        if tracer is not None:
            tracer.poll(now)
        if now >= seconds:
            break
        with annotate("bench.step"):
            pending.append(call(*feed(i))._data)
        i += 1
        if len(pending) > _IN_FLIGHT:
            pending.pop(0).block_until_ready()
            ends.append(clock() - t0)
    for p in pending:
        p.block_until_ready()
        ends.append(clock() - t0)
    if tracer is not None:
        tracer.finish(clock() - t0)
    steps = i - start
    return {"steps": steps, "window_s": ends[-1],
            "tokens": steps * tokens_per_step, "step_ends": ends}


def free(prog: dict) -> None:
    """Give the program's device memory back before the reference runs."""
    step = prog["step"]
    state = step.opt._functional_state(step._p_objs)
    for p in step._p_objs:
        p._data.delete()
    for leaves in state.values():
        for a in leaves:
            a.delete()
    prog.clear()
    gc.collect()


def run(cell, cfg, mix, args, device, t_start):
    import jax

    from paddle_tpu.observability import ledger

    global _T0
    _T0 = t_start
    family = find.family(cfg)
    s = family.sizes(cfg)
    log("imports done")
    prog = build(cfg, mix, args.seed)
    family.assert_routes(prog["model"], cfg, mix, args.rehearse)
    log("program built")
    pool = traffic.train_batches(mix, args.seed, _POOL, s["vocab"])
    batches = [(pool[i, :, :-1], pool[i, :, 1:]) for i in range(_POOL)]
    check_ids = [(np.asarray(pool[i, :, :-1]), np.asarray(pool[i, :, 1:]))
                 for i in range(int(mix["check_steps"]))]
    del pool

    def feed(i):
        return batches[i % _POOL]

    call = prog["step"]          # the entry the window drives
    measured = first_steps(prog, call, feed, cfg, mix, args.seed)
    jax.block_until_ready(batches)
    compiles = ledger.compile_count()
    log(f"set-up done: losses {measured['losses']}")
    tracer = tracing.Tracer(args) if args.trace else None
    setup_s = time.perf_counter() - t_start
    win = window(call, feed, int(mix["check_steps"]), args.seconds,
                 mix["batch"] * mix["seq"], tracing.annotate, tracer)
    compiled_in_window = ledger.compile_count() - compiles
    log(f"window done: {win['steps']} steps in {win['window_s']:.2f} s"
        + (f"; profiler start/stop took {tracer.stall_s}" if tracer else ""))
    peak = device_mod.memory_peak(int(cell["chips"]))
    free(prog)
    del batches
    gc.collect()

    ref_losses, ref_grad, ref_delta = family.train_steps(
        cfg, args.seed, check_ids, mix["optimizer"])
    ref = {"losses": ref_losses, "grad": ref_grad, "delta": ref_delta}
    nums = checks.train_numbers(measured, ref)
    log(f"reference done; losses {ref_losses}; worst leaves {nums['where']}")
    values = dict(nums["values"])
    values["compiles_in_window"] = compiled_in_window
    compared = checks.compared(
        values, checks.load_limits(cell["name"], args.rehearse))
    ctx = {"setup_s": setup_s, "window": win, "seq": mix["seq"],
           "batch": mix["batch"], "sizes": s}
    extra = {"memory_peak_bytes": peak}
    if tracer is not None:
        ctx["trace"] = tracer.reduce(chips=int(cell["chips"]), kind="train")
        ctx["breakdown"] = ctx["trace"]["breakdown"]
        extra.update(busy_s=ctx["trace"]["busy_s"],
                     window_s=ctx["trace"]["window_s"])
    return {"ctx": ctx, "attempted": win["steps"], "failed": 0,
            "compared": compared, "device": extra}


def control(cell, cfg, mix, args) -> None:
    """Builder-only: the reference put in the program's place, in the
    nearest lower precision and with the planted fault, each read against
    the reference itself and judged under the cell's own limits: both
    have to come out as not correct. Prints one JSON line a seed."""
    import json

    family = find.family(cfg)
    s = family.sizes(cfg)
    n = int(mix["check_steps"])
    pool = traffic.train_batches(mix, args.seed, n, s["vocab"])
    ids = [(np.asarray(pool[i, :, :-1]), np.asarray(pool[i, :, 1:]))
           for i in range(n)]
    opt = mix["optimizer"]

    def three(**kw):
        l, g, d = family.train_steps(cfg, args.seed, ids, opt, **kw)
        return {"losses": l, "grad": g, "delta": d}

    ref = three()
    limits = checks.load_limits(cell["name"], args.rehearse)
    out = {"seed": args.seed, "workload": cell["name"], "correct": {}}
    half = slice(0, mix["batch"] // 2)
    # a step that returns its state unchanged reads 1 by the measure of
    # the change and needs no run
    for name, kw in ((mix["control"], {"precision": mix["control"]}),
                     ("half_batch", {"rows": half, "row_block": 1})):
        values = checks.train_numbers(three(**kw), ref)["values"]
        values["compiles_in_window"] = 0     # the reference has no window
        rows = checks.compared(values, limits)
        out["correct"][name] = checks.verdict(rows)
        out[name] = values
        print(f"-- {name} in the program's place", file=sys.stderr)
        checks.report(rows, out["correct"][name], sys.stderr)
    print(json.dumps(out), flush=True)


def sweep(cell, cfg, mix, args, device) -> None:
    sys.exit("train cells have no rate to sweep")
