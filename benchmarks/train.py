"""The training cells: `jit.TrainStep` over `serving.TransformerLM`.

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
import reference
import tracing
import traffic
import weights

#: steps in flight before the host waits for the oldest: keeps the device
#: fed and gives every step an end on the host's clock
_IN_FLIGHT = 2
#: batches made before the window; the feed cycles if a window outlasts it
_POOL = 512


_T0 = time.perf_counter()     # `run` sets it to the process's start


def log(msg: str) -> None:
    print(f"[train +{time.perf_counter() - _T0:.1f}s] {msg}", file=sys.stderr,
          flush=True)


def build(cfg: dict, mix: dict, seed: int):
    """The program: model, optimizer and the compiled step, loaded with
    the seed's weights. Returns a dict so that `free` can drop it all."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import comm, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.ops.creation import arange
    from paddle_tpu.serving import TransformerLM

    s = weights.sizes(cfg)
    paddle.seed(0)
    strategy = DistributedStrategy()
    strategy.amp = mix["amp"] == "bfloat16"
    fleet.init(is_collective=True, strategy=strategy)
    # fleet.init lets dp fill every visible device; a one-chip cell is a
    # one-chip program on any host
    comm.set_hybrid_mesh(None)
    lm = TransformerLM(s["vocab"], d_model=s["d"], num_heads=s["heads"],
                       num_layers=s["layers"], max_position=s["positions"],
                       dim_feedforward=s["ffn"])

    class Trunk(nn.Layer):
        """The model's own parts up to the final LayerNorm: the head sits
        in the loss, where the blockwise cross-entropy streams it."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            lm = self.lm
            h = lm.embed(ids) + lm.pos_embed(
                arange(int(ids.shape[1]), dtype="int64"))
            for blk in lm.blocks:
                h = blk(h)
            return lm.ln_f(h)

    w = weights.make(cfg, seed)
    names = {}
    for name, p in lm.named_parameters():
        p._data = w[name].astype(p._data.dtype)
        names[id(p)] = name
    del w
    trunk = Trunk(lm)
    trunk.train()
    o = mix["optimizer"]
    opt = fleet.distributed_optimizer(optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=lm.parameters()))

    def lm_loss(h, labels):
        d = h.shape[-1]
        return nn.functional.fused_linear_cross_entropy(
            h.reshape([-1, d]), lm.head.weight, lm.head.bias,
            labels.reshape([-1]))

    step = TrainStep(trunk, lm_loss, opt)
    return {"lm": lm, "step": step, "names": names}


def assert_routes(prog: dict, cfg: dict, mix: dict, rehearse: bool) -> None:
    """The cell's shapes must take the Pallas kernels, as chip_smoke
    asserts: a cell that falls off the kernel path measures another
    program."""
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import flash_plan
    from paddle_tpu.nn.functional.norm import _fused_ln_route

    s = weights.sizes(cfg)
    plan = flash_plan(mix["seq"], mix["seq"], causal=True, mesh=None,
                      batch=mix["batch"], heads=s["heads"])
    blk = prog["lm"].blocks[0]
    route = _fused_ln_route(
        jnp.zeros((mix["batch"], mix["seq"], s["d"]), jnp.bfloat16),
        (s["d"],), blk.ln1.weight, blk.ln1.bias, mesh=blk.mesh)
    if plan is None or plan[0] != "plain":
        raise RuntimeError(f"flash_plan is {plan}, the cell expects plain")
    if route is None or route[0] is not rehearse:
        raise RuntimeError(f"_fused_ln_route is {route}: the cell's "
                           "LayerNorm is off the kernel path")


def _norms(named: dict, base: dict = None) -> dict:
    """{leaf: L2 norm} of the arrays (or of their distance from `base`),
    a fused QKV leaf as its three projections, in one jitted call."""
    import jax
    import jax.numpy as jnp

    def f(xs, ys):
        if ys is not None:
            xs = {n: x.astype(jnp.float32) - ys[n] for n, x in xs.items()}
        return {n: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
                for n, x in weights.split_fused(xs).items()}

    return {n: float(v) for n, v in jax.jit(f)(named, base).items()}


def first_steps(prog, call, feed, cfg, mix, seed):
    """Drive the first `check_steps` steps through the window's own call
    and read what is compared: each loss, the first gradient's norm a
    leaf (from AdamW's first moment after one step: m1 = (1 - beta1) g),
    and the norm of every leaf's change after the steps."""
    step = prog["step"]
    names = [prog["names"][id(p)] for p in step._p_objs]
    losses, grad = [], None
    for i in range(int(mix["check_steps"])):
        losses.append(float(call(*feed(i)).numpy()))
        if i == 0:
            m1 = step.opt._functional_state(step._p_objs)["moment1"]
            scale = 1.0 / (1.0 - mix["optimizer"]["beta1"])
            grad = {n: v * scale
                    for n, v in _norms(dict(zip(names, m1))).items()}
    delta = _norms({n: p._data for n, p in zip(names, step._p_objs)},
                   weights.make(cfg, seed))
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
    s = weights.sizes(cfg)
    log("imports done")
    prog = build(cfg, mix, args.seed)
    assert_routes(prog, cfg, mix, args.rehearse)
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

    ref_losses, ref_grad, ref_delta = reference.train_steps(
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

    s = weights.sizes(cfg)
    n = int(mix["check_steps"])
    pool = traffic.train_batches(mix, args.seed, n, s["vocab"])
    ids = [(np.asarray(pool[i, :, :-1]), np.asarray(pool[i, :, 1:]))
           for i in range(n)]
    opt = mix["optimizer"]

    def three(**kw):
        l, g, d = reference.train_steps(cfg, args.seed, ids, opt, **kw)
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
