"""chip_smoke.py — does the system still start on the chip?

Drives the repo's two main paths once, through the entry points a user
calls, at the full width of GPT-medium (d_model 1024, 16 heads x 64, FFN
4096, vocab 32,000; 24 layers unless `LAYERS` below says otherwise):

  0. device: platform must be `tpu` — no CPU fallback, no interpreter;
  1. kernels: one flash-attention and one fused-LN / add-LN forward +
     backward at the training shapes, bf16, against dense f32 XLA;
  2. train: `fleet` bf16 amp + AdamW + `jit.TrainStep` (guard on,
     donation on), 5 steps on one fixed seeded batch;
  3. serve: `serving.InferenceEngine` (contiguous cache, then the paged
     pool), seeded greedy requests across four prefill buckets, checked
     against a teacher-forced full forward;
  4. four chips (only when the host has >= 4): the same trainer on
     dp2 x mp2 through the shard_map seams.

One process, no children. Any failed check raises; the last line of
stdout is the result object only when every phase that ran passed:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

`python chip_smoke.py --rehearse` is the builder's CPU rehearsal of the
control flow (depth 2, short sequences, Pallas interpreter): it checks no
Mosaic lowering, prints no result object and always exits 3.

Any time this script prints is a smoke observation, not a measurement.
"""
from __future__ import annotations

import gc
import json
import re
import sys
import time

import numpy as np

VOCAB, D_MODEL, HEADS, FULL_LAYERS = 32000, 1024, 16, 24
#: the trainer's position table, and the sequence length run on the chip
SEQ = 1024
#: depth actually run on the chip. Cut depth before a width if the
#: 1200 s contract ever bites, and the cut is printed.
LAYERS = 24
TRAIN_STEPS = 5
NEW_TOKENS = 32
#: prompt lengths -> prefill buckets 16, 16, 32, 64, 64, 128 (23 and 47
#: are not multiples of 16)
PROMPT_LENS = (9, 16, 23, 47, 64, 100)

# Tolerances (all errors are max|a - ref| / max|ref| over a tensor).
#  * kernel outputs stored in bf16 carry 2^-9 relative rounding per
#    element on top of the f32 reference; in-kernel sums run in f32 but
#    in another order. 2e-2 is ~10x that and ~50x below what a wrong
#    mask, offset or row statistic produces (O(1)).
#  * dgamma / dbeta are f32 sums over 4096 rows of the SAME bf16-rounded
#    inputs the reference sees, so only summation order differs.
KERNEL_TOL_BF16 = 2e-2
KERNEL_TOL_F32_SUM = 2e-3
#  * four chips vs one chip, step-1 loss: identical parameters and
#    batch; the mp-split matmuls add two partial products in another
#    order and round activations to bf16 (2^-8) at other points. The
#    loss is a mean over 4096 tokens, so the error averages down; 1 % is
#    far above that and far below a mis-sharded head or row (which moves
#    the loss by O(1)).
MULTICHIP_LOSS_RTOL = 1e-2
#  * serve: a generated token must score within this fraction of the
#    position's logit range (max - min over the vocabulary) of the best
#    token of an f32 `highest`-precision full forward. The engine runs
#    f32 matmuls at the TPU default (one bf16 pass, 2^-8 per product)
#    through 24 layers, so near-ties may resolve differently; a broken
#    cache, position or splice picks an unrelated token, which sits
#    several sigma (tens of percent of the range) below the maximum.
SERVE_LOGIT_FRAC = 0.05

T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_err(a, ref) -> float:
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(a - ref)) / (jnp.max(jnp.abs(ref)) + 1e-30))


# ---------------------------------------------------------------------------
# phase 0: the device
# ---------------------------------------------------------------------------


def phase_device(rehearse: bool) -> dict:
    import os

    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU: jax found no usable backend ({e})")
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    print(f"jax {jax.__version__}  device {json.dumps(device)}", flush=True)
    if d0.platform != "tpu" and not rehearse:
        sys.exit(
            "chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{d0.platform!r}; this script only passes on the chip "
            "(--rehearse is the CPU control-flow rehearsal)")
    # importing the package is what places the compile cache
    from paddle_tpu.core import compile_cache

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    print(f"compile cache: {compile_cache.cache_dir} "
          f"({'from JAX_COMPILATION_CACHE_DIR' if env_dir else 'repo default'})",
          flush=True)
    check(jax.config.jax_compilation_cache_dir == compile_cache.cache_dir,
          "jax is not using the cache directory the program reports")
    if not rehearse:
        # the repo's one table of peaks is the benchmark's, read as data
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "benchmarks", "peaks.json")) as f:
            peaks = json.load(f)
        check(d0.device_kind in peaks,
              f"benchmarks/peaks.json has no peak for device_kind "
              f"{d0.device_kind!r}: on the chip an unknown device is an "
              "error, not a missing key")
        print("peak flops/s for this device_kind: "
              f"{peaks[d0.device_kind]['flops_per_s']:.3g}", flush=True)
    return device


# ---------------------------------------------------------------------------
# phase 1: kernels against dense f32 XLA
# ---------------------------------------------------------------------------


def phase_kernels(batch: int, seq: int, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _flash_block
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.ops.pallas.layer_norm import (
        fused_add_layer_norm, fused_layer_norm,
    )

    interp = rehearse
    dh = D_MODEL // HEADS
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    f32 = jnp.float32

    # -- flash attention, causal, [B, H, S, Dh] bf16 ----------------------
    q, k, v, g = (jax.random.normal(ks[i], (batch, HEADS, seq, dh), f32)
                  .astype(bf) for i in range(4))
    blk = _flash_block(seq)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, blk, blk, None, interp, 0, 0)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
        pos = jnp.arange(seq)
        s = jnp.where(pos[None, :] > pos[:, None], -1e30, s)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    out, vjp = jax.vjp(flash, q, k, v)
    grads = vjp(g)
    with jax.default_matmul_precision("highest"):
        ref, rvjp = jax.vjp(dense, *(a.astype(f32) for a in (q, k, v)))
        rgrads = rvjp(g.astype(f32))
    errs = {"out": rel_err(out, ref)}
    errs.update({n: rel_err(a, b)
                 for n, a, b in zip(("dq", "dk", "dv"), grads, rgrads)})
    log(f"flash fwd+bwd vs dense f32: {errs}")
    for n, e in errs.items():
        check(np.isfinite(e) and e < KERNEL_TOL_BF16,
              f"flash {n}: error {e} >= {KERNEL_TOL_BF16}")

    # -- fused LN and add-LN, [B, S, D] bf16, f32 affine ------------------
    x, y, gs, go = (jax.random.normal(ks[4 + i], (batch, seq, D_MODEL), f32)
                    .astype(bf) for i in range(4))
    w = 1.0 + 0.1 * jax.random.normal(ks[0], (D_MODEL,), f32)
    b = 0.1 * jax.random.normal(ks[1], (D_MODEL,), f32)

    def ln_ref(x32, w, b):
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        return (x32 - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    out, vjp = jax.vjp(
        lambda x, w, b: fused_layer_norm(x, w, b, 1e-5, interp), x, w, b)
    grads = vjp(go)
    ref, rvjp = jax.vjp(ln_ref, x.astype(f32), w, b)
    rgrads = rvjp(go.astype(f32))
    errs = {"out": rel_err(out, ref)}
    errs.update({n: rel_err(a, r)
                 for n, a, r in zip(("dx", "dw", "db"), grads, rgrads)})
    log(f"fused LN fwd+bwd vs dense f32: {errs}")

    # the kernel normalizes the bf16-ROUNDED sum (what downstream sees)
    def add_ln_ref(x, y, w, b):
        s = (x + y).astype(bf).astype(f32)
        return s, ln_ref(s, w, b)

    (s, out), vjp = jax.vjp(
        lambda x, y, w, b: fused_add_layer_norm(x, y, w, b, 1e-5, interp),
        x, y, w, b)
    grads = vjp((gs, go))
    (rs, ref), rvjp = jax.vjp(add_ln_ref, x.astype(f32), y.astype(f32), w, b)
    rgrads = rvjp((gs.astype(f32), go.astype(f32)))
    aerrs = {"sum": rel_err(s, rs), "out": rel_err(out, ref)}
    aerrs.update({n: rel_err(a, r) for n, a, r in
                  zip(("dx", "dy", "dw", "db"), grads, rgrads)})
    log(f"fused add-LN fwd+bwd vs dense f32: {aerrs}")
    for name, table in (("LN", errs), ("add-LN", aerrs)):
        for n, e in table.items():
            tol = KERNEL_TOL_F32_SUM if n in ("dw", "db") else KERNEL_TOL_BF16
            check(np.isfinite(e) and e < tol,
                  f"fused {name} {n}: error {e} >= {tol}")


# ---------------------------------------------------------------------------
# phase 2 / 4: the trainer
# ---------------------------------------------------------------------------

#: the lowered step names a Mosaic call by its `pallas_call`'s `name=`
_MOSAIC_KERNELS = {
    "flash fwd": ("flash_fwd",),
    "flash dq": ("flash_dq",),
    "flash dk/dv": ("flash_dkv",),
    "LN / add-LN fwd": ("ln_fwd", "ln_residual_fwd"),
    "LN bwd": ("ln_bwd",),
}


def _mosaic_calls(text: str):
    """(all Mosaic custom calls, calls by kernel) in a lowered program."""
    names = re.findall(r'kernel_name = "(\w+)"', text)
    return text.count("@tpu_custom_call"), {
        label: sum(names.count(k) for k in kernels)
        for label, kernels in _MOSAIC_KERNELS.items()}


def _check_mosaic_calls(step, layers: int) -> None:
    """Count the Mosaic custom calls in the lowered step, by kernel."""
    total, per = _mosaic_calls(
        step._jitted.lower(*step._lower_avals).as_text())
    log(f"Mosaic custom calls in the lowered TrainStep: {total} {per}")
    check(total > 0, "the lowered step holds no Mosaic custom call: the "
                     "Pallas kernels did not reach the chip's compiler")
    for label, n in per.items():
        want = 2 * layers if label.startswith("LN") else layers
        check(n == want, f"{label}: {n} Mosaic calls, expected {want}")


def _gpt(layers: int):
    """The smoke's training decoder at GPT-medium's widths: token and
    learned position embeddings, `layers` ParallelGPTBlocks (a trivial
    one-chip mesh, the same code the hybrid shards) and an untied head.
    `forward` stops before the head: the loss streams it over vocabulary
    chunks (the blockwise fused cross-entropy)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import ParallelGPTBlock, comm

    if comm.hybrid_mesh() is None:
        comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)

    class GPT(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(VOCAB, D_MODEL)
            self.pos = nn.Embedding(SEQ, D_MODEL)
            self.blocks = nn.LayerList([
                ParallelGPTBlock(D_MODEL, HEADS, dropout=0.0)
                for _ in range(layers)])
            self.head = nn.Linear(D_MODEL, VOCAB)

        def forward(self, ids):
            pos_ids = paddle.arange(ids.shape[1], dtype="int64")
            h = self.embed(ids) + self.pos(pos_ids)
            for blk in self.blocks:
                h = blk(h)
            return h

    return GPT()


def _build_trainer(layers: int, hybrid=None):
    """`fleet` bf16 amp + AdamW + `jit.TrainStep` over `_gpt(layers)`,
    the loss through the blockwise cross-entropy on the pre-head state."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import comm, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    strategy = DistributedStrategy()
    strategy.amp = True
    if hybrid:
        strategy.hybrid_configs = hybrid
    fleet.init(is_collective=True, strategy=strategy)
    if not hybrid:
        # fleet.init lets dp fill every visible device; the one-chip
        # phase is a one-chip program on a four-chip host too
        # (_gpt then declares the trivial mesh itself)
        comm.set_hybrid_mesh(None)
    model = _gpt(layers)
    wrapped = fleet.distributed_model(model) if hybrid else model
    opt = fleet.distributed_optimizer(
        optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                        parameters=model.parameters()))

    def lm_loss(h, labels):
        d = h.shape[-1]
        return nn.functional.fused_linear_cross_entropy(
            h.reshape([-1, d]), model.head.weight, model.head.bias,
            labels.reshape([-1]))

    return model, wrapped, TrainStep(wrapped, lm_loss, opt)


def _routes(model, batch: int, seq: int, mesh=None):
    """(attention plan, LN route) the routers give this trainer's shapes."""
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import flash_plan
    from paddle_tpu.nn.functional.norm import _fused_ln_route

    plan = flash_plan(seq, seq, causal=True, mesh=mesh, batch=batch,
                      heads=HEADS)
    blk = model.blocks[0]
    route = _fused_ln_route(
        jnp.zeros((batch, seq, D_MODEL), jnp.bfloat16), (D_MODEL,),
        blk.ln1.weight, blk.ln1.bias, mesh=blk.mesh)
    return plan, route


def _batch(batch: int, seq: int):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, size=(batch, seq + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _run_steps(step, ids, labels, steps: int):
    from paddle_tpu.observability import ledger

    losses, after_first = [], None
    for i in range(steps):
        t = time.perf_counter()
        loss = float(step(ids, labels).numpy())
        losses.append(loss)
        log(f"  step {i + 1}: loss {loss:.4f} "
            f"({time.perf_counter() - t:.2f}s, smoke observation)")
        if i == 0:
            after_first = ledger.compile_count()
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(ledger.compile_count() == after_first,
          f"{ledger.compile_count() - after_first} compile(s) after step 1")
    return losses


def phase_train(layers: int, batch: int, seq: int, rehearse: bool) -> float:
    import jax

    from paddle_tpu.distributed import comm

    model, _, step = _build_trainer(layers)
    log(f"trainer built: {layers} layers, "
        f"{sum(int(p._data.size) for p in model.parameters()) / 1e6:.0f}M "
        "parameters")
    plan, route = _routes(model, batch, seq)
    check(plan == ("plain",), f"flash_plan is {plan}, expected ('plain',)")
    check(route is not None and route[0] is rehearse and route[1] is None,
          f"_fused_ln_route is {route}, expected the plain kernel with "
          f"interpret={rehearse}")

    ids, labels = _batch(batch, seq)
    losses = _run_steps(step, jax.device_put(ids), jax.device_put(labels),
                        TRAIN_STEPS)
    check(losses[-1] < losses[0],
          f"loss did not fall over {TRAIN_STEPS} steps: {losses}")
    if not rehearse:
        _check_mosaic_calls(step, layers)
        stats = jax.devices()[0].memory_stats()
        check(stats and "peak_bytes_in_use" in stats,
              f"device.memory_stats() has no peak_bytes_in_use: {stats}")
        log(f"peak_bytes_in_use {stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    comm.set_hybrid_mesh(None)
    return losses[0]


def phase_four_chips(layers: int, batch: int, seq: int, one_chip_loss: float,
                     rehearse: bool) -> None:
    import jax

    from paddle_tpu.distributed import comm

    devs = jax.devices()[:4]
    if not rehearse:
        log("bytes_in_use per device before the phase: "
            + ", ".join(f"{d.memory_stats()['bytes_in_use'] / 2**20:.0f} MiB"
                        for d in devs))
    model, wrapped, step = _build_trainer(
        layers, hybrid={"dp_degree": 2, "mp_degree": 2})
    plan, route = _routes(model, batch, seq, mesh=comm.hybrid_mesh())
    check(plan is not None and plan[0] == "sharded",
          f"attention plan on dp2 x mp2 is {plan}, expected ('sharded', …)")
    check(route is not None and route[0] is rehearse
          and route[1] is not None,
          f"_fused_ln_route on dp2 x mp2 is {route}, expected the "
          "shard_map seam")
    log(f"plans: attention {plan[0]} over {plan[2]}, LN rows over {route[2]}")

    ids, labels = _batch(batch, seq)
    losses = _run_steps(step, wrapped.shard_input(ids),
                        wrapped.shard_input(labels), 3)
    check(abs(losses[0] - one_chip_loss)
          <= MULTICHIP_LOSS_RTOL * abs(one_chip_loss),
          f"step-1 loss {losses[0]} on four chips vs {one_chip_loss} on one")
    holders = set()
    for p in model.parameters():
        holders.update(s.device for s in p._data.addressable_shards)
    check(holders == set(devs),
          f"parameter shards live on {sorted(d.id for d in holders)}, "
          f"expected all of {[d.id for d in devs]}")
    if not rehearse:
        _check_mosaic_calls(step, layers)
        used = [d.memory_stats()["bytes_in_use"] for d in devs]
        log("bytes_in_use per device: "
            + ", ".join(f"{u / 2**30:.2f} GiB" for u in used))
        check(max(used) <= 2 * min(used),
              f"device memory is lopsided: {used}")
    comm.set_hybrid_mesh(None)


# ---------------------------------------------------------------------------
# phase 3: the server
# ---------------------------------------------------------------------------


def _requests(seed: int):
    from paddle_tpu.serving import Request

    rng = np.random.RandomState(seed)
    return [Request(rng.randint(0, VOCAB, size=n), max_new_tokens=NEW_TOKENS)
            for n in PROMPT_LENS]


def _check_against_full_forward(model, req, tokens) -> None:
    """Teacher-forced greedy check: one no-cache full forward over
    prompt + generated tokens; each generated token must score within
    SERVE_LOGIT_FRAC of the logit range of that position's best token."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    seq = np.concatenate([req.prompt_ids, np.asarray(tokens, np.int32)])
    pad = -len(seq) % 16  # causal: right padding cannot reach the past
    ids = np.pad(seq, (0, pad))[None, :]
    with jax.default_matmul_precision("highest"):
        logits = model(paddle.to_tensor(ids))._data[0].astype(jnp.float32)
    n0 = req.prompt_ids.size
    rows = logits[n0 - 1: n0 - 1 + len(tokens)]
    top = rows.max(-1)
    got = jnp.take_along_axis(
        rows, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    gap = np.asarray((top - got) / (top - rows.min(-1)))
    exact = int((np.asarray(rows.argmax(-1)) == np.asarray(tokens)).sum())
    log(f"  vs full forward (prompt {n0}): {exact}/{len(tokens)} tokens are "
        f"the argmax, worst logit gap {gap.max():.4f} of the range")
    check(np.all(np.isfinite(gap)) and gap.max() <= SERVE_LOGIT_FRAC,
          f"generated tokens disagree with the full forward: gaps {gap}")


def _serve_once(model, label: str, **engine_kw):
    from paddle_tpu.observability import ledger
    from paddle_tpu.serving import InferenceEngine

    engine = InferenceEngine(model, slots=8, **engine_kw)
    out = None
    for round_, seed in enumerate((1, 2)):
        reqs = _requests(seed)
        for r in reqs:
            engine.submit(r)
        before = ledger.compile_count()
        t = time.perf_counter()
        results = engine.run()
        log(f"  {label} run {round_ + 1}: {len(results)} requests in "
            f"{time.perf_counter() - t:.1f}s (smoke observation), "
            f"{ledger.compile_count() - before} compiles")
        for r in reqs:
            toks = results[r.rid].tokens
            check(len(toks) == NEW_TOKENS,
                  f"{label}: request of prompt {r.prompt_ids.size} returned "
                  f"{len(toks)} tokens, budget {NEW_TOKENS}")
            check(all(0 <= t < VOCAB for t in toks),
                  f"{label}: token ids out of [0, {VOCAB}): {toks}")
        if round_ == 0:
            buckets = {16, 32, 64, 128}
            check(engine._decode.compiles == 1,
                  f"{label}: DecodeStep compiled "
                  f"{engine._decode.compiles} times")
            check(engine._prefill.compiles == len(buckets),
                  f"{label}: PrefillStep compiled {engine._prefill.compiles}"
                  f" times for buckets {sorted(buckets)}")
            out = (reqs, results)
        else:
            check(ledger.compile_count() == before,
                  f"{label}: the second run compiled "
                  f"{ledger.compile_count() - before} program(s)")
    reqs, results = out
    # the request whose prompt length (23) is not a multiple of 16
    _check_against_full_forward(model, reqs[2], results[reqs[2].rid].tokens)
    return [results[r.rid].tokens for r in reqs]


def phase_serve(layers: int) -> None:
    import paddle_tpu as paddle
    from paddle_tpu.distributed import comm
    from paddle_tpu.serving import TransformerLM

    paddle.seed(0)
    model = TransformerLM(VOCAB, d_model=D_MODEL, num_heads=HEADS,
                          num_layers=layers)
    model.eval()
    log(f"TransformerLM built: {layers} layers")
    a = _serve_once(model, "contiguous")
    b = _serve_once(model, "paged(16)", block_size=16)
    same = sum(x == y for x, y in zip(a, b))
    log(f"  contiguous and paged agree on {same}/{len(a)} requests")
    comm.set_hybrid_mesh(None)


# ---------------------------------------------------------------------------


def main(argv) -> int:
    rehearse = "--rehearse" in argv[1:]
    if rehearse:
        import os

        # the interpreter stands in for Mosaic; nothing else changes
        os.environ["PADDLE_FLASH_DEFAULT"] = "interpret"
        os.environ["PADDLE_FUSED_LN"] = "interpret"
    device = phase_device(rehearse)
    layers, batch, seq = (2, 4, 128) if rehearse else (LAYERS, 4, SEQ)
    if layers != FULL_LAYERS or rehearse:
        log(f"CUT: depth {layers} of {FULL_LAYERS}, batch {batch}, "
            f"seq {seq}; every width is full")

    log("phase 1: kernels vs dense f32")
    phase_kernels(batch, seq, rehearse)
    log("phase 2: train")
    loss1 = phase_train(layers, batch, seq, rehearse)
    gc.collect()
    log("phase 3: serve")
    phase_serve(layers)
    gc.collect()
    if device["count"] >= 4:
        log("phase 4: four chips, dp2 x mp2")
        phase_four_chips(layers, batch, seq, loss1, rehearse)
    else:
        log(f"multichip: not run ({device['count']} device)")
    log("all phases that ran passed")
    if rehearse:
        print("rehearsal only: no result", flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
