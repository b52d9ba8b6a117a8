"""Driver benchmark: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}.

Benchmarks the framework's REAL hot path — `paddle_tpu.jit.TrainStep`
(forward + loss + backward + framework optimizer fused into one donated XLA
program; the analog of the reference's generated `core.ops` bindings +
run_program op, pybind/op_function_generator.cc:488) — exactly the harness
`__graft_entry__.dryrun_multichip` drives on the virtual mesh.

Headline metric (round 5+): `resnet50_bf16_train_imgs_per_sec` — the
compute-bound number (BASELINE.json config 2). The old headline
`lenet_mnist_train_imgs_per_sec` (r01-r04) is per-program-overhead-bound
and rides in `extra` for continuity. `extra` also carries BERT-base and
GPT-medium bf16 steps and per-model compile times.

TrainStep issues ONE async program per step with donated buffers, so
steps pipeline; an eager `tree_map(p - lr*g)` update outside jit (the
rounds 1–3 bench) serializes several launches per step against the grad
program.

Measurement note: every timed loop here ends with `np.asarray(...)` of a
scalar/slice as the barrier (a device-to-host read waits for the work
that produced it), and timed calls never reuse the warmup arguments.

Nothing this file prints has been measured on the current machine: the
r01-r05 records were taken on a retired platform and are deleted.
Reworking the benchmark (device named in every result, no CPU fallback,
cells) is ROADMAP S0.

vs_baseline: BASELINE.json publishes no reference numbers (BASELINE.md), so
the recorded value IS the baseline (1.0); extra.vs_r02 carries the ratio
against round 2's 663.6 on the same metric.
"""
import json
import os
import time

import numpy as np

#: repeats per metric: a single-shot number carries run-to-run jitter;
#: the headline is the MEDIAN of N runs and min/max spread rides in
#: `extra` per metric
REPEATS = max(int(os.environ.get("PADDLE_BENCH_REPEATS", "3") or 3), 1)


def _spread(vals):
    sv = sorted(vals)
    return {"n": len(sv), "median": round(sv[len(sv) // 2], 1),
            "min": round(sv[0], 1), "max": round(sv[-1], 1)}


def _repeat(fn):
    """Run `fn() -> (value, extra_dict)` REPEATS times; return the median
    run's (value, extra) plus the spread record across runs."""
    runs = [fn() for _ in range(REPEATS)]
    runs.sort(key=lambda r: r[0])
    med = runs[len(runs) // 2]
    return med[0], med[1], _spread([r[0] for r in runs])


def _bench_train(model_fn, opt_fn, x_shape, y_classes, batch, steps, label,
                 amp=False):
    """Time `steps` TrainStep calls (one donated XLA program each), async-
    dispatched, single block at the end. Returns (imgs/sec, breakdown).
    amp=True routes the optimizer through the fleet bf16 strategy."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = model_fn()
    opt = opt_fn(model)
    if amp:
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy

        strategy = DistributedStrategy()
        strategy.amp = True
        fleet.init(is_collective=True, strategy=strategy)
        opt = fleet.distributed_optimizer(opt)
    step = TrainStep(
        model, lambda out, y: nn.functional.cross_entropy(out, y), opt
    )

    # stage the batch in HBM once (DataLoader's double-buffer analog,
    # operators/reader/buffered_reader.cc) — host->device bandwidth must
    # not be inside the timed loop
    import jax.numpy as jnp

    x = jax.device_put(
        jnp.asarray(np.random.rand(batch, *x_shape).astype(np.float32))
    )
    y = jax.device_put(jnp.asarray((np.arange(batch) % y_classes).astype(np.int32)))
    _ = np.asarray(x.ravel()[:1])  # devget barrier: upload must finish here

    t0 = time.perf_counter()
    loss = step(x, y)  # compile + first step
    _ = np.asarray(loss._data)  # devget barrier
    compile_s = time.perf_counter() - t0

    # steady state: async dispatch, one block at the end -> steps pipeline
    # optional device-trace artifact (DeviceTracer/GenProfile analog):
    # PADDLE_TPU_TRACE=<dir> captures an XPlane trace of the timed loop
    import os

    trace_dir = os.environ.get("PADDLE_TPU_TRACE")
    if trace_dir:
        from paddle_tpu import profiler as prof

        prof.start_profiler(trace_dir=os.path.join(trace_dir, label))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    _ = np.asarray(loss._data)  # waits for the whole queued sequence
    dt = time.perf_counter() - t0
    if trace_dir:
        prof.stop_profiler()

    step_ms = dt / steps * 1e3
    bd = {
        f"{label}_step_ms": round(step_ms, 2),
        f"{label}_compile_s": round(compile_s, 1),
    }
    # achieved-FLOPs accounting (ISSUE 8): XLA-cost-model FLOPs of the
    # exact compiled step vs the device-kind peak table — None on CPU CI
    # without a PADDLE_OBS_PEAK_FLOPS override, recorded when known
    mfu = step.mfu_pct(step_ms / 1e3)
    if mfu is not None:
        bd[f"{label}_mfu_pct"] = mfu
    return steps * batch / dt, bd


def _bert_base():
    """BERT-base-shaped encoder (BASELINE config 3): 12 layers, hidden
    768, 12 heads, seq 128 — the encoder dominates FLOPs; the head is a
    2-way classifier. bf16 autocast via the fleet amp strategy (TPU-first
    policy; MXU-bound matmuls cast down, softmax/norms stay f32)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    class Bert(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(30522, 768)
            self.pos = nn.Embedding(512, 768)
            self.encoder = nn.LayerList([
                nn.TransformerEncoderLayer(768, 12, 3072, dropout=0.0)
                for _ in range(12)
            ])
            self.head = nn.Linear(768, 2)

        def forward(self, ids):
            T = ids.shape[1]
            pos_ids = paddle.arange(T, dtype="int64")
            h = self.embed(ids) + self.pos(pos_ids)
            for lyr in self.encoder:
                h = lyr(h)
            return self.head(h.mean(axis=1))

    return Bert()


def _bench_bert(steps=10, batch=32, seq=128):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    strategy = DistributedStrategy()
    strategy.amp = True  # bf16 autocast inside the fused step
    fleet.init(is_collective=True, strategy=strategy)
    model = _bert_base()
    opt = fleet.distributed_optimizer(
        optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                        parameters=model.parameters())
    )
    step = TrainStep(
        model, lambda out, y: nn.functional.cross_entropy(out, y), opt
    )
    import jax.numpy as jnp

    ids = jax.device_put(jnp.asarray(
        (np.arange(batch * seq) % 30000).reshape(batch, seq)
        .astype(np.int32)
    ))
    y = jax.device_put(jnp.asarray((np.arange(batch) % 2).astype(np.int32)))
    _ = np.asarray(ids.ravel()[:1])

    t0 = time.perf_counter()
    loss = step(ids, y)
    _ = np.asarray(loss._data)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, y)
    _ = np.asarray(loss._data)
    dt = time.perf_counter() - t0
    return steps * batch / dt, {
        "bert_base_bf16_step_ms": round(dt / steps * 1e3, 2),
        "bert_base_bf16_compile_s": round(compile_s, 1),
    }


def _gpt_medium(dense=False):
    """GPT-medium-shaped causal decoder (the single-chip proxy for
    BASELINE config 5's GPT-3 1.3B, which needs the dp x pp x mp hybrid
    dryrun_multichip proves): 24 ParallelGPTBlock layers (trivial 1-chip
    mesh — same code path the hybrid shards), d_model 1024, 16 heads,
    seq 1024, tied-free 32k vocab head.

    Round 6: the decoder hot path is the DEFAULT path — flash attention
    routes automatically inside every block (PADDLE_FLASH_DEFAULT policy)
    and the model returns the pre-head hidden state so the loss can run
    the blockwise fused vocab CE. `dense=True` is the escape-hatch
    configuration (forced dense attention + materialized-logits CE) used
    to record the routed/unrouted pair."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import ParallelGPTBlock, comm

    if comm.hybrid_mesh() is None:
        comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)

    class GPT(nn.Layer):
        def __init__(self, vocab=32000, d=1024, heads=16, layers=24,
                     seq=1024):
            super().__init__()
            self.embed = nn.Embedding(vocab, d)
            self.pos = nn.Embedding(seq, d)
            self.blocks = nn.LayerList([
                ParallelGPTBlock(
                    d, heads, dropout=0.0,
                    use_flash_attention=False if dense else None,
                )
                for _ in range(layers)
            ])
            self.head = nn.Linear(d, vocab)

        def forward(self, ids):
            T = ids.shape[1]
            pos_ids = paddle.arange(T, dtype="int64")
            h = self.embed(ids) + self.pos(pos_ids)
            for blk in self.blocks:
                h = blk(h)
            # the head projection lives in the LOSS (blockwise fused CE
            # streams it over vocab chunks); the dense escape hatch
            # materializes the logits here as before
            return self.head(h) if dense else h

    return GPT()


def _bench_gpt(steps=10, batch=4, seq=1024, dense=False, guard=None):
    """Causal-LM training step: next-token CE over the full sequence.
    guard: None follows PADDLE_GUARD_MODE (default skip = sentinel ON);
    "off" forces the unguarded seed program for the overhead pair."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    if guard is not None:
        os.environ["PADDLE_GUARD_MODE"] = guard
    paddle.seed(0)
    strategy = DistributedStrategy()
    strategy.amp = True
    fleet.init(is_collective=True, strategy=strategy)
    model = _gpt_medium(dense=dense)
    opt = fleet.distributed_optimizer(
        optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                        parameters=model.parameters())
    )

    if dense:
        def lm_loss(logits, labels):
            V = logits.shape[-1]
            return nn.functional.cross_entropy(
                logits.reshape([-1, V]), labels.reshape([-1])
            )
    else:
        def lm_loss(h, labels):
            d = h.shape[-1]
            # blockwise fused head-projection + CE: the [B*S, 32k] f32
            # logits/grads never materialize at once (PADDLE_CE_CHUNK)
            return nn.functional.fused_linear_cross_entropy(
                h.reshape([-1, d]), model.head.weight, model.head.bias,
                labels.reshape([-1]),
            )

    step = TrainStep(model, lm_loss, opt)
    ids = jax.device_put(jnp.asarray(
        (np.arange(batch * seq) % 31000).reshape(batch, seq)
        .astype(np.int32)
    ))
    labels = jax.device_put(jnp.asarray(
        ((np.arange(batch * seq) + 1) % 31000).reshape(batch, seq)
        .astype(np.int32)
    ))
    _ = np.asarray(ids.ravel()[:1])

    t0 = time.perf_counter()
    loss = step(ids, labels)
    _ = np.asarray(loss._data)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    _ = np.asarray(loss._data)
    dt = time.perf_counter() - t0
    tok_s = steps * batch * seq / dt
    out = {
        "gpt_medium_bf16_step_ms": round(dt / steps * 1e3, 2),
        "gpt_medium_bf16_tokens_per_sec": round(tok_s, 0),
        "gpt_medium_bf16_compile_s": round(compile_s, 1),
    }
    mfu = step.mfu_pct(dt / steps)
    if mfu is not None:
        out["gpt_medium_bf16_mfu_pct"] = mfu
    return out


def _bench_gpt_multichip(steps=10, seq=1024, shard_off=False):
    """GPT-medium training step on a dp x mp2 mesh (ISSUE 6): the
    sharded-flash/fused-LN default vs the `PADDLE_FLASH_SHARD=0` dense
    fallback (the r6 multi-device behavior). Records the pair so the
    shard_map-seam win is tracked by tools/bench_continuity.py's >10%
    gate instead of anecdote. Runs only when the job spans >= 2 devices
    with an even count (mp=2, dp fills the rest)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    ndev = len(jax.devices())
    mp = 2
    dp = ndev // mp
    shard_before = os.environ.get("PADDLE_FLASH_SHARD")
    if shard_off:
        os.environ["PADDLE_FLASH_SHARD"] = "0"
    try:
        paddle.seed(0)
        strategy = DistributedStrategy()
        strategy.amp = True
        strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp}
        fleet.init(is_collective=True, strategy=strategy)
        model = _gpt_medium()
        fl_model = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                            parameters=model.parameters())
        )

        def lm_loss(h, labels):
            d = h.shape[-1]
            return nn.functional.fused_linear_cross_entropy(
                h.reshape([-1, d]), model.head.weight, model.head.bias,
                labels.reshape([-1]),
            )

        step = TrainStep(fl_model, lm_loss, opt)
        batch = 4 * dp  # 4 per data-parallel shard
        ids = fl_model.shard_input(
            (np.arange(batch * seq) % 31000).reshape(batch, seq)
            .astype(np.int32)
        )
        labels = fl_model.shard_input(
            ((np.arange(batch * seq) + 1) % 31000).reshape(batch, seq)
            .astype(np.int32)
        )
        _ = np.asarray(ids._data.ravel()[:1])

        t0 = time.perf_counter()
        loss = step(ids, labels)
        _ = np.asarray(loss._data)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, labels)
        _ = np.asarray(loss._data)
        dt = time.perf_counter() - t0
        tok_s = steps * batch * seq / dt
    finally:
        if shard_before is None:
            os.environ.pop("PADDLE_FLASH_SHARD", None)
        else:
            os.environ["PADDLE_FLASH_SHARD"] = shard_before
        # drop the dp x mp fleet mesh: it is process-global routing
        # state, and everything benched after this pair must not
        # silently run as a fleet job (same lingering-mesh class as the
        # dryrun phases, which null it after every section)
        from paddle_tpu.distributed import comm as _comm

        _comm._state.hybrid_mesh = None
    tag = "_dense" if shard_off else ""
    return {
        f"gpt_medium_bf16_dp_mp{tag}_step_ms": round(dt / steps * 1e3, 2),
        f"gpt_medium_bf16_dp_mp{tag}_tokens_per_sec": round(tok_s, 0),
        f"gpt_medium_bf16_dp_mp{tag}_compile_s": round(compile_s, 1),
    }


def _bench_gpt_dp_q8(steps=10, seq=1024, quant=True):
    """GPT-medium training step on a hierarchical dcn x ici dp mesh with
    the dcn hop quantized (ISSUE 10) vs full-width f32: the
    `gpt_medium_bf16_dp_q8_*` / `*_q8_off_*` pair under the
    tools/bench_continuity.py >10% gate. Both configs run the explicit
    per-grad dcn reduction (async_dcn_allreduce), so the ONLY difference
    is the wire width of the slow inter-node hop — int8 payload +
    per-block scales vs f32. The static comm-byte estimate rides along
    report-only (`gpt_medium_bf16_dp_q8_comm_mb`). Runs when the job
    spans >= 4 devices with an even count (dcn = ndev/2 x ici 2)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    ndev = len(jax.devices())
    try:
        paddle.seed(0)
        strategy = DistributedStrategy()
        strategy.amp = True
        strategy.hierarchical_allreduce = True
        strategy.hierarchical_allreduce_inter_nranks = 2
        strategy.async_dcn_allreduce = True
        if quant:
            strategy.quantized_allreduce = "int8"
        fleet.init(is_collective=True, strategy=strategy)
        model = _gpt_medium()
        fl_model = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                            parameters=model.parameters())
        )

        def lm_loss(h, labels):
            d = h.shape[-1]
            return nn.functional.fused_linear_cross_entropy(
                h.reshape([-1, d]), model.head.weight, model.head.bias,
                labels.reshape([-1]),
            )

        step = TrainStep(fl_model, lm_loss, opt)
        batch = 4 * ndev  # 4 per data-parallel shard
        ids = fl_model.shard_input(
            (np.arange(batch * seq) % 31000).reshape(batch, seq)
            .astype(np.int32)
        )
        labels = fl_model.shard_input(
            ((np.arange(batch * seq) + 1) % 31000).reshape(batch, seq)
            .astype(np.int32)
        )
        _ = np.asarray(ids._data.ravel()[:1])

        t0 = time.perf_counter()
        loss = step(ids, labels)
        _ = np.asarray(loss._data)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, labels)
        _ = np.asarray(loss._data)
        dt = time.perf_counter() - t0
        tok_s = steps * batch * seq / dt
        comm = step._grad_comm_info
    finally:
        from paddle_tpu.distributed import comm as _comm

        _comm._state.hybrid_mesh = None
    tag = "" if quant else "_off"
    out = {
        f"gpt_medium_bf16_dp_q8{tag}_step_ms": round(dt / steps * 1e3, 2),
        f"gpt_medium_bf16_dp_q8{tag}_tokens_per_sec": round(tok_s, 0),
        f"gpt_medium_bf16_dp_q8{tag}_compile_s": round(compile_s, 1),
    }
    if quant and comm:
        # report-only (no per_sec/_ms suffix -> never gated): the dcn
        # hop's priced bytes, payload + scales
        out["gpt_medium_bf16_dp_q8_comm_mb"] = round(
            comm["bytes_on_wire"] / 1e6, 1)
        out["gpt_medium_bf16_dp_q8_comm_reduction_x"] = \
            comm["reduction_x"]
    return out


def _bench_gpt_q8m(steps=10, batch=4, seq=1024, quant=True):
    """GPT-medium training step with int8 Adam moments (ISSUE 19):
    `strategy.quantized_moments = "int8"` stores moment1/moment2 as
    int8 payload + per-block f32 scales (moment2 in sqrt domain) and
    the compiled apply dequantizes/requantizes around the unchanged
    AdamW rule. The `gpt_medium_bf16_q8m_*` / `*_q8m_off_*` pair lands
    under the tools/bench_continuity.py >10% gate; the static
    moment-byte estimate rides report-only."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.jit import TrainStep

    try:
        paddle.seed(0)
        strategy = DistributedStrategy()
        strategy.amp = True
        if quant:
            strategy.quantized_moments = "int8"
        fleet.init(is_collective=True, strategy=strategy)
        model = _gpt_medium()
        opt = fleet.distributed_optimizer(
            optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                            parameters=model.parameters()),
            strategy=strategy,
        )

        def lm_loss(h, labels):
            d = h.shape[-1]
            return nn.functional.fused_linear_cross_entropy(
                h.reshape([-1, d]), model.head.weight, model.head.bias,
                labels.reshape([-1]),
            )

        step = TrainStep(model, lm_loss, opt)
        ids = jax.device_put(jnp.asarray(
            (np.arange(batch * seq) % 31000).reshape(batch, seq)
            .astype(np.int32)
        ))
        labels = jax.device_put(jnp.asarray(
            ((np.arange(batch * seq) + 1) % 31000).reshape(batch, seq)
            .astype(np.int32)
        ))
        _ = np.asarray(ids.ravel()[:1])

        t0 = time.perf_counter()
        loss = step(ids, labels)
        _ = np.asarray(loss._data)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, labels)
        _ = np.asarray(loss._data)
        dt = time.perf_counter() - t0
        tok_s = steps * batch * seq / dt
        minfo = step._moment_bytes_info
    finally:
        from paddle_tpu.distributed import comm as _comm

        _comm._state.hybrid_mesh = None
    tag = "" if quant else "_off"
    out = {
        f"gpt_medium_bf16_q8m{tag}_step_ms": round(dt / steps * 1e3, 2),
        f"gpt_medium_bf16_q8m{tag}_tokens_per_sec": round(tok_s, 0),
        f"gpt_medium_bf16_q8m{tag}_compile_s": round(compile_s, 1),
    }
    if quant and minfo:
        # report-only: resident optimizer-state bytes, payload + scales
        out["gpt_medium_bf16_q8m_moment_mb"] = round(
            minfo["bytes_resident"] / 1e6, 1)
        out["gpt_medium_bf16_q8m_moment_reduction_x"] = \
            minfo["reduction_x"]
    return out


def _bench_decode_q8w(batch_sizes=(1, 8), prompt_len=128,
                      new_tokens=64):
    """Serving bench over an int8 CHECKPOINT (ISSUE 19): the
    GPT-medium-shaped TransformerLM is block-quantized once via
    `jit.save_quantized`, reloaded narrow (`load_quantized`: int8
    payload becomes the resident weight, scales attach as buffers, the
    compiled decode streams the narrow bytes + scales from HBM every
    token), and generate() prices decode at batch 1/8 next to the
    full-width `serve_gpt_medium_tokens_per_sec_bN` keys. The
    checkpoint load itself is timed (`q_ckpt_load_ms`, gated) and the
    on-disk payload/scale bytes ride report-only."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.jit import DecodeStep, PrefillStep, save_quantized
    from paddle_tpu.serving import generate
    from paddle_tpu.serving.model import TransformerLM

    paddle.seed(0)
    cap = prompt_len + new_tokens
    model = TransformerLM(32000, d_model=1024, num_heads=16,
                          num_layers=24, max_position=cap)
    model.eval()
    tmp = tempfile.mkdtemp(prefix="q8w_ckpt_")
    out = {}
    try:
        path = os.path.join(tmp, "gpt_medium")
        info = save_quantized(model, path, dtype="int8")
        paddle.seed(0)
        qmodel = TransformerLM(32000, d_model=1024, num_heads=16,
                               num_layers=24, max_position=cap)
        qmodel.eval()
        meta = qmodel.load_quantized(path)
        out["q_ckpt_load_ms"] = round(meta["load_ms"], 1)
        # report-only (no _ms/per_sec suffix -> never gated): narrow
        # checkpoint bytes vs the full-width form it replaces
        out["q_ckpt_payload_mb"] = round(
            (info["bytes_payload"] + info["bytes_scales"]) / 1e6, 1)
        # int8 payload is 1 byte/elem, so the f32 form it replaces is
        # exactly 4x the payload bytes
        out["q_ckpt_reduction_x"] = round(
            4.0 * info["bytes_payload"]
            / (info["bytes_payload"] + info["bytes_scales"]), 2)
        pre = PrefillStep(qmodel)
        dec = DecodeStep(qmodel)
        for B in batch_sizes:
            prompts = (np.arange(B * prompt_len) % 31000).reshape(
                B, prompt_len).astype(np.int32)
            _ = generate(qmodel, prompts, 2, max_length=cap,
                         prefill=pre, decode=dec)
            t0 = time.perf_counter()
            toks = generate(qmodel, prompts, new_tokens,
                            max_length=cap, prefill=pre, decode=dec)
            assert toks.shape == (B, new_tokens)
            dt = time.perf_counter() - t0
            out[f"serve_gpt_medium_tokens_per_sec_b{B}_q8w"] = round(
                B * new_tokens / dt, 1)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_decode(batch_sizes=(1, 8, 64), prompt_len=128, new_tokens=64):
    """Serving bench (ISSUE 9): the compiled prefill/decode pair over
    the GPT-medium-shaped TransformerLM (same decoder the training
    bench prices).

    Throughput: `generate()` at batch 1/8/64 — the loop state stays on
    device and the host syncs ONCE at the end, so the number is the
    device's steady decode rate (`serve_gpt_medium_tokens_per_sec_bN`).

    Latency: batch 1 with a host sync after EVERY token — the per-token
    time a single-stream client observes (`serve_gpt_medium_token_p50_ms`
    / `_p99_ms`), plus the bucketed prefill cost
    (`serve_gpt_medium_prefill_ms`). All keys land under the
    tools/bench_continuity.py >10% gate (per_sec higher-better, _ms
    lower-better)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit import DecodeState, DecodeStep, PrefillStep
    from paddle_tpu.serving import generate
    from paddle_tpu.serving.model import TransformerLM

    paddle.seed(0)
    cap = prompt_len + new_tokens
    model = TransformerLM(32000, d_model=1024, num_heads=16,
                          num_layers=24, max_position=cap)
    model.eval()
    pre = PrefillStep(model)
    dec = DecodeStep(model)
    out = {}
    for B in batch_sizes:
        prompts = (np.arange(B * prompt_len) % 31000).reshape(
            B, prompt_len).astype(np.int32)
        # warm (compiles prefill for this B + the decode step once)
        _ = generate(model, prompts, 2, max_length=cap, prefill=pre,
                     decode=dec)
        t0 = time.perf_counter()
        toks = generate(model, prompts, new_tokens, max_length=cap,
                        prefill=pre, decode=dec)
        assert toks.shape == (B, new_tokens)
        dt = time.perf_counter() - t0
        out[f"serve_gpt_medium_tokens_per_sec_b{B}"] = round(
            B * new_tokens / dt, 1)

    # batch-1 per-token latency: sync every step (client view). The
    # prompt pads to the SAME bucket the warm generate() used, so
    # prefill_ms prices the warm compiled program, not a fresh compile.
    from paddle_tpu.serving.engine import bucket_for

    bucket = bucket_for(prompt_len, cap)
    prompts = np.zeros((1, bucket), np.int32)
    prompts[0, :prompt_len] = np.arange(prompt_len) % 31000
    t0 = time.perf_counter()
    last, cache_raws, pos = pre(
        model.gen_cache(1, cap), prompts,
        np.full((1,), prompt_len, np.int32))
    first = jnp.argmax(last, -1).astype(jnp.int32)
    _ = np.asarray(first)
    out["serve_gpt_medium_prefill_ms"] = round(
        (time.perf_counter() - t0) * 1e3, 2)
    state = DecodeState.make(cache_raws, first, pos)
    lat = []
    for _ in range(new_tokens - 1):
        t0 = time.perf_counter()
        emit, _, state = dec(state)
        _ = np.asarray(emit)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat.sort()
    out["serve_gpt_medium_token_p50_ms"] = round(
        lat[len(lat) // 2], 2)
    out["serve_gpt_medium_token_p99_ms"] = round(
        lat[min(int(len(lat) * 0.99), len(lat) - 1)], 2)
    # the fleet monitor's online log-histogram digest over the SAME
    # samples (ISSUE 14): report-only `_digest` keys pin the stored-vs-
    # merged-counts agreement each round (never gated — the `_ms` pair
    # above is the gated truth; the digest is bin-quantized)
    from paddle_tpu.observability.monitor import LogHistogram

    hist = LogHistogram()
    for v in lat:
        hist.add(v)
    out["serve_gpt_medium_token_p50_ms_digest"] = round(
        hist.percentile(50), 2)
    out["serve_gpt_medium_token_p99_ms_digest"] = round(
        hist.percentile(99), 2)
    return out


def _bench_decode_paged(prompt_len=128, new_tokens=64, block=16,
                        chunk=32):
    """Production-tier serving bench (ISSUE 13): the PAGED-KV decode
    throughput next to round-10's contiguous `serve_gpt_medium_*` keys
    (`_paged` suffix — same >10% continuity gate), the time-to-first-
    token of a loaded engine under CHUNKED prefill
    (`serve_gpt_medium_ttft_ms`, lower-better gated), and the KV HBM
    bytes the paged pool actually holds vs the worst-case contiguous
    reservation for the same slots (report-only extras — the headroom
    PERF.md round-13 prices)."""
    import jax.numpy as jnp  # noqa: F401 — device warm-up parity

    import paddle_tpu as paddle
    from paddle_tpu.serving import (
        InferenceEngine, Request, TransformerLM, generate, paged_kv,
    )

    paddle.seed(0)
    cap = prompt_len + new_tokens
    cap += (-cap) % block  # engine pools splice block-aligned
    model = TransformerLM(32000, d_model=1024, num_heads=16,
                          num_layers=24, max_position=cap)
    model.eval()
    out = {}
    B = 8
    prompts = (np.arange(B * prompt_len) % 31000).reshape(
        B, prompt_len).astype(np.int32)
    from paddle_tpu.jit import DecodeStep, PrefillStep

    pre = PrefillStep(model)
    dec = DecodeStep(model)
    prev = os.environ.get("PADDLE_SERVE_BLOCK_SIZE")
    os.environ["PADDLE_SERVE_BLOCK_SIZE"] = str(block)
    try:
        # warm the SAME step objects the timed call uses (the round-10
        # pattern): the timed interval prices decode, not trace+compile
        _ = generate(model, prompts, 2, max_length=cap, prefill=pre,
                     decode=dec)
        t0 = time.perf_counter()
        toks = generate(model, prompts, new_tokens, max_length=cap,
                        prefill=pre, decode=dec)
        assert toks.shape == (B, new_tokens)
        dt = time.perf_counter() - t0
        out["serve_gpt_medium_tokens_per_sec_b8_paged"] = round(
            B * new_tokens / dt, 1)
    finally:
        if prev is None:
            os.environ.pop("PADDLE_SERVE_BLOCK_SIZE", None)
        else:
            os.environ["PADDLE_SERVE_BLOCK_SIZE"] = prev

    # TTFT under load with chunked prefill: slots stay busy decoding
    # while each new prompt prefills chunk-by-chunk — submit->first-
    # token is what the router's SLO admission bounds
    # pool sized by ACTUAL demand (prompt + 16 new tokens per slot),
    # not capacity — the paged-vs-worstcase byte pair below is the
    # point of the layout
    demand = 4 * paged_kv.blocks_for(prompt_len + 16, block) + 1
    engine = InferenceEngine(model, slots=4, max_length=cap,
                             block_size=block, prefill_chunk=chunk,
                             pool_blocks=demand)
    for i in range(8):
        p = (np.arange(prompt_len) % 31000).astype(np.int32)
        engine.submit(Request(p, max_new_tokens=16, rid=i))
    res = engine.run()
    ttfts = sorted(r.ttft_ms for r in res.values())
    out["serve_gpt_medium_ttft_ms"] = round(ttfts[len(ttfts) // 2], 2)
    # KV HBM: what the paged pool holds vs the contiguous worst case
    # for the same slot count (static shape arithmetic)
    out["serve_kv_hbm_paged_bytes"] = paged_kv.pool_bytes(
        engine._state.caches)
    dh = model.d_model // 16
    itemsize = 1 if os.environ.get("PADDLE_SERVE_KV_QUANT") else 4
    out["serve_kv_hbm_worstcase_bytes"] = paged_kv.worst_case_bytes(
        4, 16, cap, dh, itemsize=itemsize, layers=24)
    return out


def _bench_serve_failover(n_requests=6, budget=48, rate=4000.0):
    """Serving-plane fault tolerance (ISSUE 15): host-kill → first
    post-failover token on a survivor (`serve_failover_recovery_ms`,
    lower-better under the continuity gate) and the tokens the recovery
    dropped (`serve_failover_tokens_lost` — ASSERTED 0: the resume path
    re-prefills prompt + emitted prefix, so greedy continuations are
    token-exact by construction; the forbidden alternative is request
    loss, which PERF.md round-15 prices).

    Runs the jax-free mailbox workers (the dryrun transport) so the
    number measures the CONTROL plane — detection latency (timeout +
    probation backoff) plus re-submission — not model compute; the
    re-prefill cost on a real engine is the round-10 prefill_ms at the
    request's bucket, priced separately in PERF.md."""
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    from paddle_tpu.serving.router import FileHost, Router, sim_next_token

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="pdtpu_failover_bench_")
    base = os.path.join(tmp, "mail")
    obs = os.path.join(tmp, "obs")
    os.makedirs(obs, exist_ok=True)
    worker = os.path.join(repo, "paddle_tpu", "serving", "router.py")
    procs = []
    out = {}
    try:
        for rank in (0, 1):
            env = dict(os.environ, PADDLE_TRAINER_ID=str(rank),
                       PADDLE_OBS_DIR=obs)
            env.pop("PADDLE_FAULT_SPEC", None)
            env.pop("PADDLE_OBS_BUS_FILE", None)
            procs.append(subprocess.Popen(
                [sys.executable, worker, repo, base, str(rate), "0.005"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        hosts = [FileHost(os.path.join(base, f"host{r}"), r, obs_dir=obs)
                 for r in (0, 1)]
        # tight detection knobs: the bench prices the recovery path,
        # not the production-default patience
        router = Router(hosts, admit_queue=64, avg_new_tokens=budget,
                        host_timeout_ms=250, retry_backoff_ms=50,
                        retry_max=2)
        prompts = {}
        for i in range(n_requests):
            rid = f"fo{i}"
            prompts[rid] = [i + 1, i + 2, i + 3]
            router.submit({"rid": rid, "prompt_ids": prompts[rid],
                           "max_new_tokens": budget})
        deadline = time.time() + 60
        # let host 0 get mid-decode (progress on the bus) before the kill
        while time.time() < deadline:
            router.tick()
            if any(e.progress for e in router._tracked.values()
                   if e.host == 0):
                break
            time.sleep(0.005)
        t_kill = time.perf_counter()
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait()
        recovery_ms = None
        while time.time() < deadline and \
                len(router.completed) < n_requests:
            router.tick()
            if recovery_ms is None:
                resumed_live = any(
                    e.attempts > 1 and e.progress
                    for e in router._tracked.values())
                resumed_done = any(
                    r.get("resumed") for r in router.completed.values())
                if resumed_live or resumed_done:
                    recovery_ms = (time.perf_counter() - t_kill) * 1e3
            time.sleep(0.005)
        assert len(router.completed) == n_requests, (
            f"failover bench dropped requests: "
            f"{len(router.completed)}/{n_requests}")
        assert recovery_ms is not None
        lost = 0
        for rid, prompt in prompts.items():
            chain = list(prompt)
            expect = []
            for _ in range(budget):
                t = sim_next_token(chain)
                chain.append(t)
                expect.append(t)
            got = router.completed[rid]["tokens"]
            assert got == expect, (
                f"failover bench: {rid} not token-exact vs the "
                f"uninterrupted chain")
            lost += budget - len(got)
        assert lost == 0, f"failover bench lost {lost} tokens"
        out["serve_failover_recovery_ms"] = round(recovery_ms, 1)
        out["serve_failover_tokens_lost"] = lost
        out["serve_failover_requests_recovered"] = router.failovers
    finally:
        try:
            os.makedirs(base, exist_ok=True)
            open(os.path.join(base, "stop"), "w").close()
            for p in procs:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_serve_failover_migrate(n_requests=6, budget=48, rate=4000.0):
    """KV block migration plane (ISSUE 17): drain-triggered recovery
    over the MIGRATE fast path — drain_host -> extract verb -> bundle
    blob -> CRC gate -> splice -> first post-migration token on the
    survivor. `serve_failover_recovery_ms_migrate` lands next to the
    round-15 re-prefill key under the continuity gate (the pair IS the
    PERF.md round-17 pricing: block-move vs re-prefill);
    `serve_migrate_bytes` / `serve_migrate_blocks` ride report-only.
    Token-exactness and zero-drop are asserted inside, like the
    re-prefill bench; at least one request must take the fast path
    (migrations >= 1) or the number would silently price the wrong
    ladder rung."""
    import shutil
    import subprocess
    import sys
    import tempfile

    from paddle_tpu.serving.router import FileHost, Router, sim_next_token

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="pdtpu_migrate_bench_")
    base = os.path.join(tmp, "mail")
    obs = os.path.join(tmp, "obs")
    os.makedirs(obs, exist_ok=True)
    worker = os.path.join(repo, "paddle_tpu", "serving", "router.py")
    procs = []
    out = {}
    try:
        for rank in (0, 1):
            env = dict(os.environ, PADDLE_TRAINER_ID=str(rank),
                       PADDLE_OBS_DIR=obs)
            env.pop("PADDLE_FAULT_SPEC", None)
            env.pop("PADDLE_OBS_BUS_FILE", None)
            procs.append(subprocess.Popen(
                [sys.executable, worker, repo, base, str(rate), "0.005"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        hosts = [FileHost(os.path.join(base, f"host{r}"), r, obs_dir=obs)
                 for r in (0, 1)]
        # drain_inplace_tokens small so mid-decode victims clear the
        # cost boundary and take the migrate path (the thing priced)
        router = Router(hosts, admit_queue=64, avg_new_tokens=budget,
                        host_timeout_ms=250, retry_backoff_ms=50,
                        retry_max=2, migrate_timeout_ms=2000,
                        drain_inplace_tokens=4)
        prompts = {}
        for i in range(n_requests):
            rid = f"mg{i}"
            prompts[rid] = [i + 1, i + 2, i + 3]
            router.submit({"rid": rid, "prompt_ids": prompts[rid],
                           "max_new_tokens": budget})
        deadline = time.time() + 60
        # the drained host must be mid-decode: the fast path moves KV
        # that exists, not an empty cache
        while time.time() < deadline:
            router.tick()
            if any(e.progress for e in router._tracked.values()
                   if e.host == 0):
                break
            time.sleep(0.005)
        t_drain = time.perf_counter()
        router.drain_host(0)
        assert router.migrations >= 1, (
            "migrate bench: drain took the re-prefill path "
            f"(migrate_failed={router.migrate_failed})")
        recovery_ms = None
        while time.time() < deadline and \
                len(router.completed) < n_requests:
            router.tick()
            if recovery_ms is None:
                resumed_live = any(
                    e.attempts > 1 and e.progress
                    for e in router._tracked.values())
                resumed_done = any(
                    r.get("resumed") for r in router.completed.values())
                if resumed_live or resumed_done:
                    recovery_ms = (time.perf_counter() - t_drain) * 1e3
            time.sleep(0.005)
        assert len(router.completed) == n_requests, (
            f"migrate bench dropped requests: "
            f"{len(router.completed)}/{n_requests}")
        assert recovery_ms is not None
        for rid, prompt in prompts.items():
            chain = list(prompt)
            expect = []
            for _ in range(budget):
                t = sim_next_token(chain)
                chain.append(t)
                expect.append(t)
            assert router.completed[rid]["tokens"] == expect, (
                f"migrate bench: {rid} not token-exact vs the "
                f"uninterrupted chain")
        out["serve_failover_recovery_ms_migrate"] = round(recovery_ms, 1)
        out["serve_migrate_blocks"] = router.migrate_blocks
        out["serve_migrate_bytes"] = router.migrate_bytes
    finally:
        try:
            os.makedirs(base, exist_ok=True)
            open(os.path.join(base, "stop"), "w").close()
            for p in procs:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_ctl(waves=8, per_wave=6, budget=8, rate=4000.0):
    """Train-serve co-tenancy (ISSUE 16): what a serving burst sheds
    with the fleet controller OFF vs ON, plus the cost of one lend
    transition. Jax-free like the failover bench — one mailbox worker,
    a small admission bound (admit_queue=2), and bursts of `per_wave`
    submits per control window, so the OFF run rejects most of every
    wave while the ON run's controller sees the rejection rate, lends
    after `sustain_n` hot windows (the bench's lend callback registers
    4x capacity on the host — the stand-in for expand_slots absorbing
    the lent devices), and later waves admit in full.

    `serve_burst_shed_tokens_ctl_off/_on` are report-only (no gated
    suffix); `ctl_lend_ms` (begin->commit journal wall time) lands
    under the continuity gate's lower-is-better `_ms` rule."""
    import shutil
    import subprocess
    import sys
    import tempfile

    from paddle_tpu.distributed.fleet_controller import (
        CtlConfig, FleetController,
    )
    from paddle_tpu.observability.monitor import FleetMonitor
    from paddle_tpu.serving.router import FileHost, Router

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "paddle_tpu", "serving", "router.py")
    out = {}

    def _run(with_ctl: bool) -> dict:
        tmp = tempfile.mkdtemp(prefix="pdtpu_ctl_bench_")
        base = os.path.join(tmp, "mail")
        obs = os.path.join(tmp, "obs")
        os.makedirs(obs, exist_ok=True)
        env_prev = os.environ.get("PADDLE_OBS_DIR")
        os.environ["PADDLE_OBS_DIR"] = obs  # router_metrics -> monitor
        proc = None
        try:
            wenv = dict(os.environ, PADDLE_TRAINER_ID="0",
                        PADDLE_OBS_DIR=obs)
            wenv.pop("PADDLE_FAULT_SPEC", None)
            wenv.pop("PADDLE_OBS_BUS_FILE", None)
            proc = subprocess.Popen(
                [sys.executable, worker, repo, base, str(rate), "0.005"],
                env=wenv, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            host = FileHost(os.path.join(base, "host0"), 0, obs_dir=obs)
            router = Router([host], admit_queue=2, avg_new_tokens=budget,
                            admit_ttft_ms=0)
            ctl = None
            if with_ctl:
                mon = FleetMonitor(obs, emit=False)
                ctl = FleetController(
                    obs, monitor=mon, donor_ranks=[7],
                    config=CtlConfig(pressure=0.25, release=0.01,
                                     sustain_n=2, cooldown_n=2,
                                     window_s=0.01),
                    lend=lambda ranks, s: router.register_capacity(0, 4),
                    reclaim=lambda ranks, s: router.register_capacity(0, 1),
                    emit=True)
            rid = 0
            for _ in range(waves):
                for _ in range(per_wave):
                    rid += 1
                    router.submit({"rid": f"b{rid}",
                                   "prompt_ids": [1, 2, 3],
                                   "max_new_tokens": budget})
                deadline = time.time() + 10
                while time.time() < deadline and router.inflight():
                    router.tick()
                    time.sleep(0.005)
                if ctl is not None:
                    mon.poll()
                    ctl.window()
            return {"shed": router.rejected * budget,
                    "admitted": router.admitted,
                    "lend_ms": (ctl.transitions[0]["dur_ms"]
                                if ctl is not None and ctl.transitions
                                else None)}
        finally:
            try:
                os.makedirs(base, exist_ok=True)
                open(os.path.join(base, "stop"), "w").close()
                if proc is not None:
                    try:
                        proc.wait(timeout=20)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            finally:
                if env_prev is None:
                    os.environ.pop("PADDLE_OBS_DIR", None)
                else:
                    os.environ["PADDLE_OBS_DIR"] = env_prev
                shutil.rmtree(tmp, ignore_errors=True)

    off = _run(False)
    on = _run(True)
    assert on["lend_ms"] is not None, "ctl bench: controller never lent"
    assert on["shed"] < off["shed"], (
        f"ctl bench: lend did not reduce shed "
        f"(on {on['shed']} vs off {off['shed']})")
    out["serve_burst_shed_tokens_ctl_off"] = off["shed"]
    out["serve_burst_shed_tokens_ctl_on"] = on["shed"]
    out["ctl_lend_ms"] = round(on["lend_ms"], 1)
    return out


def _bench_ctl_live(steps=30, hot=12):
    """Live lend plane (ISSUE 20): the serving-capacity latency a live
    migration actually delivers. Runs one 2-rank launcher cycle over
    the jax-free ``tiny_rank`` live protocol (``PADDLE_CTL=live``),
    watches the journal for the ``ctl_lend`` commit, drops a probe
    request into the lent rank's mailbox THAT instant, and prices

    - ``ctl_live_lend_ms``: lend commit -> the probe request's done
      file (first served tokens). This is the number the whole phase
      ladder exists to minimize — weight delivery via ``.pdqparams``
      (the 4x-narrower int8 load from round 19) is its dominant term —
      and it lands under the continuity gate's lower-better ``_ms``
      rule;
    - ``ctl_live_reclaim_ms``: the reclaim ladder's begin->commit wall
      time from the journal (drain + leave + rejoin). Report-only: it
      scales with whatever queue depth drain happens to find, so
      gating it would flake.
    """
    import shutil
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="pdtpu_ctl_live_")
    obs = os.path.join(tmp, "obs")
    serve = os.path.join(tmp, "serve")
    ckpt = os.path.join(tmp, "w.pdqparams")
    os.makedirs(obs)
    with open(ckpt, "wb") as f:
        f.write(b"\0" * 1_000_000)
    env = dict(os.environ)
    for k in ("PADDLE_FAULT_SPEC", "PADDLE_OBS_BUS_FILE"):
        env.pop(k, None)
    env.update({
        "PADDLE_OBS_DIR": obs, "PADDLE_CTL": "live",
        "PADDLE_RESHARD_MODE": "shrink", "PADDLE_MON_POLL": "0.05",
        "PADDLE_CTL_WINDOW_S": "0.15", "PADDLE_CTL_SUSTAIN_N": "2",
        "PADDLE_CTL_COOLDOWN_N": "2",
        "PADDLE_CTL_SERVE_CKPT": ckpt, "PADDLE_CTL_SERVE_DIR": serve,
        "TINY_MODE": "live", "TINY_TRAIN_STEPS": str(steps),
        "TINY_TRAIN_DT": "0.05", "TINY_SERVE_HOT": str(hot),
        "JAX_PLATFORMS": "cpu",
    })
    journal = os.path.join(obs, "telemetry.launcher.jsonl")
    done = os.path.join(serve, "host1", "outbox", "done_bench.json")
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2",
         os.path.join(repo, "tests", "helpers", "tiny_rank.py")],
        env=env, cwd=repo, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        # watch for the lend commit, then stage the probe request
        t_commit = None
        deadline = time.time() + 60
        while time.time() < deadline and t_commit is None:
            if proc.poll() is not None:
                break
            if os.path.exists(journal):
                for line in open(journal):
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue
                    if r.get("kind") == "ctl_lend" and \
                            r["payload"].get("phase") == "commit":
                        t_commit = float(r["time"])
                        break
            time.sleep(0.002)
        assert t_commit is not None, "ctl live bench: lend never committed"
        inbox = os.path.join(serve, "host1", "inbox")
        os.makedirs(inbox, exist_ok=True)
        with open(os.path.join(inbox, "req_bench.json"), "w") as f:
            json.dump({"rid": "bench", "token_ids": [5, 7],
                       "max_new_tokens": 4}, f)
        while time.time() < deadline and not os.path.exists(done):
            if proc.poll() is not None:
                break
            time.sleep(0.002)
        assert os.path.exists(done), "ctl live bench: request never served"
        lend_ms = (os.stat(done).st_mtime - t_commit) * 1e3
        rc = proc.wait(timeout=60)
        assert rc == 0, f"ctl live bench: launcher rc {rc}"
        reclaim_ms = None
        for line in open(journal):
            r = json.loads(line)
            if r.get("kind") == "ctl_reclaim" and \
                    r["payload"].get("phase") == "commit" and \
                    not r["payload"].get("forced"):
                reclaim_ms = float(r["payload"].get("dur_ms") or 0.0)
                break
        assert reclaim_ms is not None, "ctl live bench: never reclaimed"
        return {"ctl_live_lend_ms": round(max(lend_ms, 0.0), 1),
                "ctl_live_reclaim_ms": round(reclaim_ms, 1)}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_serve_multitenant(prompt_len=128, new_tokens=32, block=16):
    """Multi-tenant serving plane (ISSUE 18): the submit->first-token
    time of a borrower whose preamble is already PUBLISHED in the
    refcounted CoW prefix cache (`serve_gpt_medium_ttft_ms_prefix_warm`,
    lower-better gated — the shared-prefill saving the cache exists to
    buy; compare against the cold `serve_gpt_medium_ttft_ms` key), and
    the decode-tier throughput when every prefill burns on a DEDICATED
    prefill host and ships across as a KV bundle
    (`serve_gpt_medium_tokens_per_sec_b8_disagg`, gated — the decode
    tier's steady cadence with the prefill steal removed).
    `serve_prefix_hit_rate` and `serve_adapter_count` ride report-only
    (PERF.md round 18 prices both)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import (
        AdapterSet, InferenceEngine, Request, TransformerLM,
    )
    from paddle_tpu.serving.router import LocalHost, PrefillHost, Router

    paddle.seed(0)
    cap = prompt_len + new_tokens
    cap += (-cap) % block  # engine pools splice block-aligned
    model = TransformerLM(32000, d_model=1024, num_heads=16,
                          num_layers=24, max_position=cap)
    model.eval()
    # adapters attach BEFORE any engine: the compiled steps snapshot
    # the stacked buffers at construction
    adapters = AdapterSet(model, n_adapters=4, rank=8)
    adapters.load(1)
    adapters.load(2)
    out = {"serve_adapter_count": len(adapters.resident) - 1}
    prompt = (np.arange(prompt_len) % 31000).astype(np.int32)

    # -- warm-prefix TTFT: cold publishes, the borrower shares --------
    eng = InferenceEngine(model, slots=2, max_length=cap,
                          block_size=block, prefix_cache=True)
    eng.submit(Request(prompt, max_new_tokens=8, rid="cold"))
    eng.run()
    eng.submit(Request(prompt, max_new_tokens=8, rid="warm"))
    warm = eng.run()["warm"]
    out["serve_gpt_medium_ttft_ms_prefix_warm"] = round(warm.ttft_ms, 2)
    out["serve_prefix_hit_rate"] = round(eng._prefix_hits / 2.0, 3)

    # -- disaggregated decode-tier throughput: B=8 mixed-adapter ------
    B = 8
    decode = LocalHost(InferenceEngine(model, slots=B, max_length=cap,
                                       block_size=block))
    prefill = PrefillHost(InferenceEngine(model, slots=2,
                                          max_length=cap,
                                          block_size=block))
    router = Router([decode], prefill_hosts=[prefill],
                    admit_queue=2 * B, avg_new_tokens=new_tokens)
    t0 = time.perf_counter()
    for i in range(B):
        router.submit({"rid": f"d{i}", "prompt_ids": prompt.tolist(),
                       "max_new_tokens": new_tokens,
                       "adapter": i % 3})
    while len(router.completed) < B:
        router.tick()
        decode.pump()
    dt = time.perf_counter() - t0
    assert router.disagg_prefills == B, (
        f"disagg bench: {router.disagg_fallbacks} handoffs fell back "
        f"to colocated prefill")
    out["serve_gpt_medium_tokens_per_sec_b8_disagg"] = round(
        B * new_tokens / dt, 1)
    return out


def _bench_flash_attention(steps=500):
    """Long-context attention: the Pallas flash kernel vs XLA dense at
    S=2048 causal. The `steps` iterations run INSIDE one jitted lax.scan
    (each output chained into the next query), so a single dispatch
    measures device time rather than per-call host dispatch."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention

    B, H, S, D = 4, 12, 2048, 64
    # unseeded: operands differ across bench invocations
    q, k, v = [
        jax.device_put(jnp.asarray(
            np.random.rand(B, H, S, D).astype(np.float32) - 0.5
        ))
        for _ in range(3)
    ]

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        pos = jnp.arange(S)
        s = jnp.where(pos[None, :] > pos[:, None], -1e30, s)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def looped(attn):
        @jax.jit
        def run(q, k, v):
            def body(qq, _):
                return attn(qq, k, v), None

            out, _ = jax.lax.scan(body, q, None, length=steps)
            return out

        return run

    flash_l = looped(
        lambda q, k, v: flash_attention(q, k, v, True, 256, 256, None,
                                        False)
    )
    dense_l = looped(dense)

    # compile/warm on one input set, time on another; the barrier is a
    # tiny devget slice
    q2 = jax.device_put(q + 1.0)
    _ = np.asarray(q2[0, 0, 0, :2])

    def ms(f):
        _ = np.asarray(f(q2, k, v)[0, 0, 0, :2])  # compile + real sync
        t0 = time.perf_counter()
        _ = np.asarray(f(q, k, v)[0, 0, 0, :2])
        return (time.perf_counter() - t0) / steps * 1e3

    out = {
        "flash_attn_s2048_pallas_ms": round(ms(flash_l), 2),
        "flash_attn_s2048_dense_ms": round(ms(dense_l), 2),
    }

    # long context: 32k causal fwd+bwd through the K/V-streaming kernel
    # (impossible for the dense path: the 32k x 32k score matrix alone is
    # 4GB; the old VMEM-resident kernel capped at 16k)
    q32, k32, v32 = [
        jax.device_put(jnp.asarray(
            np.random.rand(1, 1, 32768, 128).astype(np.float32) - 0.5))
        for _ in range(3)
    ]
    vg = jax.jit(jax.value_and_grad(
        lambda a, b, c: flash_attention(
            a, b, c, True, 512, 512, None, False).sum(),
        (0, 1, 2),
    ))
    val, _ = vg(q32 + 1.0, k32, v32)  # compile+warm on different values
    _ = np.asarray(val)
    t0 = time.perf_counter()
    val, grads = vg(q32, k32, v32)
    _ = np.asarray(val)
    out["flash_attn_s32k_fwdbwd_ms"] = round(
        (time.perf_counter() - t0) * 1e3, 1
    )
    return out


def main():
    from paddle_tpu import optimizer
    from paddle_tpu.vision.models import LeNet, resnet50

    extra = {}

    lenet_ips, bd, sp = _repeat(lambda: _bench_train(
        LeNet,
        lambda m: optimizer.Adam(
            learning_rate=1e-3, parameters=m.parameters()
        ),
        (1, 28, 28), 10, batch=256, steps=50, label="lenet",
    ))
    extra.update(bd)
    # r01-r04 continuity: this was the headline metric; it is
    # per-program-overhead-bound (r02 663.6, r03 ~15-26k, r04 58196),
    # so round 5 promotes the compute-bound ResNet-50 bf16 number to
    # `metric` instead (VERDICT r4 weak #8)
    extra["lenet_mnist_train_imgs_per_sec"] = round(lenet_ips, 1)
    extra["lenet_mnist_train_imgs_per_sec_spread"] = sp

    r50_ips, bd, sp = _repeat(lambda: _bench_train(
        lambda: resnet50(num_classes=1000),
        lambda m: optimizer.Momentum(
            learning_rate=0.1, momentum=0.9, parameters=m.parameters()
        ),
        (3, 224, 224), 1000, batch=256, steps=20, label="resnet50",
    ))
    extra.update(bd)
    extra["resnet50_synthetic_imgs_per_sec"] = round(r50_ips, 1)
    extra["resnet50_synthetic_imgs_per_sec_spread"] = sp

    r50_bf16_ips, bd, sp = _repeat(lambda: _bench_train(
        lambda: resnet50(num_classes=1000),
        lambda m: optimizer.Momentum(
            learning_rate=0.1, momentum=0.9, parameters=m.parameters()
        ),
        (3, 224, 224), 1000, batch=256, steps=20, label="resnet50_bf16",
        amp=True,
    ))
    extra.update(bd)
    extra["resnet50_bf16_imgs_per_sec"] = round(r50_bf16_ips, 1)
    extra["resnet50_bf16_imgs_per_sec_spread"] = sp

    bert_ips, bd, sp = _repeat(_bench_bert)
    extra.update(bd)
    extra["bert_base_bf16_samples_per_sec"] = round(bert_ips, 1)
    extra["bert_base_bf16_samples_per_sec_spread"] = sp

    # round 6: the default GPT path IS the overhauled decoder (flash
    # attention auto-routed, Pallas fused LN, blockwise vocab CE) — the
    # old PADDLE_BENCH_GPT_FLASH side channel is retired. The headline
    # pair's other half (forced dense attention + materialized-logits
    # CE, i.e. the PADDLE_FLASH_DEFAULT=0 / PADDLE_CE_CHUNK=0 escape
    # hatches) records under *_dense when PADDLE_BENCH_GPT_DENSE=1.
    gpt_tok, gpt_bd, sp = _repeat(
        lambda: (lambda d: (d["gpt_medium_bf16_tokens_per_sec"], d))(
            _bench_gpt())
    )
    extra.update(gpt_bd)
    extra["gpt_medium_bf16_tokens_per_sec_spread"] = sp

    # numerical-guard overhead pair (ISSUE 5): the default gpt numbers
    # above ran with the in-graph sentinel ON (PADDLE_GUARD_MODE=skip is
    # the default); re-record with the guard compiled out, restoring
    # whatever mode the operator exported afterwards. The pair feeds
    # tools/bench_continuity.py's guard_overhead gate (<2%).
    _guard_env_before = os.environ.get("PADDLE_GUARD_MODE")
    try:
        gpt_off_tok, off_bd, off_sp = _repeat(
            lambda: (lambda d: (d["gpt_medium_bf16_tokens_per_sec"], d))(
                _bench_gpt(guard="off"))
        )
    finally:
        if _guard_env_before is None:
            os.environ.pop("PADDLE_GUARD_MODE", None)
        else:
            os.environ["PADDLE_GUARD_MODE"] = _guard_env_before
    for k in ("step_ms", "tokens_per_sec", "compile_s"):
        extra[f"gpt_medium_bf16_{k}_noguard"] = \
            off_bd[f"gpt_medium_bf16_{k}"]
    extra["gpt_medium_bf16_tokens_per_sec_noguard_spread"] = off_sp
    if gpt_off_tok > 0:
        extra["guard_overhead_pct"] = round(
            max(0.0, (gpt_off_tok - gpt_tok) / gpt_off_tok) * 100.0, 2)

    if os.environ.get("PADDLE_BENCH_GPT_DENSE", "") not in ("", "0"):
        _, dense_d, dsp = _repeat(
            lambda: (lambda d: (d["gpt_medium_bf16_tokens_per_sec"], d))(
                _bench_gpt(dense=True))
        )
        for k in ("step_ms", "tokens_per_sec", "compile_s"):
            extra[f"gpt_medium_bf16_{k}_dense"] = \
                dense_d[f"gpt_medium_bf16_{k}"]
        extra["gpt_medium_bf16_tokens_per_sec_dense_spread"] = dsp
    import jax

    if len(jax.devices()) > 1 and len(jax.devices()) % 2 == 0:
        # multi-device pair (ISSUE 6): sharded-flash dp x mp2 vs the
        # PADDLE_FLASH_SHARD=0 dense fallback — the shard_map-seam win
        # lands under the bench_continuity >10% gate
        _, mc_d, mc_sp = _repeat(
            lambda: (lambda d: (
                d["gpt_medium_bf16_dp_mp_tokens_per_sec"], d))(
                _bench_gpt_multichip())
        )
        extra.update(mc_d)
        extra["gpt_medium_bf16_dp_mp_tokens_per_sec_spread"] = mc_sp
        _, mcd_d, mcd_sp = _repeat(
            lambda: (lambda d: (
                d["gpt_medium_bf16_dp_mp_dense_tokens_per_sec"], d))(
                _bench_gpt_multichip(shard_off=True))
        )
        extra.update(mcd_d)
        extra["gpt_medium_bf16_dp_mp_dense_tokens_per_sec_spread"] = mcd_sp

    if len(jax.devices()) >= 4 and len(jax.devices()) % 2 == 0:
        # quantized dcn-hop pair (ISSUE 10): int8 block-scaled grad
        # allreduce over the slow inter-node hop vs the f32 hop, both on
        # the hierarchical dcn x ici mesh with the explicit per-grad
        # reduction — the wire-width win lands under the >10% gate and
        # the priced comm bytes ride report-only
        _, q8_d, q8_sp = _repeat(
            lambda: (lambda d: (
                d["gpt_medium_bf16_dp_q8_tokens_per_sec"], d))(
                _bench_gpt_dp_q8(quant=True))
        )
        extra.update(q8_d)
        extra["gpt_medium_bf16_dp_q8_tokens_per_sec_spread"] = q8_sp
        _, q8o_d, q8o_sp = _repeat(
            lambda: (lambda d: (
                d["gpt_medium_bf16_dp_q8_off_tokens_per_sec"], d))(
                _bench_gpt_dp_q8(quant=False))
        )
        extra.update(q8o_d)
        extra["gpt_medium_bf16_dp_q8_off_tokens_per_sec_spread"] = q8o_sp

    # int8-moment pair (ISSUE 19): AdamW with quantized moment state vs
    # wide f32 moments, single-mesh — the dequant/requant overhead and
    # the resident-byte win land under the gate / report-only split
    _, q8m_d, q8m_sp = _repeat(
        lambda: (lambda d: (
            d["gpt_medium_bf16_q8m_tokens_per_sec"], d))(
            _bench_gpt_q8m(quant=True))
    )
    extra.update(q8m_d)
    extra["gpt_medium_bf16_q8m_tokens_per_sec_spread"] = q8m_sp
    _, q8mo_d, q8mo_sp = _repeat(
        lambda: (lambda d: (
            d["gpt_medium_bf16_q8m_off_tokens_per_sec"], d))(
            _bench_gpt_q8m(quant=False))
    )
    extra.update(q8mo_d)
    extra["gpt_medium_bf16_q8m_off_tokens_per_sec_spread"] = q8mo_sp

    if jax.default_backend() == "tpu":  # compiled pallas is TPU-only
        # single-shot by design: 500 iterations already run inside ONE
        # dispatched lax.scan, so the device time is self-averaged
        extra.update(_bench_flash_attention())

    # serving bench (ISSUE 9): decode tokens/sec at batch 1/8/64 +
    # batch-1 per-token p50/p99 and prefill cost over the compiled
    # PrefillStep/DecodeStep pair. Median-of-REPEATS like every other
    # metric; the throughput/latency keys land under the continuity
    # gate. PADDLE_BENCH_SERVE=0 skips (the decode sweep adds minutes
    # on a CPU smoke run).
    if os.environ.get("PADDLE_BENCH_SERVE", "1") not in ("0", "false"):
        serve_tok, serve_bd, serve_sp = _repeat(
            lambda: (lambda d: (
                d["serve_gpt_medium_tokens_per_sec_b8"], d))(
                _bench_decode())
        )
        extra.update(serve_bd)
        extra["serve_gpt_medium_tokens_per_sec_b8_spread"] = serve_sp
        # production tier (ISSUE 13): paged-KV throughput next to the
        # contiguous b8 key, TTFT under chunked prefill, and the KV
        # HBM byte pair (paged pool vs worst-case reservation) —
        # throughput/_ms keys gated, byte extras report-only
        pg_tok, pg_bd, pg_sp = _repeat(
            lambda: (lambda d: (
                d["serve_gpt_medium_tokens_per_sec_b8_paged"], d))(
                _bench_decode_paged())
        )
        extra.update(pg_bd)
        extra["serve_gpt_medium_tokens_per_sec_b8_paged_spread"] = pg_sp
        # fault-tolerant serving plane (ISSUE 15): host-kill -> first
        # post-failover token on a survivor, jax-free control-plane
        # workers; recovery_ms gated (lower-better), tokens_lost
        # asserted 0 inside the bench itself
        fo_ms, fo_bd, fo_sp = _repeat(
            lambda: (lambda d: (
                d["serve_failover_recovery_ms"], d))(
                _bench_serve_failover())
        )
        extra.update(fo_bd)
        extra["serve_failover_recovery_ms_spread"] = fo_sp
        # KV block migration plane (ISSUE 17): the recompute-free twin
        # of the key above — drain-triggered extract->blob->splice
        # recovery; gated next to the re-prefill number so the fast
        # path staying fast IS a continuity invariant. bytes/blocks
        # moved ride report-only
        mg_ms, mg_bd, mg_sp = _repeat(
            lambda: (lambda d: (
                d["serve_failover_recovery_ms_migrate"], d))(
                _bench_serve_failover_migrate())
        )
        extra.update(mg_bd)
        extra["serve_failover_recovery_ms_migrate_spread"] = mg_sp
        # train-serve co-tenancy (ISSUE 16): burst tokens shed with the
        # fleet controller off vs on (report-only pair) and the
        # begin->commit cost of the lend transition (gated _ms key)
        ctl_ms, ctl_bd, ctl_sp = _repeat(
            lambda: (lambda d: (d["ctl_lend_ms"], d))(_bench_ctl())
        )
        extra.update(ctl_bd)
        extra["ctl_lend_ms_spread"] = ctl_sp
        # live lend plane (ISSUE 20): lend-commit -> first served token
        # over a real launcher cycle (gated _ms key); the reclaim
        # ladder's wall time rides report-only (drain depth varies)
        cl_ms, cl_bd, cl_sp = _repeat(
            lambda: (lambda d: (d["ctl_live_lend_ms"], d))(
                _bench_ctl_live())
        )
        extra.update(cl_bd)
        extra["ctl_live_lend_ms_spread"] = cl_sp
        # multi-tenant serving plane (ISSUE 18): warm-prefix TTFT and
        # the disaggregated decode-tier throughput land under the gate
        # (_ms lower-better / per_sec higher-better); the prefix hit
        # rate and resident-adapter count ride report-only
        mt_ms, mt_bd, mt_sp = _repeat(
            lambda: (lambda d: (
                d["serve_gpt_medium_ttft_ms_prefix_warm"], d))(
                _bench_serve_multitenant())
        )
        extra.update(mt_bd)
        extra["serve_gpt_medium_ttft_ms_prefix_warm_spread"] = mt_sp
        # int8-checkpoint decode (ISSUE 19): weights load narrow from a
        # save_quantized checkpoint and the compiled decode streams
        # int8 bytes + scales from HBM — b1/b8 tokens/sec next to the
        # full-width serve keys, checkpoint load time gated, on-disk
        # byte accounting report-only
        qw_tok, qw_bd, qw_sp = _repeat(
            lambda: (lambda d: (
                d["serve_gpt_medium_tokens_per_sec_b8_q8w"], d))(
                _bench_decode_q8w())
        )
        extra.update(qw_bd)
        extra["serve_gpt_medium_tokens_per_sec_b8_q8w_spread"] = qw_sp
    # r04 measured the same model/optimizer at batch 64 with two-pass
    # f32-blacklisted batch norm: 41.78 ms / 64 imgs = 1531.7 imgs/sec
    extra["vs_r04_resnet50_bf16"] = round(r50_bf16_ips / 1531.7, 2)
    # recompile-ledger totals (ISSUE 8): jit cache misses this process
    # observed across every benched step object — compile-count drift is
    # reported (never gated) by tools/bench_continuity.py next to the
    # compile-time table
    from paddle_tpu.observability import ledger as _ledger

    extra["compile_count"] = _ledger.compile_count()
    extra["incomparable_to_prev"] = (
        f"r06 methodology change: every metric is now the MEDIAN of "
        f"{REPEATS} repeats with min/max spread recorded per metric "
        f"(*_spread keys); r01-r05 numbers were single-shot on a "
        f"shared chip, so cross-round deltas within the recorded "
        f"spread are noise, not regressions. gpt_medium_bf16_* now "
        f"measures the overhauled decoder default (flash attention "
        f"auto-routed, Pallas fused LayerNorm, blockwise vocab CE — "
        f"tools/PERF.md GPT chapter); the r05-equivalent dense "
        f"configuration records under gpt_medium_bf16_*_dense with "
        f"PADDLE_BENCH_GPT_DENSE=1. Other model/optimizer/batch configs "
        f"are unchanged from r05."
    )
    extra["note"] = (
        "TrainStep hot path (fused fwd+bwd+opt, donated, device-staged "
        "inputs; devget barriers). "
        "Round-5 ResNet work (tools/PERF.md): one-pass f32 BN "
        "stats applied in bf16 (scale+shift form, batch_norm off the amp "
        "black list) + batch 256; framework step now matches a "
        "hand-written pure-JAX step within 1.5% — the residual vs MXU "
        "peak is this chip's reduction/VPU throughput (per-op table in "
        "PERF.md). compile_s values are warm-cache (persistent XLA "
        "compilation cache, core/compile_cache.py)."
    )

    print(
        json.dumps(
            {
                "metric": "resnet50_bf16_train_imgs_per_sec",
                "value": round(r50_bf16_ips, 1),
                "unit": "imgs/sec",
                "vs_baseline": 1.0,
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()
