"""The program names itself in a trace (ISSUE 26).

(a) every compiled step's module is ``jit_<ledger label>``;
(b) ``profiler.phase`` spans of ``InferenceEngine.turn`` and ``TrainStep``
    land in an open ``jax.profiler`` session as flat siblings, and cost
    nothing visible without one;
(c) ``ledger.compile_seconds()`` and ``paddle_tpu.import_seconds``;
(d) the Pallas kernels compile for a described v5e at ``gpt2-medium``'s
    widths under their stable names (on-chip-measurement guide §2,
    rehearsal 3: compiled, never run).
"""
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler
from paddle_tpu.distributed import comm
from paddle_tpu.jit import TrainStep
from paddle_tpu.observability import ledger
from paddle_tpu.serving import InferenceEngine, Request, TransformerLM

ENGINE_PHASES = (
    "engine.prefill_chunk", "engine.admit", "engine.slot_cache",
    "engine.prefill", "engine.first_token", "engine.insert",
    "engine.first_token_read", "engine.decode_dispatch", "engine.readback",
    "engine.collect", "engine.turn_tail")
PER_REQUEST = ENGINE_PHASES[:7]
TRAIN_PHASES = ("TrainStep.prepare", "TrainStep.dispatch", "TrainStep.rebind")


@pytest.fixture(autouse=True, scope="module")
def _no_mesh():
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    yield
    comm._state.hybrid_mesh = prev


def _toy_lm(vocab=48, cap=32):
    m = TransformerLM(vocab, d_model=32, num_heads=4, num_layers=2,
                      max_position=cap)
    m.eval()
    return m


def _toy_train_step():
    lm = _toy_lm()
    lm.train()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=lm.parameters())

    def loss_fn(logits, labels):
        return nn.functional.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))

    return TrainStep(lm, loss_fn, opt)


def _train_batch(seed=0):
    ids = np.random.default_rng(seed).integers(0, 48, size=(2, 9))
    return (paddle.to_tensor(ids[:, :-1].astype(np.int64)),
            paddle.to_tensor(ids[:, 1:].astype(np.int64)))


def _serve(engine, lens=((5, 3), (9, 10), (20, 6)), seed=0):
    """Admissions (the 20-token prompt goes through the chunked path
    when the engine has a `prefill_chunk` of 8), a request that finishes
    early, and at least three turns."""
    rng = np.random.default_rng(seed)
    for n, new in lens:
        engine.submit(Request(rng.integers(0, 48, size=n),
                              max_new_tokens=new))
    results, turns = {}, 1
    while engine.turn(results):
        turns += 1
    assert turns >= 3 and len(results) == len(lens)
    return results


# -- (a) module names ------------------------------------------------------


@pytest.fixture(scope="module")
def lowered_names():
    """{ledger label: name of the module its first call lowers to}."""
    seen = {}
    call = ledger.LedgeredFunction.__call__

    def spy(self, *args, **kwargs):
        if self.label not in seen:
            text = self.lower(*args, **kwargs).as_text()
            seen[self.label] = re.search(r"module @(\S+)", text).group(1)
        return call(self, *args, **kwargs)

    ledger.LedgeredFunction.__call__ = spy
    try:
        _toy_train_step()(*_train_batch())
        _serve(InferenceEngine(_toy_lm(), slots=2, max_length=32,
                               sync_every=4))
    finally:
        ledger.LedgeredFunction.__call__ = call
    return seen


@pytest.mark.parametrize(
    "label", ["TrainStep", "DecodeStep", "PrefillStep", "CacheInsert",
              "SlotCache"])
def test_lowered_module_bears_the_ledger_label(lowered_names, label):
    assert lowered_names[label] == f"jit_{label}", lowered_names


# -- (b) phases ------------------------------------------------------------


def _thread_events(trace_dir):
    """(name, start, end, stats) of the host thread that holds the
    program's phases, read back as `benchmarks/trace_reduce` reads it."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    for line in host.lines:
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                {k: v for k, v in e.stats})
               for e in line.events]
        if any(n.startswith("engine.") for n, *_ in evs):
            return evs
    return []


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The host thread's events of a toy engine run and three train steps
    under a `jax.profiler` session, the Python tracer off as the
    benchmark's harness sets it."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    engine = InferenceEngine(_toy_lm(), slots=2, max_length=32,
                             sync_every=4, prefill_chunk=8)
    step = _toy_train_step()
    _serve(engine)                      # compile outside the session
    step(*_train_batch())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        _serve(engine, seed=1)
        for i in range(3):
            step(*_train_batch(i))
    finally:
        jax.profiler.stop_trace()
    return _thread_events(trace_dir)


def test_every_phase_is_in_the_trace(traced):
    names = [n for n, *_ in traced]
    for phase in ENGINE_PHASES:
        assert phase in names, (phase, sorted(set(names)))
    for phase in TRAIN_PHASES:
        assert names.count(phase) == 3, (phase, names.count(phase))
    # the jitted steps are dispatched inside their phases, by name
    assert any("DecodeStep" in n for n in names)
    assert not any("_step_fn" in n or "_insert_fn" in n for n in names)


def test_phases_are_flat_siblings(traced):
    """No phase of the program holds or cuts another: `host_label` names
    a gap by the outermost span under the harness's, which has to be the
    one phase the host was in."""
    spans = sorted((s, e, n) for n, s, e, _ in traced
                   if n.startswith(("engine.", "TrainStep.")))
    assert len(spans) > 20
    for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
        assert end <= start, (a, b)


def test_request_travels_as_stats(traced):
    seen = 0
    for n, _, _, stats in traced:
        if n in PER_REQUEST:
            assert "rid" in stats and "slot" in stats, (n, stats)
            seen += 1
    assert seen >= 7


def test_no_session_no_record():
    profiler.reset_profiler()
    engine = InferenceEngine(_toy_lm(), slots=2, max_length=32,
                             sync_every=4, prefill_chunk=8)
    _serve(engine)
    _toy_train_step()(*_train_batch())
    assert not profiler.is_profiling()
    assert profiler.event_summary() == {}
    # and `RecordEvent` takes its annotation from `phase` once it is on
    profiler.start_profiler()
    try:
        with profiler.RecordEvent("named"):
            pass
    finally:
        summary = profiler.stop_profiler()
    assert list(summary) == ["named"]
    profiler.reset_profiler()


# -- (c) counters ----------------------------------------------------------


def test_compile_seconds_grow_on_a_miss_only():
    f = ledger.jit(lambda x: x * 2 + 1, "Toy")
    before = ledger.compile_seconds(), ledger.compile_count()
    f(jnp.ones((3,)))
    missed = ledger.compile_seconds(), ledger.compile_count()
    assert missed[1] == before[1] + 1 and missed[0] > before[0]
    f(jnp.ones((3,)))
    assert (ledger.compile_seconds(), ledger.compile_count()) == missed
    f(jnp.ones((4,)))
    assert ledger.compile_seconds() > missed[0]
    assert f.lower(jnp.ones((3,))).as_text().startswith("module @jit_Toy")


def test_import_seconds():
    assert paddle.import_seconds > 0


# -- (d) kernel names at the real widths, compiled for a described chip -----


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(fn, *avals):
    """Names of the Mosaic custom calls in `fn` compiled for the chip
    (conftest's `force_cpu_devices` keeps the persistent compile cache
    off, which could not read such an entry back)."""
    text = jax.jit(fn).lower(*avals).compile().as_text()
    return set(re.findall(r"%([\w.]+?)(?:\.\d+)? = [^\n]*custom-call\([^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"", text))


def _aval(sharding, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_kernel_names_compiled_for_v5e(one_chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    # gpt2-medium.train: 8 x 16 heads x 1,024 x 64, bf16
    qkv = _aval(one_chip, 8, 16, 1024, 64)

    def attn(q, k, v):
        with profiler.device_annotation("attention::flash"):
            return flash_attention(q, k, v, True, 256, 256, None, False,
                                   0, 0).astype(jnp.float32).sum()

    got = _custom_calls(jax.grad(attn, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert got == {"flash_fwd", "flash_dq", "flash_dkv"}, got


@pytest.mark.parametrize("seq,dtype", [
    (1024, jnp.bfloat16),   # gpt2-medium.train, at the routed tile
    (1024, jnp.float32),
    (192, jnp.bfloat16),    # 64-row tiles: under a lane's 128
], ids=["1024-bf16", "1024-f32", "192-bf16"])
def test_flash_kernels_compile_for_v5e_at_routed_tiles(one_chip, seq, dtype):
    """Mosaic takes the three kernels at the tile `_flash_block` routes,
    under the suite's ambient `highest` matmul precision."""
    from paddle_tpu.nn.functional.attention import _flash_block
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    qkv = _aval(one_chip, 2, 16, seq, 64, dtype=dtype)
    blk = _flash_block(seq)

    def attn(q, k, v):
        return flash_attention(q, k, v, True, blk, blk, None, False,
                               0, 0).astype(jnp.float32).sum()

    got = _custom_calls(jax.grad(attn, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert len(got) == 3, got


def test_layer_norm_kernel_names_compiled_for_v5e(one_chip):
    from paddle_tpu.ops.pallas.layer_norm import (fused_add_layer_norm,
                                                  fused_layer_norm)

    # gpt2-medium.train: 8 x 1,024 rows of width 1,024, bf16
    x = _aval(one_chip, 8, 1024, 1024)
    w = _aval(one_chip, 1024, dtype=jnp.float32)

    def ln(x, y, w, b):
        with profiler.device_annotation("layer_norm::fused"):
            h = fused_layer_norm(x, w, b, 1e-5, False)
        with profiler.device_annotation("layer_norm::fused_residual"):
            s, out = fused_add_layer_norm(h, y, w, b, 1e-5, False)
        return (s.astype(jnp.float32) + out.astype(jnp.float32)).sum()

    got = _custom_calls(jax.grad(ln, argnums=(0, 1, 2, 3)), x, x, w, w)
    assert got == {"ln_fwd", "ln_bwd", "ln_residual_fwd"}, got


def test_smoke_counts_the_kernels_lowered_for_v5e(one_chip):
    """`chip_smoke.py` finds each kernel in a program lowered for the
    chip by the name its `pallas_call` carries: one flash forward, dq and
    dk/dv, and for a LayerNorm and an add-LayerNorm two forwards and two
    backwards."""
    import chip_smoke
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.layer_norm import (fused_add_layer_norm,
                                                  fused_layer_norm)

    qkv = _aval(one_chip, 2, 16, 1024, 64)
    x = _aval(one_chip, 2048, 1024)
    w = _aval(one_chip, 1024, dtype=jnp.float32)

    def block(q, k, v, x, y, w, b):
        o = flash_attention(q, k, v, True, 256, 256, None, False, 0, 0)
        s, out = fused_add_layer_norm(
            fused_layer_norm(x, w, b, 1e-5, False), y, w, b, 1e-5, False)
        return sum(t.astype(jnp.float32).sum() for t in (o, s, out))

    text = jax.jit(jax.grad(block, argnums=tuple(range(7)))).lower(
        qkv, qkv, qkv, x, x, w, w).as_text()
    assert chip_smoke._mosaic_calls(text) == (7, {
        "flash fwd": 1, "flash dq": 1, "flash dk/dv": 1,
        "LN / add-LN fwd": 2, "LN bwd": 2})


def _decode_shaped_program(one_chip, monkeypatch, B, H, dtype, layers=2,
                           cap=1024, D=64):
    """What a `DecodeStep` does to its caches, a layer at a time (K and V
    rows appended at `pos`, then the one query row a slot attends),
    compiled for the described chip with the caches donated; with it, how
    many `kv_append` and `decode_attention` kernels the trace counted.
    (The route asks `jax.default_backend()`, the CPU here: the test
    answers for it.)"""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.functional import attention as attn_route
    from paddle_tpu.observability.metrics import (cached_attention_routes,
                                                  kv_append_routes)

    monkeypatch.setattr(attn_route, "_lane_cache_route", lambda c, u: False)
    T = Tensor._wrap

    def step(caches, q, u, pos):
        x, out = q, []
        for k, v in caches:
            k = attn_route.cache_update(T(k), T(u + x), T(pos))
            v = attn_route.cache_update(T(v), T(u - x), T(pos))
            x = x + attn_route.cached_attention(T(q + x), k, v, T(pos))._data
            out.append((k._data, v._data))
        return out, x

    cache = _aval(one_chip, B, H, cap, D, dtype=dtype)
    row = _aval(one_chip, B, H, 1, D, dtype=dtype)
    before = kv_append_routes()["kernel"], cached_attention_routes()["kernel"]
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        [(cache, cache)] * layers, row, row,
        _aval(one_chip, B, dtype=jnp.int32)).compile()
    return compiled, (kv_append_routes()["kernel"] - before[0],
                      cached_attention_routes()["kernel"] - before[1])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kv_append_stays_in_place_compiled_for_v5e(one_chip, dtype,
                                                   monkeypatch):
    """A `DecodeStep`-shaped append + attention at the chat cell's cache
    (32 slots x 16 heads x 1,024 x 64, two layers, donated): one
    `kv_append` custom call a cache tensor on the `[B, H, D, cap]` view,
    which has to stay a bitcast of what the chip stores: no `while`, no
    `dynamic-update-slice`, no temporary the size of a cache tensor, and
    every cache aliased in to out."""
    B, H, cap, D, layers = 32, 16, 1024, 64, 2
    compiled, (appends, _) = _decode_shaped_program(
        one_chip, monkeypatch, B, H, dtype)
    assert appends == 2 * layers
    text = compiled.as_text()
    calls = re.findall(r"= ([^\n]*?) custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                       r"kv_append", text)
    assert len(calls) == 2 * layers, calls
    view = f"[{B},{H},{D},{cap}]{{3,2,1,0:"
    assert all(view in c for c in calls), calls
    assert " while(" not in text and "dynamic-update-slice" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == (
        2 * layers * B * H * cap * D * jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("B,H", [(32, 16), (16, 20)], ids=["chat", "batch"])
def test_decode_attention_reads_the_stored_view_compiled_for_v5e(
        one_chip, B, H, monkeypatch):
    """A `DecodeStep`-shaped append + attention at the chat and the batch
    cell's caches (1,024 x 64 float32, two layers, donated): one
    `decode_attention` custom call a layer, its K and V operands the
    `[B, H, D, cap]` view of what `kv_append` wrote (a bitcast, no
    copy), no temporary the size of a cache tensor, every cache still
    aliased in to out, and nothing left in the program that touches a
    whole cache tensor but parameters, bitcasts, the result's tuple and
    the two kernels: the dense read of the capacity is gone."""
    cap, D, layers = 1024, 64, 2
    compiled, counted = _decode_shaped_program(
        one_chip, monkeypatch, B, H, jnp.float32)
    assert counted == (2 * layers, layers)
    text = compiled.as_text()
    reads = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "decode_attention" in line]
    assert len(reads) == layers, reads
    view = f"f32[{B},{H},{D},{cap}]{{3,2,1,0}}"
    for line in reads:
        # pos, q, then K and V once as the first tile's block and once whole
        assert line.count(view) == 4, line
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        assert operands.count("%kv_append") == 4, operands
    stored, seen = f"[{B},{H},{cap},{D}]", f"[{B},{H},{D},{cap}]"
    for line in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(", line)
        if made is None or not (stored in line or seen in line):
            continue
        assert made.group(1) in ("parameter", "bitcast", "tuple") or (
            made.group(1) == "custom-call"
            and re.search(r"kv_append|decode_attention", line)), line
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == 2 * layers * B * H * cap * D * 4


def _fused_decode_program(one_chip, monkeypatch, B, H, dtype, layers=2,
                          cap=1024, D=64, live=False):
    """What a `DecodeStep` does to its caches through the one seam it
    calls, `cached_append_attention` (the K and V rows written at `pos`
    and the one query row a slot attended, a layer at a time; with
    `live`, the step's [B] mask of the slots that hold a request handed
    on), compiled for the described chip with the caches donated; with
    it, how the trace counted the writes and the reads. (The route asks
    `jax.default_backend()`, the CPU here: the test answers for it.)"""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.functional import attention as attn_route
    from paddle_tpu.observability.metrics import (cached_attention_routes,
                                                  kv_append_routes)

    monkeypatch.setattr(attn_route, "_lane_cache_route", lambda c, u: False)
    T = Tensor._wrap

    def step(caches, q, u, pos, *mask):
        x, out = q, []
        for k, v in caches:
            o, k, v = attn_route.cached_append_attention(
                T(q + x), T(k), T(v), T(u + x), T(u - x), T(pos),
                live=T(mask[0]) if mask else None)
            x = x + o._data
            out.append((k._data, v._data))
        return out, x

    cache = _aval(one_chip, B, H, cap, D, dtype=dtype)
    row = _aval(one_chip, B, H, 1, D, dtype=dtype)
    mask = (_aval(one_chip, B, dtype=jnp.bool_),) if live else ()
    w0, r0 = kv_append_routes(), cached_attention_routes()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        [(cache, cache)] * layers, row, row,
        _aval(one_chip, B, dtype=jnp.int32), *mask).compile()
    w1, r1 = kv_append_routes(), cached_attention_routes()
    return compiled, ({r: w1[r] - w0[r] for r in w1},
                      {r: r1[r] - r0[r] for r in r1})


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B,H", [(32, 16), (16, 20)], ids=["chat", "batch"])
def test_decode_append_attention_writes_in_place_compiled_for_v5e(
        one_chip, B, H, dtype, monkeypatch):
    """The decode step's cache work through `cached_append_attention` at
    the chat and the batch cell's caches (1,024 x 64, two layers,
    donated): one `decode_append_attention` custom call a layer and no
    `kv_append`, each cache tensor its operand once, as the `[B, H, D,
    cap]` view (a bitcast), and aliased in to out; no copy of a `[B, H,
    D, 1]` row, no temporary the size of a cache tensor, and nothing in
    the program that touches a whole cache tensor but parameters,
    bitcasts, the result's tuple and the kernel."""
    cap, D, layers = 1024, 64, 2
    compiled, (writes, reads) = _fused_decode_program(
        one_chip, monkeypatch, B, H, dtype)
    assert writes == {"kernel": 0, "scatter": 0, "fused": 2 * layers}
    assert reads == {"kernel": layers, "dense": 0}
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == layers, calls
    assert all("decode_append_attention" in c and "kv_append" not in c
               for c in calls), calls
    name = jnp.dtype(dtype).name.replace("float", "f")
    view = f"{name}[{B},{H},{D},{cap}]{{3,2,1,0"
    for line in calls:
        # the result (out, K, V) and the operands K and V, once each
        assert line.count(view) == 4, line
    for line in calls:
        # the query and the new rows come as the projection gives them
        # ([B, H, D]): no launch of its own lays a row out for the kernel
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        assert not re.search(r"%copy(\.\d+)?(,|$)", operands), operands
    stored, seen = f"[{B},{H},{cap},{D}]", f"[{B},{H},{D},{cap}]"
    for line in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(", line)
        if made is None or not (stored in line or seen in line):
            continue
        assert made.group(1) in ("parameter", "bitcast", "tuple",
                                 "get-tuple-element") or (
            made.group(1) == "custom-call"
            and "decode_append_attention" in line), line
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == (
        2 * layers * B * H * cap * D * jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B,H", [(32, 16), (16, 20)], ids=["chat", "batch"])
def test_masked_decode_append_attention_compiled_for_v5e(
        one_chip, B, H, dtype, monkeypatch):
    """The decode step's cache work with the step's live mask handed on,
    at the chat and the batch cell's caches: still one
    `decode_append_attention` custom call a layer and no `kv_append`, its
    scalar operands `pos` and the mask (two, where the unmasked call has
    one: one program computes both for every layer), K and V aliased in
    to out at their places after them, every cache aliased, no temporary
    the size of a cache tensor; the trace counts one masked kernel a
    layer."""
    from paddle_tpu.observability.metrics import masked_kernels

    cap, D, layers = 1024, 64, 2
    before = masked_kernels()
    compiled, (writes, reads) = _fused_decode_program(
        one_chip, monkeypatch, B, H, dtype, live=True)
    assert masked_kernels() - before == layers
    assert writes == {"kernel": 0, "scatter": 0, "fused": 2 * layers}
    assert reads == {"kernel": layers, "dense": 0}
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == layers, calls
    name = jnp.dtype(dtype).name.replace("float", "f")
    view = f"{name}[{B},{H},{D},{cap}]{{3,2,1,0"
    for line in calls:
        assert "decode_append_attention" in line and "kv_append" not in line
        assert line.count(view) == 4, line
        layout = re.search(r"operand_layout_constraints=\{(.*?)\}, "
                           r"output_to_operand_aliasing", line).group(1)
        assert layout.startswith(f"s32[{B}]{{0}}, s32[{B}]{{0}}, ")
        assert layout.count(f"s32[{B}]") == 2, layout
        assert "output_to_operand_aliasing={{1}: (5, {}), {2}: (6, {})}" \
            in line, line
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == (
        2 * layers * B * H * cap * D * jnp.dtype(dtype).itemsize)


# -- what the live mask leaves as it was -------------------------------------

#: sha-256 of the lowered text of each family's `PrefillStep` and
#: `DecodeStep` at the toy sizes below, without and with the Pallas
#: interpreter forced, as the program gave them before the decode step
#: handed its live mask on. GPT-2's `DecodeStep` in the interpreter takes
#: the masked kernel and is left out: every other program is the same.
UNMASKED_STEPS = {
    ("gpt2", "PrefillStep", "cpu"):
        "e59435b9090777e5203a94396439beeb114d064cae12902d3baffc4e66d17f0c",
    ("gpt2", "DecodeStep", "cpu"):
        "ed1fb97c9492089857f1c0d46b6fa597765fd5e84a6be4c1b9c99e2e763521b9",
    ("gpt2", "PrefillStep", "interpret"):
        "e59435b9090777e5203a94396439beeb114d064cae12902d3baffc4e66d17f0c",
    ("sarvam", "PrefillStep", "cpu"):
        "acff77143d3c78b1b91a76fa49749e6f02c5e0021dd83de28462c0028c603235",
    ("sarvam", "DecodeStep", "cpu"):
        "b5fe18fc700b8eb583f56b8310846e7783af4185f1481d71fa5a383b9a9704fb",
    ("sarvam", "PrefillStep", "interpret"):
        "acff77143d3c78b1b91a76fa49749e6f02c5e0021dd83de28462c0028c603235",
    ("sarvam", "DecodeStep", "interpret"):
        "b5fe18fc700b8eb583f56b8310846e7783af4185f1481d71fa5a383b9a9704fb",
    ("keye", "PrefillStep", "cpu"):
        "6437cb731a19489467e2b4d5dbe96891fc9c5a9fc36859c2bad689a0a7dda272",
    ("keye", "DecodeStep", "cpu"):
        "88ec3eb936f59dbb8cae1391821f85707962729447623f8705d8834ab8879601",
    ("keye", "PrefillStep", "interpret"):
        "6437cb731a19489467e2b4d5dbe96891fc9c5a9fc36859c2bad689a0a7dda272",
    ("keye", "DecodeStep", "interpret"):
        "88ec3eb936f59dbb8cae1391821f85707962729447623f8705d8834ab8879601",
    ("granite", "PrefillStep", "cpu"):
        "f1f0f77c5873369d04bea97e55186b67056ceb96b50fae013fde64c671910c05",
    ("granite", "DecodeStep", "cpu"):
        "7aa467350103169d982fa3b9a4fab0c40f588991bc6523e00ec76460eb0100d1",
    ("granite", "PrefillStep", "interpret"):
        "f1f0f77c5873369d04bea97e55186b67056ceb96b50fae013fde64c671910c05",
    ("granite", "DecodeStep", "interpret"):
        "0f55a545f23b96a38c6404f73af610c7be1c4e4c3aaef588b78e67684c201e83",
}


def _step_texts(build, slots=2, cap=128, prompt=16):
    """{label: sha-256 of the lowered text} of a `PrefillStep` over a
    batch of `prompt`-token prompts and a `DecodeStep` after it, each
    lowered where it is first called."""
    import hashlib

    from paddle_tpu.jit import DecodeState, DecodeStep, PrefillStep

    comm._state.hybrid_mesh = None
    paddle.seed(5)
    lm = build()
    lm.eval()
    texts = {}
    call = ledger.LedgeredFunction.__call__

    def spy(self, *args, **kwargs):
        if self.label not in texts:
            texts[self.label] = hashlib.sha256(self.lower(
                *args, **kwargs).as_text().encode()).hexdigest()
        return call(self, *args, **kwargs)

    ledger.LedgeredFunction.__call__ = spy
    try:
        ids = np.arange(slots * prompt).reshape(slots, prompt) % 40
        _, cache, pos = PrefillStep(lm)(lm.gen_cache(slots, cap), ids,
                                        [prompt] * slots)
        DecodeStep(lm)(DecodeState.make(
            cache, first_tokens=np.zeros(slots, np.int32), pos=pos))
    finally:
        ledger.LedgeredFunction.__call__ = call
    return texts


def _family(name, config):
    """A builder of a benchmark family's model at its toy size
    (`TOY_CFG`), unloaded."""
    def build():
        import sys

        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks")
        if bench not in sys.path:
            sys.path.insert(0, bench)
        import find
        import weights

        fam = find.load("families", name)
        cfg = dict(weights.load_config(config), **fam.TOY_CFG)
        return sys.modules[fam.__name__ + ".model"].build(
            cfg, dtype="float32")

    return build


FAMILIES = {
    "gpt2": lambda: TransformerLM(48, d_model=32, num_heads=4, num_layers=2,
                                  max_position=128),
    "sarvam": _family("sarvam_mla", "sarvam-105b"),
    "keye": _family("keye_dsa", "keye-vl-2.0-30b-a3b"),
    "granite": _family("granite_hybrid", "granite-4.0-h-micro"),
}


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["cpu", "interpret"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_programs_without_the_mask_are_unchanged(family, interpret,
                                                 monkeypatch):
    """Families that do not declare `takes_live` (sarvam, keye, granite)
    lower the `PrefillStep` and `DecodeStep` they lowered before the
    mask, textually, with the interpreter forced or not; so do GPT-2's
    `PrefillStep` and, on the CPU's dense route, its `DecodeStep`, which
    is handed the mask and takes no notice of it."""
    if interpret:
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    else:
        monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    got = _step_texts(FAMILIES[family])
    mode = "interpret" if interpret else "cpu"
    for label in ("PrefillStep", "DecodeStep"):
        want = UNMASKED_STEPS.get((family, label, mode))
        if family == "gpt2" and label == "DecodeStep" and interpret:
            assert want is None
            continue
        assert got[label] == want, (family, label, mode)
