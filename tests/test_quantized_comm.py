"""Quantized comm plane (ISSUE 10): block-scaled int8/fp8 grad
allreduce over the dcn hop + quantized KV cache.

Covers the ISSUE 10 parity gates on the 8-device CPU mesh:
  - quantize/dequantize round-trip error bounds (per-block scale/2),
  - the wire-true ``quantized_allreduce`` inside a manual shard_map,
  - the DistributedStrategy policy at both grad-comm seams (boundary
    round trip on flat dp; explicit per-grad dcn exchange composed with
    hierarchical_allreduce / async_dcn_allreduce),
  - 8-mesh loss-continuity vs f32 comm + policy-off numerics unchanged,
  - the int8 block-scaled KV cache against the f32 cache through the
    serving seam,
  - zero new per-step host syncs for the byte-accounting telemetry,
  - a slow-marked LeNet convergence parity run.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import comm, fleet
from paddle_tpu.distributed import quantized_comm as qc
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.jit import TrainStep
from paddle_tpu.nn import functional as F

_HAS_FP8 = qc.fp8_dtype() is not None


@pytest.fixture(autouse=True)
def _fresh_mesh():
    """Every test declares its own fleet topology; none may leak the
    process-global routing mesh into its neighbors (the PR 6
    lingering-mesh lesson)."""
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    yield
    comm._state.hybrid_mesh = prev


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


class TestPrimitives:
    def test_int8_round_trip_error_bound(self):
        """|x - dq(q(x))| <= scale/2 per block, scale = block amax/127
        (symmetric round-to-nearest)."""
        x = np.random.RandomState(0).randn(1000).astype(np.float32) * 5
        p, s = qc.quantize_blockwise(jnp.asarray(x), "int8", 128)
        assert p.dtype == jnp.int8 and p.shape == (8, 128)
        assert s.shape == (8,) and s.dtype == jnp.float32
        dq = np.asarray(qc.dequantize_blockwise(p, s, (1000,)))
        scales = np.asarray(s)
        for i in range(1000):
            assert abs(dq[i] - x[i]) <= scales[i // 128] / 2 + 1e-7

    def test_scales_are_per_block_not_per_tensor(self):
        """A tensor mixing a huge and a tiny block keeps the tiny
        block's resolution — THE reason for block scales (EQuARX)."""
        x = np.zeros(256, np.float32)
        x[:128] = np.random.RandomState(1).randn(128) * 1000
        x[128:] = np.random.RandomState(2).randn(128) * 1e-3
        dq = np.asarray(qc.quantize_dequantize(jnp.asarray(x), "int8", 128))
        # per-tensor scaling (scale ~ 1000/127 ~ 8) would zero the small
        # block entirely; per-block scaling resolves it at ITS amax
        small_bound = np.abs(x[128:]).max() / 127 / 2 + 1e-9
        assert np.abs(dq[128:] - x[128:]).max() <= small_bound
        assert np.abs(dq[128:]).max() > 0

    def test_zero_block_and_shape_dtype_preserved(self):
        x = jnp.zeros((4, 33), jnp.float32)
        out = qc.quantize_dequantize(x, "int8", 128)
        assert out.shape == (4, 33) and out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    @pytest.mark.skipif(not _HAS_FP8, reason="no float8_e4m3fn")
    def test_fp8_round_trip(self):
        x = np.random.RandomState(3).randn(512).astype(np.float32)
        p, s = qc.quantize_blockwise(jnp.asarray(x), "fp8", 128)
        assert p.dtype == qc.fp8_dtype()
        dq = np.asarray(qc.dequantize_blockwise(p, s, (512,)))
        # e4m3: 3 mantissa bits -> <= ~6.25% relative per element
        assert np.max(np.abs(dq - x)) <= 0.07 * np.abs(x).max()

    def test_lastaxis_kv_form(self):
        """[B, H, cap, D] layout: payload keeps the cache shape, scales
        ride a parallel per-row-block buffer; D < block falls back to
        one scale per row."""
        k = np.random.RandomState(4).randn(2, 4, 16, 8).astype(np.float32)
        p, s = qc.quantize_lastaxis(jnp.asarray(k), "int8", 128)
        assert p.shape == k.shape and p.dtype == jnp.int8
        assert s.shape == (2, 4, 16, 1)
        dq = np.asarray(qc.dequantize_lastaxis(p, s))
        row_amax = np.abs(k).max(-1, keepdims=True)
        assert np.all(np.abs(dq - k) <= row_amax / 254 + 1e-7)
        # a tiling block width splits the row
        k2 = np.random.RandomState(5).randn(2, 256).astype(np.float32)
        p2, s2 = qc.quantize_lastaxis(jnp.asarray(k2), "int8", 128)
        assert s2.shape == (2, 2)

    def test_wire_accounting(self):
        info = qc.grad_comm_info(368_000_000, ("int8", 128))
        assert info["dtype"] == "int8"
        # payload 1 byte/elem + f32 scale per 128 elems
        assert info["bytes_on_wire"] == 368_000_000 + 4 * 2_875_000
        assert info["bytes_f32"] == 4 * 368_000_000
        assert 3.5 < info["reduction_x"] < 4.0
        bf = qc.grad_comm_info(100, None, fp16_allreduce=True)
        assert bf["dtype"] == "bfloat16" and bf["bytes_on_wire"] == 200
        f32 = qc.grad_comm_info(100, None)
        assert f32["dtype"] == "float32" and f32["reduction_x"] == 1.0

    def test_resolve_policy_is_loud(self):
        assert qc.resolve_policy(None) is None
        assert qc.resolve_policy("int8", 64) == ("int8", 64)
        with pytest.raises(ValueError, match="supported"):
            qc.resolve_policy("int4")
        with pytest.raises(ValueError, match="block"):
            qc.resolve_policy("int8", 0)

    def test_kv_quant_policy_env_is_loud(self, monkeypatch):
        assert qc.kv_quant_policy(None) is None
        assert qc.kv_quant_policy("int8") == "int8"
        assert qc.kv_quant_policy("float32") is None  # a real dtype
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int8")
        assert qc.kv_quant_policy(None) == "int8"
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "0")
        assert qc.kv_quant_policy(None) is None
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int9")
        with pytest.raises(ValueError, match="PADDLE_SERVE_KV_QUANT"):
            qc.kv_quant_policy(None)


class TestQuantizedAllreduce:
    """The wire-true exchange inside a shard_map manual over the axis."""

    def _mesh(self):
        from jax.sharding import Mesh

        devs = jax.devices()
        return Mesh(np.array(devs).reshape(len(devs)), ("dcn",))

    def test_matches_full_width_mean_within_bound(self):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh()
        n = mesh.shape["dcn"]
        x = np.random.RandomState(6).randn(n, 160).astype(np.float32)
        f = comm.shard_map(
            lambda xl: qc.quantized_allreduce(xl, "dcn"),
            mesh, in_specs=P("dcn"), out_specs=P("dcn"),
        )
        out = np.asarray(jax.jit(f)(jnp.asarray(x)))
        ref = x.mean(0)
        # each peer's contribution is quantized once: the mean's error
        # is bounded by the mean of the per-peer block quantization
        # errors (<= amax/254 each)
        bound = np.abs(x).max() / 254 + 1e-6
        for r in range(n):
            np.testing.assert_allclose(out[r], ref, atol=bound)
        # every dcn rank agrees exactly (they reduced identical bytes)
        for r in range(1, n):
            np.testing.assert_array_equal(out[r], out[0])

    def test_dtype_preserved(self):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh()
        n = mesh.shape["dcn"]
        x = jnp.asarray(
            np.random.RandomState(7).randn(n, 64), jnp.bfloat16)
        f = comm.shard_map(
            lambda xl: qc.quantized_allreduce(xl, "dcn"),
            mesh, in_specs=P("dcn"), out_specs=P("dcn"),
        )
        assert jax.jit(f)(x).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the DistributedStrategy policy — boundary round trip (flat dp)
# ---------------------------------------------------------------------------


class _DenseNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(10, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


class TestBoundaryPolicy:
    """strategy.quantized_allreduce on a flat-dp mesh: the grad-comm
    width round trip at the same seam as the bf16 fp16_allreduce
    policy (eager step() AND the TrainStep functional path)."""

    def _train(self, quantized, steps=5):
        paddle.seed(7)
        strategy = DistributedStrategy()
        if quantized:
            strategy.quantized_allreduce = quantized
        fleet.init(is_collective=True, strategy=strategy)
        net = _DenseNet()
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=net.parameters()),
            strategy=strategy,
        )
        x = paddle.to_tensor(
            np.random.RandomState(0).rand(8, 10).astype(np.float32)
        )
        losses = []
        for _ in range(steps):
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses, [p.numpy() for p in net.parameters()]

    def test_eager_parity_vs_f32(self):
        lq, pq = self._train("int8")
        lf, pf = self._train(None)
        assert lq[-1] < lq[0]
        np.testing.assert_allclose(lq, lf, rtol=2e-2, atol=1e-3)
        for a, b in zip(pq, pf):
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=1e-3)

    def test_quant_cast_is_block_width(self):
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int8"
        fleet.init(is_collective=True, strategy=strategy)
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=1.0,
                          parameters=_DenseNet().parameters()),
            strategy=strategy,
        )
        # a block with amax 2: resolution 2/127 — a NON-amax value like
        # 1 + 2^-12 lands back on a code point, not on itself (the amax
        # itself always round-trips exactly: it IS code 127)
        g = jnp.ones((128,), jnp.float32).at[0].set(2.0) \
            .at[1].set(1.0 + 2.0 ** -12)
        out = opt._quant_cast(g)
        assert out.dtype == jnp.float32          # f32 master apply
        assert float(out[0]) == 2.0
        assert float(out[1]) != 1.0 + 2.0 ** -12
        assert abs(float(out[1]) - (1.0 + 2.0 ** -12)) <= 2.0 / 127 / 2
        # non-f32 grads pass through untouched
        h = jnp.asarray(3, jnp.int32)
        assert opt._quant_cast(h) is h
        # no policy -> no width cast
        s2 = DistributedStrategy()
        fleet.init(is_collective=True, strategy=s2)
        opt2 = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=1.0,
                          parameters=_DenseNet().parameters()),
            strategy=s2,
        )
        assert opt2._comm_width_cast() is None

    def test_functional_path_applies_policy(self):
        paddle.seed(7)
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int8"
        fleet.init(is_collective=True, strategy=strategy)
        net = _DenseNet()
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=net.parameters()),
            strategy=strategy,
        )
        step = TrainStep(net, lambda out, y: (out ** 2).mean(), opt)
        assert step._quant_info == ("int8", 128)
        assert step._dcn_quant is None        # flat dp: boundary seam
        assert not opt._quant_explicit
        x = paddle.to_tensor(
            np.random.RandomState(1).rand(8, 10).astype(np.float32))
        y = paddle.to_tensor(np.zeros((8, 4), np.float32))
        first = float(step(x, y).numpy())
        for _ in range(4):
            last = float(step(x, y).numpy())
        assert last < first

    def test_failed_ctor_leaves_boundary_policy_armed(self):
        """A TrainStep ctor that RAISES after electing the explicit dcn
        path must not have disarmed the optimizer's boundary round trip
        — the eager fallback would otherwise silently train full-width
        (review fix)."""
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int8"
        fleet.init(is_collective=True, strategy=strategy)  # FLAT mesh
        strategy.hierarchical_allreduce = True  # set after init: no dcn
        net = _DenseNet()
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        )
        with pytest.raises(ValueError, match="dcn axis"):
            TrainStep(net, lambda o, y: (o ** 2).mean(), opt)
        assert not opt._quant_explicit
        assert opt._comm_width_cast() is not None

    def test_dgc_plus_fp16_names_the_conflict(self):
        strategy = DistributedStrategy()
        strategy.dgc = True
        strategy.fp16_allreduce = True
        fleet.init(is_collective=True, strategy=strategy)
        with pytest.raises(ValueError, match="dgc"):
            fleet.distributed_optimizer(
                optimizer.SGD(learning_rate=0.1,
                              parameters=_DenseNet().parameters())
            )

    def test_two_width_policies_raise(self):
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int8"
        strategy.fp16_allreduce = True
        fleet.init(is_collective=True, strategy=strategy)
        with pytest.raises(ValueError, match="one, not both"):
            fleet.distributed_optimizer(
                optimizer.SGD(learning_rate=0.1,
                              parameters=_DenseNet().parameters())
            )

    def test_unknown_policy_raises(self):
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int4"
        fleet.init(is_collective=True, strategy=strategy)
        with pytest.raises(ValueError, match="supported"):
            fleet.distributed_optimizer(
                optimizer.SGD(learning_rate=0.1,
                              parameters=_DenseNet().parameters())
            )

    def test_localsgd_composition_raises(self):
        strategy = DistributedStrategy()
        strategy.quantized_allreduce = "int8"
        strategy.localsgd = True
        fleet.init(is_collective=True, strategy=strategy)
        net = _DenseNet()
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        )
        with pytest.raises(NotImplementedError, match="localsgd"):
            TrainStep(net, lambda out, y: (out ** 2).mean(), opt)


# ---------------------------------------------------------------------------
# hierarchical composition: dcn quantized, ici full-width (the 8-mesh
# loss-continuity gate)
# ---------------------------------------------------------------------------


class TestHierarchicalQuantized:
    def _train(self, quantized, async_dcn=True, steps=3, seed=21):
        strategy = DistributedStrategy()
        strategy.hierarchical_allreduce = True
        strategy.hierarchical_allreduce_inter_nranks = 2
        strategy.async_dcn_allreduce = async_dcn
        if quantized:
            strategy.quantized_allreduce = quantized
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(seed)
        net = _DenseNet()
        model = fleet.distributed_model(net)
        opt = fleet.distributed_optimizer(
            optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                               parameters=net.parameters())
        )
        step = TrainStep(
            model, lambda out, y: F.cross_entropy(out, y), opt,
        )
        # ONE fixed batch: "the loss falls" is only a sound expectation
        # when every step sees the same data (fresh noise each step made
        # it a property of the seed, not of the optimizer)
        data = np.random.RandomState(4)
        x = model.shard_input(data.rand(16, 10).astype(np.float32))
        y = model.shard_input((np.arange(16) % 4).astype(np.int64))
        losses = [float(step(x, y).numpy()) for _ in range(steps)]
        params = {k: v.numpy().copy() for k, v in net.state_dict().items()}
        return losses, params, step, opt

    def test_explicit_dcn_path_engages(self):
        """quantized + hierarchical routes the step through the
        manual-over-'dcn' seam (wire-true per-grad quantized exchange,
        ici full-width) even WITHOUT async_dcn_allreduce, and the
        optimizer's boundary round trip stands down."""
        _, _, step, opt = self._train("int8", async_dcn=False, steps=1)
        assert step._async_dcn and step._dcn_quant == ("int8", 128)
        assert opt._quant_explicit
        assert opt._comm_width_cast() is None  # no double quantization

    def test_loss_continuity_vs_f32_comm(self):
        """THE ROADMAP parity gate: the 8-mesh (dcn4 x ici2) run with
        the dcn hop quantized tracks the f32-comm run. Documented
        bitwise expectation: NOT bitwise-equal (int8 codes round each
        block to an amax/127 grid — asserted below), but within one
        quantization step per grad per update."""
        lq, pq, _, _ = self._train("int8", async_dcn=True)
        lf, pf, _, _ = self._train(None, async_dcn=True)
        assert lq[-1] < lq[0]
        np.testing.assert_allclose(lq, lf, rtol=2e-2, atol=1e-3)
        assert any(
            not np.array_equal(pq[k], pf[k]) for k in pf
        ), "quantized run bitwise-identical to f32: policy not applied"
        for k in pf:
            np.testing.assert_allclose(
                pq[k], pf[k], rtol=2e-2, atol=1e-3, err_msg=k)

    @pytest.mark.skipif(not _HAS_FP8, reason="no float8_e4m3fn")
    def test_fp8_loss_continuity(self):
        lq, _, step, _ = self._train("fp8", async_dcn=True)
        lf, _, _, _ = self._train(None, async_dcn=True)
        assert step._dcn_quant == ("fp8", 128)
        np.testing.assert_allclose(lq, lf, rtol=5e-2, atol=5e-3)

    def test_policy_off_numerics_unchanged(self):
        """Healthy-step numerics with the policy OFF are bitwise
        reproducible — the quantization plane leaves the default
        program untouched (acceptance criterion)."""
        l1, p1, _, _ = self._train(None, async_dcn=False)
        l2, p2, _, _ = self._train(None, async_dcn=False)
        assert l1 == l2
        for k in p1:
            np.testing.assert_array_equal(p1[k], p2[k], err_msg=k)

    def test_composes_with_parallel_gpt_block(self, monkeypatch):
        """dcn2 x ici2 x mp2 ParallelGPTBlock with the dcn hop
        quantized: the routed hot path (flash/fused-LN decline inside
        the manual region) still traces, trains, and tracks f32 comm."""
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        from paddle_tpu.distributed import ParallelGPTBlock

        def run(quantized):
            strategy = DistributedStrategy()
            strategy.hierarchical_allreduce = True
            strategy.hierarchical_allreduce_inter_nranks = 2
            strategy.async_dcn_allreduce = True
            if quantized:
                strategy.quantized_allreduce = "int8"
            strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
            fleet.init(is_collective=True, strategy=strategy)
            paddle.seed(33)
            net = ParallelGPTBlock(16, 4, dropout=0.0)
            model = fleet.distributed_model(net)
            opt = fleet.distributed_optimizer(
                optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                   parameters=net.parameters())
            )
            step = TrainStep(
                model,
                lambda out, y: F.cross_entropy(out.mean(axis=1), y), opt,
            )
            data = np.random.RandomState(9)
            losses = []
            for _ in range(2):
                x = model.shard_input(
                    data.rand(8, 32, 16).astype(np.float32))
                y = model.shard_input((np.arange(8) % 4).astype(np.int64))
                losses.append(float(step(x, y).numpy()))
            comm._state.hybrid_mesh = None
            return losses

        lq = run(True)
        lf = run(False)
        np.testing.assert_allclose(lq, lf, rtol=2e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# quantized KV cache (serving seam)
# ---------------------------------------------------------------------------


class TestKVCacheQuant:
    def test_cached_attention_equals_dense_on_dequantized(self):
        """Seam exactness: attention over a QuantKV cache IS the dense
        cached_attention over the dequantized buffers (same ops, no
        approximation beyond the quantizer itself)."""
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.nn.functional import attention as attn

        rng = np.random.RandomState(11)
        B, H, cap, D = 2, 4, 16, 8
        q = Tensor._wrap(jnp.asarray(rng.randn(B, H, 1, D), jnp.float32))
        k = jnp.asarray(rng.randn(B, H, cap, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, cap, D), jnp.float32)
        pos = Tensor._wrap(jnp.full((B,), 7, jnp.int32))
        kq, ks = qc.quantize_lastaxis(k, "int8")
        vq, vs = qc.quantize_lastaxis(v, "int8")
        quant = attn.cached_attention(
            q,
            qc.QuantKV(Tensor._wrap(kq), Tensor._wrap(ks)),
            qc.QuantKV(Tensor._wrap(vq), Tensor._wrap(vs)),
            pos,
        )
        dense = attn.cached_attention(
            q,
            Tensor._wrap(qc.dequantize_lastaxis(kq, ks)),
            Tensor._wrap(qc.dequantize_lastaxis(vq, vs)),
            pos,
        )
        np.testing.assert_array_equal(quant.numpy(), dense.numpy())

    def test_gen_cache_layouts(self, monkeypatch):
        from paddle_tpu.serving.model import TransformerLM

        model = TransformerLM(64, d_model=32, num_heads=4, num_layers=2,
                              max_position=32)
        caches = model.gen_cache(2, 16, dtype="int8")
        c0 = caches[0]
        assert isinstance(c0.k, qc.QuantKV)
        assert c0.k.q.dtype == jnp.int8
        assert tuple(c0.k.q.shape) == (2, 4, 16, 8)
        assert tuple(c0.k.scale.shape) == (2, 4, 16, 1)
        # the env knob is the no-code-change path
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int8")
        env_caches = model.gen_cache(2, 16)
        assert isinstance(env_caches[0].k, qc.QuantKV)
        monkeypatch.delenv("PADDLE_SERVE_KV_QUANT")
        f32_caches = model.gen_cache(2, 16)
        assert not isinstance(f32_caches[0].k, qc.QuantKV)
        # single-chip MultiHeadAttention seam carries the same form
        mha = nn.MultiHeadAttention(32, 4)
        c = mha.gen_cache(batch_size=2, max_length=16, dtype="int8")
        assert isinstance(c.k, qc.QuantKV)
        with pytest.raises(ValueError, match="static-capacity"):
            mha.gen_cache(batch_size=2, dtype="int8")
        # the env default must NOT break a legacy concat-cache caller
        # that never opted in (no max_length, no dtype — review fix)
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int8")
        legacy = mha.gen_cache(batch_size=2)
        assert not isinstance(legacy.k, qc.QuantKV)
        assert tuple(legacy.k.shape)[2] == 0  # zero-length concat form
        monkeypatch.delenv("PADDLE_SERVE_KV_QUANT")

    def test_decode_parity_vs_f32_cache(self, monkeypatch):
        """ROADMAP item-1(b) seam: generate() with the int8 cache
        tracks the f32-cache run — same greedy decode, logits within
        the quantizer's error budget."""
        from paddle_tpu.serving import generate
        from paddle_tpu.serving.model import TransformerLM

        paddle.seed(5)
        model = TransformerLM(64, d_model=32, num_heads=4, num_layers=2,
                              max_position=64)
        prompts = (np.arange(2 * 12) % 60).reshape(2, 12).astype(np.int32)

        toks_f32, log_f32 = generate(
            model, prompts, 6, max_length=32, return_logits=True)
        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int8")
        toks_q8, log_q8 = generate(
            model, prompts, 6, max_length=32, return_logits=True)

        assert np.max(np.abs(log_q8 - log_f32)) < 0.25
        # greedy argmax agrees on the overwhelming majority of steps
        agree = (toks_q8 == toks_f32).mean()
        assert agree >= 0.8, f"only {agree:.0%} of greedy tokens agree"

    def test_engine_runs_quantized(self, monkeypatch):
        """The continuous-batching engine end to end on the quantized
        pool: CacheInsert splices payload+scale leaves, budgets/eos
        fold as before."""
        from paddle_tpu.serving import InferenceEngine, Request
        from paddle_tpu.serving.model import TransformerLM

        monkeypatch.setenv("PADDLE_SERVE_KV_QUANT", "int8")
        paddle.seed(5)
        model = TransformerLM(64, d_model=32, num_heads=4, num_layers=2,
                              max_position=64)
        eng = InferenceEngine(model, slots=2, max_length=32, sync_every=4)
        assert isinstance(eng._state.caches[0].k, qc.QuantKV)
        for i in range(3):
            eng.submit(Request((np.arange(6) + i) % 60,
                               max_new_tokens=5))
        results = eng.run()
        assert len(results) == 3
        for r in results.values():
            assert 1 <= len(r.tokens) <= 5


# ---------------------------------------------------------------------------
# telemetry: byte accounting with zero new per-step syncs
# ---------------------------------------------------------------------------


class TestCommTelemetry:
    def _mk_step(self, quantized, seed=0):
        paddle.seed(seed)
        strategy = DistributedStrategy()
        if quantized:
            strategy.quantized_allreduce = "int8"
        fleet.init(is_collective=True, strategy=strategy)
        net = _DenseNet()
        opt = fleet.distributed_optimizer(
            optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        )
        return TrainStep(net, lambda o, y: (o ** 2).mean(), opt)

    def test_step_metrics_carry_grad_comm(self, monkeypatch, tmp_path):
        from paddle_tpu.observability import bus

        busf = str(tmp_path / "bus.jsonl")
        monkeypatch.setenv("PADDLE_OBS_BUS_FILE", busf)
        monkeypatch.setenv("PADDLE_GUARD_SYNC_EVERY", "2")
        step = self._mk_step("int8")
        n_elems = sum(int(p._data.size) for p in step._p_objs)
        assert step._grad_comm_info["dtype"] == "int8"
        assert step._grad_comm_info["grad_elems"] == n_elems
        x = np.random.RandomState(0).rand(8, 10).astype(np.float32)
        y = np.zeros((8, 4), np.float32)
        for _ in range(8):
            step(x, y)
        rows = bus.read_stream(busf)
        static = [r for r in rows if r["kind"] == "grad_comm"]
        assert static and static[0]["payload"]["dtype"] == "int8"
        sm = [r for r in rows if r["kind"] == "step_metrics"]
        assert sm and sm[-1]["payload"]["grad_comm"]["dtype"] == "int8"
        assert sm[-1]["payload"]["grad_comm"]["bytes_on_wire"] < \
            sm[-1]["payload"]["grad_comm"]["bytes_f32"]

    def test_zero_extra_host_syncs(self, monkeypatch):
        """The byte accounting is static-shape arithmetic: enabling the
        quantized policy changes the device->host read count by exactly
        zero (same contract as the PR 8 step_metrics cadence)."""
        monkeypatch.setenv("PADDLE_GUARD_SYNC_EVERY", "2")

        def count_reads(quantized, seed):
            step = self._mk_step(quantized, seed=seed)
            x = np.random.RandomState(0).rand(8, 10).astype(np.float32)
            y = np.zeros((8, 4), np.float32)
            step(x, y)  # compile outside the counted window
            counted = {"n": 0}
            real = np.asarray

            def counting(a, *args, **kw):
                if isinstance(a, jax.Array):
                    counted["n"] += 1
                return real(a, *args, **kw)

            monkeypatch.setattr(np, "asarray", counting)
            try:
                for _ in range(8):
                    step(x, y)
            finally:
                monkeypatch.setattr(np, "asarray", real)
            return counted["n"]

        base = count_reads(None, seed=0)
        quant = count_reads("int8", seed=1)
        assert quant == base

    def test_timeline_summarizes_grad_comm(self, tmp_path):
        """tools/timeline.py surfaces the wire dtype/bytes next to its
        exposed-comm estimate (stdlib-pure, synthetic stream)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "timeline", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "tools", "timeline.py"))
        timeline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(timeline)
        obs = tmp_path / "obs"
        obs.mkdir()
        rows = [
            {"v": 1, "kind": "grad_comm", "step": 0, "time": 1.0,
             "rank": 0, "payload": qc.grad_comm_info(
                 1_000_000, ("int8", 128))},
            {"v": 1, "kind": "step_metrics", "step": 4, "time": 2.0,
             "rank": 0, "payload": {"step_ms": 10.0, "steps": 4}},
        ]
        with open(obs / "telemetry.rank0.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        streams, dumps, trace, lines = timeline.merge(str(obs))
        joined = "\n".join(lines)
        assert "grad comm" in joined and "int8" in joined
        stats = timeline._rank_stats(streams[0], [])
        assert stats["grad_comm"]["dtype"] == "int8"


# ---------------------------------------------------------------------------
# convergence (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestConvergence:
    def test_lenet_loss_decrease_parity(self):
        """LeNet under the quantized grad-comm policy converges in step
        with the f32 run (the ISSUE 10 convergence gate)."""
        from paddle_tpu.vision.models import LeNet

        def run(quantized, steps=25):
            paddle.seed(3)
            strategy = DistributedStrategy()
            if quantized:
                strategy.quantized_allreduce = "int8"
            fleet.init(is_collective=True, strategy=strategy)
            net = LeNet()
            opt = fleet.distributed_optimizer(
                optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                   parameters=net.parameters()),
                strategy=strategy,
            )
            step = TrainStep(
                net, lambda o, y: F.cross_entropy(o, y), opt)
            rng = np.random.RandomState(0)
            x = rng.rand(32, 1, 28, 28).astype(np.float32)
            y = (np.arange(32) % 10).astype(np.int64)
            losses = [float(step(x, y).numpy()) for _ in range(steps)]
            return losses

        lq = run(True)
        lf = run(False)
        assert lq[-1] < 0.5 * lq[0], "quantized run failed to learn"
        # same trajectory within the quantizer's budget
        np.testing.assert_allclose(lq[-1], lf[-1], rtol=0.2, atol=0.05)
