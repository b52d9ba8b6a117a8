"""Pallas flash-attention kernel, interpreter mode (CPU CI; the compiled
kernel runs on the chip: `flash_fwd_roofline` / `flash_bwd_roofline` of
the `gpt2-medium.train` cell carry its timing)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention

B, H, S, D = 2, 2, 128, 32


@pytest.fixture(autouse=True, scope="module")
def _clear_trivial_mesh():
    """Same leak as test_decoder_hot_path (ISSUE 7 satellite): the
    trivial 1-device hybrid mesh installed for the routing tests must
    not outlive this module."""
    from paddle_tpu.distributed import comm

    prev = comm._state.hybrid_mesh
    yield
    comm._state.hybrid_mesh = prev


def _qkv(seed=0):
    r = np.random.RandomState(seed)
    return [
        jnp.asarray(r.rand(B, H, S, D).astype(np.float32) - 0.5)
        for _ in range(3)
    ]


def _dense(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    if causal:
        pos = jnp.arange(S)
        s = jnp.where(pos[None, :] > pos[:, None], -1e30, s)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 64, 128])
def test_kernel_matches_dense(causal, block):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, block, block, None, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v, causal)),
        rtol=2e-4, atol=2e-5,
    )


def test_kernel_gradients_match_dense():
    q, k, v = _qkv(1)
    cot = jnp.asarray(
        np.random.RandomState(2).rand(B, H, S, D).astype(np.float32)
    )

    def loss_flash(a, b, c):
        return (flash_attention(a, b, c, True, 64, 64, None, True)
                * cot).sum()

    def loss_dense(a, b, c):
        return (_dense(a, b, c, True) * cot).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )


def test_indivisible_block_raises():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, False, 96, 96, None, True)


def test_mha_blockwise_stays_on_xla_path_on_cpu():
    """On the CPU backend blockwise_attention must NOT pick the pallas
    kernel (compiled pallas is TPU-only; interpret is for tests)."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.layers.ring_attention import blockwise_attention

    q, k, v = _qkv(3)
    out = blockwise_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
        paddle.to_tensor(np.asarray(v)), causal=True, block_size=64,
    )
    np.testing.assert_allclose(
        out.numpy(), np.asarray(_dense(q, k, v, True)), rtol=2e-4,
        atol=2e-5,
    )


class TestParallelMHAFlashRouting:
    """ParallelMultiHeadAttention(use_flash_attention=True): the flash
    core must match the dense softmax path, forward and backward, on
    shared weights."""

    def _pair(self, T=128, d=32, heads=2):
        import paddle_tpu as paddle
        from paddle_tpu.distributed import comm
        from paddle_tpu.distributed.meta_parallel import (
            ParallelMultiHeadAttention,
        )

        if comm.hybrid_mesh() is None:
            comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)
        paddle.seed(3)
        dense = ParallelMultiHeadAttention(d, heads, causal=True)
        flash = ParallelMultiHeadAttention(
            d, heads, causal=True, use_flash_attention=True
        )
        flash.set_state_dict(dense.state_dict())
        x = paddle.to_tensor(
            np.random.RandomState(0).rand(2, T, d).astype(np.float32),
            stop_gradient=False,
        )
        return dense, flash, x

    def test_forward_matches_dense(self):
        dense, flash, x = self._pair()
        np.testing.assert_allclose(
            flash(x).numpy(), dense(x).numpy(), rtol=2e-4, atol=2e-5
        )

    def test_backward_matches_dense(self):
        import paddle_tpu as paddle

        dense, flash, x = self._pair()
        flash(x).sum().backward()
        g_flash = x.grad.numpy().copy()
        x2 = paddle.to_tensor(x.numpy(), stop_gradient=False)
        dense(x2).sum().backward()
        np.testing.assert_allclose(
            g_flash, x2.grad.numpy(), rtol=5e-4, atol=5e-5
        )

    def test_dropout_with_flash_raises(self):
        import pytest as _pytest

        from paddle_tpu.distributed import comm
        from paddle_tpu.distributed.meta_parallel import (
            ParallelMultiHeadAttention,
        )

        if comm.hybrid_mesh() is None:
            comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)
        with _pytest.raises(ValueError, match="dropout"):
            ParallelMultiHeadAttention(
                32, 2, dropout=0.1, use_flash_attention=True
            )


# ---------------------------------------------------------------------------
# offset-aware causal masking (ISSUE 9 decode-append seam)
# ---------------------------------------------------------------------------


class TestOffsetCausal:
    """`q_offset`/`kv_offset` through the PUBLIC flash_attention entry:
    the kernel's global-position causal mask vs a dense oracle with the
    same offsets, forward and backward — the seam the decode-append
    routing (attention.flash_plan Sq != Sk) and ring attention share."""

    def _dense_offset(self, q, k, v, q_off, kv_off):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
        qpos = jnp.arange(q.shape[2]) + q_off
        kpos = jnp.arange(k.shape[2]) + kv_off
        s = jnp.where(kpos[None, :] > qpos[:, None], -1e30, s)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    @pytest.mark.parametrize("Sq,Sk,q_off,kv_off", [
        (64, 128, 64, 0),    # end-aligned decode-append
        (32, 128, 96, 0),    # deeper append
        (64, 64, 64, 64),    # both shifted equally == aligned diagonal
        (64, 64, 128, 64),   # fully-visible KV shard (ring rotation)
    ])
    def test_forward_matches_dense_oracle(self, Sq, Sk, q_off, kv_off):
        r = np.random.RandomState(5)
        q, k, v = [
            jnp.asarray(r.rand(2, 2, s, 32).astype(np.float32) - 0.5)
            for s in (Sq, Sk, Sk)
        ]
        out = flash_attention(q, k, v, True, 32, 32, None, True,
                              q_off, kv_off)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(self._dense_offset(q, k, v, q_off, kv_off)),
            rtol=2e-5, atol=2e-6)

    def test_backward_matches_dense_oracle(self):
        Sq, Sk, q_off = 32, 96, 64
        r = np.random.RandomState(6)
        q, k, v = [
            jnp.asarray(r.rand(2, 2, s, 32).astype(np.float32) - 0.5)
            for s in (Sq, Sk, Sk)
        ]
        g = jnp.asarray(r.rand(2, 2, Sq, 32).astype(np.float32))

        def f_flash(q, k, v):
            return (flash_attention(q, k, v, True, 32, 32, None, True,
                                    q_off, 0) * g).sum()

        def f_dense(q, k, v):
            return (self._dense_offset(q, k, v, q_off, 0) * g).sum()

        gf = jax.grad(f_flash, (0, 1, 2))(q, k, v)
        gd = jax.grad(f_dense, (0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_default_offsets_keep_r5_signature(self):
        """Positional callers that predate the offset params (sharded
        seam, ring attention, benches) get offset 0 — identical to the
        r5 kernel."""
        q, k, v = _qkv(3)
        out_old = flash_attention(q, k, v, True, 64, 64, None, True)
        out_new = flash_attention(q, k, v, True, 64, 64, None, True,
                                  0, 0)
        np.testing.assert_array_equal(np.asarray(out_old),
                                      np.asarray(out_new))


# ---------------------------------------------------------------------------
# the MXU operands follow the input dtype (ISSUE 28): bf16 tiles reach
# dot_general unwidened; the softmax state and the accumulators are f32
# ---------------------------------------------------------------------------

#: chip_smoke.KERNEL_TOL_BF16 and its measure (max|a - ref| / max|ref|)
_TOL_BF16 = 2e-2
_DH = 64
#: (Sq, Sk, q_offset): the aligned diagonal, and a decode-append suffix
_BF16_SHAPES = {"aligned": (256, 256, 0), "append": (128, 256, 128)}


def _flash_module():
    import importlib

    return importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _route(monkeypatch, route):
    """The streaming forward takes over past `_RESIDENT_KV_BYTES`, which
    no interpreter-sized K/V reaches: steer the budget, not the kernel."""
    if route == "streaming":
        monkeypatch.setattr(_flash_module(), "_RESIDENT_KV_BYTES", 0)


@pytest.fixture(scope="module", ids="-".join, params=[
    (route, shape) for route in ("resident", "streaming")
    for shape in sorted(_BF16_SHAPES)])
def bf16_errs(request):
    """out/dq/dk/dv of the bf16 kernels against the dense f32 reference
    at `highest` on the same bf16-rounded inputs; one run a (route, shape)."""
    route, shape = request.param
    Sq, Sk, q_off = _BF16_SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, g = (
        jax.random.normal(kk, (1, 2, s, _DH), jnp.float32)
        .astype(jnp.bfloat16)
        for kk, s in zip(ks, (Sq, Sk, Sk, Sq))
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, True, 128, 128, None, True,
                               q_off, 0)

    def dense(q, k, v):
        return TestOffsetCausal()._dense_offset(q, k, v, q_off, 0)

    with pytest.MonkeyPatch.context() as mp:
        _route(mp, route)
        out, vjp = jax.vjp(flash, q, k, v)
        got = (out,) + vjp(g)
    with jax.default_matmul_precision("highest"):
        ref, rvjp = jax.vjp(
            dense, *(a.astype(jnp.float32) for a in (q, k, v)))
        want = (ref,) + rvjp(g.astype(jnp.float32))
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, (name, a.dtype)
        a, b = np.asarray(a, np.float32), np.asarray(b)
        errs[name] = float(np.abs(a - b).max() / np.abs(b).max())
    return errs


@pytest.mark.parametrize("tensor", ["out", "dq", "dk", "dv"])
def test_bf16_kernels_match_dense_f32(bf16_errs, tensor):
    err = bf16_errs[tensor]
    assert np.isfinite(err) and err < _TOL_BF16, (tensor, err)


def _dots(jaxpr, out):
    """Every dot_general under `jaxpr`, through pl.when's cond branches
    and the resident forward's fori_loop."""
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            out.append(e)
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dots(sub, out)
    return out


#: kernel -> its matmuls: QK^T and PV; QK^T, dOV^T, dSK; QK^T, P^TdO,
#: dOV^T, dS^TQ
_KERNEL_DOTS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}


@pytest.mark.parametrize("kernel,route", [
    ("flash_fwd", "resident"), ("flash_fwd", "streaming"),
    ("flash_dq", "resident"), ("flash_dkv", "resident"),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_dot_operands_follow_input_dtype(monkeypatch, dtype, kernel, route):
    """bf16 in -> every MXU operand bf16, f32 in -> f32 (today's kernel:
    every cast a no-op); the result is always f32. Under an ambient
    `highest` the f32 dots keep it and the bf16 dots ask for the default:
    Mosaic refuses a bf16 operand at fp32 contract precision."""
    _route(monkeypatch, route)
    x = jnp.zeros((1, 2, 256, _DH), dtype)
    P = jax.lax.Precision
    precision = P.DEFAULT if dtype == jnp.bfloat16 else P.HIGHEST

    def f(q, k, v, g):
        out, vjp = jax.vjp(
            lambda a, b, c: flash_attention(a, b, c, True, 128, 128,
                                            None, True), q, k, v)
        return (out,) + vjp(g)

    with jax.default_matmul_precision("highest"):
        eqns = jax.make_jaxpr(f)(x, x, x, x).jaxpr.eqns
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"] == kernel]
    assert len(calls) == 1, [e.params["name"] for e in calls]
    if kernel == "flash_fwd":      # the streaming grid adds the k axis
        grid = calls[0].params["grid_mapping"].grid
        assert len(grid) == (3 if route == "streaming" else 2), grid
    dots = _dots(calls[0].params["jaxpr"], [])
    assert len(dots) == _KERNEL_DOTS[kernel]
    for e in dots:
        assert [a.aval.dtype for a in e.invars] == [dtype, dtype], e
        assert e.outvars[0].aval.dtype == jnp.float32, e
        assert e.params["precision"] == (precision, precision), e
