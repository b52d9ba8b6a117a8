"""The decode step's K/V append as the in-place `kv_append` kernel
(ISSUE 33): in the Pallas interpreter on the CPU the kernel's cache is
the `vmap`-of-`dynamic_update_slice` cache bit for bit, and
`cache_update` sends only the decode step's plain, unsharded,
one-row-a-slot write to it (`observability.metrics.kv_append_routes()`
tells the two ways apart)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import comm
from paddle_tpu.distributed import quantized_comm as qc
from paddle_tpu.nn.functional import attention as attn_route
from paddle_tpu.observability.metrics import kv_append_routes
from paddle_tpu.ops.pallas.kv_append import kv_append
from paddle_tpu.serving import paged_kv as pk


@pytest.fixture(autouse=True)
def _no_mesh():
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    yield
    comm._state.hybrid_mesh = prev


def _scatter(c, u, p):
    return jax.vmap(
        lambda cb, ub, pb: jax.lax.dynamic_update_slice_in_dim(
            cb, ub.astype(cb.dtype), pb, axis=1))(c, u, p)


def _operands(shape, dtype, seed=0):
    B, H, cap, D = shape
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(k[0], shape, jnp.float32).astype(dtype),
            jax.random.normal(k[1], (B, H, 1, D), jnp.float32).astype(dtype))


# capacities of several 128-lane tiles, slots and heads as the chat cell's
# [32, 16, 1024, 64] and the batch cell's [16, 20, 1024, 64]
SHAPES = {"chat-like": (32, 16, 384, 64), "batch-like": (16, 20, 256, 64)}


def _positions(kind, B, cap):
    if kind == "edges":      # first and last lane of a tile, first and last row
        base = [0, 127, 128, cap - 1]
        return np.array((base * B)[:B])
    if kind == "equal":      # the same position in different slots
        return np.full(B, 129)
    return np.random.default_rng(7).integers(0, cap, size=B)


@pytest.mark.parametrize("positions", ["edges", "equal", "spread"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES.values()), ids=list(SHAPES))
def test_kernel_equals_scatter_bit_for_bit(shape, dtype, positions):
    c, u = _operands(shape, dtype)
    p = jnp.asarray(_positions(positions, shape[0], shape[2]), jnp.int32)
    got = jax.jit(lambda c, u, p: kv_append(c, u, p, True))(c, u, p)
    want = _scatter(c, u, p)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool((got == want).all())
    # nothing but the B x H appended rows changed
    changed = np.asarray((got != c).any(axis=(1, 3)))
    assert changed.sum() <= shape[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_done_slot_rewrites_its_frozen_row(dtype):
    """A done slot's position stays put, so every later step writes its
    dead row again, and the live slots move on: three steps of both forms
    keep the same cache."""
    shape = (4, 2, 256, 64)
    c, _ = _operands(shape, dtype)
    a = b = c
    step = jax.jit(lambda c, u, p: kv_append(c, u, p, True))
    for i in range(3):
        _, u = _operands(shape, dtype, seed=10 + i)
        p = jnp.asarray([127 + i, 200, 5 + i, 255], jnp.int32)  # 1, 3 done
        a, b = step(a, u, p), _scatter(b, u, p)
    assert bool((a == b).all())


def test_out_of_range_positions_clamp_as_the_scatter_does():
    c, u = _operands((4, 2, 256, 64), jnp.float32)
    p = jnp.asarray([-3, 256, 1 << 20, 255], jnp.int32)
    assert bool((kv_append(c, u, p, True) == _scatter(c, u, p)).all())


def test_cast_to_the_cache_dtype():
    c, _ = _operands((2, 2, 128, 64), jnp.bfloat16)
    _, u = _operands((2, 2, 128, 64), jnp.float32, seed=3)
    p = jnp.asarray([3, 100], jnp.int32)
    got = kv_append(c, u, p, True)
    assert got.dtype == jnp.bfloat16
    assert bool((got == _scatter(c, u, p)).all())


@pytest.mark.parametrize("shape,rows", [
    ((2, 2, 100, 64), (2, 2, 1, 64)),    # no whole lane tiles
    ((2, 2, 128, 64), (2, 2, 2, 64)),    # two rows a slot
])
def test_kernel_refuses_what_it_cannot_tile(shape, rows):
    with pytest.raises(ValueError, match="kv_append"):
        kv_append(jnp.zeros(shape), jnp.zeros(rows),
                  jnp.zeros(shape[0], jnp.int32), True)


# -- routing ---------------------------------------------------------------


def _update(cache, new, pos):
    """`cache_update` on Tensors, and how its calls were lowered."""
    before = kv_append_routes()
    out = attn_route.cache_update(
        cache, Tensor._wrap(new), Tensor._wrap(jnp.asarray(pos, jnp.int32)))
    after = kv_append_routes()
    return out, {k: after[k] - before[k] for k in after}


def _plain(shape=(2, 2, 256, 64), dtype=jnp.float32, sq=1):
    c, _ = _operands(shape, dtype)
    B, H, _, D = shape
    u = jax.random.normal(jax.random.PRNGKey(5), (B, H, sq, D), dtype)
    return Tensor._wrap(c), u


def test_decode_shape_takes_the_kernel(monkeypatch):
    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    cache, u = _plain()
    out, routes = _update(cache, u, [128, 7])
    assert routes == {"kernel": 1, "scatter": 0, "fused": 0}
    assert bool((out._data == _scatter(cache._data, u,
                                       jnp.asarray([128, 7]))).all())


def test_cpu_without_the_interpreter_keeps_the_scatter(monkeypatch):
    monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    cache, u = _plain()
    _, routes = _update(cache, u, [128, 7])
    assert routes == {"kernel": 0, "scatter": 1, "fused": 0}


def _sq_gt_1():
    return _plain(sq=4) + ([3, 9],)


def _odd_capacity():
    return _plain(shape=(2, 2, 200, 64)) + ([3, 199],)


def _lane_wide_head():
    # at head_dim >= 128 the chip keeps head_dim in the lanes, and the
    # kernel's [B, H, D, cap] view would be a copy of the cache tensor
    return _plain(shape=(2, 2, 256, 128)) + ([3, 199],)


def _int_cache():
    c = Tensor._wrap(jnp.zeros((2, 2, 256, 64), jnp.int8))
    return c, jnp.ones((2, 2, 1, 64), jnp.int8), [3, 199]


def _quantized():
    q, s = qc.kv_zero((2, 2, 256, 64), "int8")
    cache = qc.QuantKV(Tensor._wrap(q), Tensor._wrap(s))
    return cache, _plain()[1], [3, 199]


def _paged():
    raw = pk.paged_zero(2, 2, 256, 64, block=128, dtype=jnp.float32)
    cache = pk.PagedKV(Tensor._wrap(raw.kv), Tensor._wrap(raw.table))
    return cache, _plain()[1], [3, 199]


def _meshed():
    from jax.sharding import Mesh

    comm._state.hybrid_mesh = Mesh(
        np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    return _plain() + ([3, 199],)


@pytest.mark.parametrize("case", [
    _sq_gt_1, _odd_capacity, _lane_wide_head, _int_cache, _quantized,
    _paged, _meshed], ids=lambda f: f.__name__.strip("_"))
def test_every_other_call_keeps_the_scatter(case, monkeypatch):
    """Prefill and speculative steps (Sq > 1), a capacity that is no
    whole number of lane tiles, a lane-wide head, a payload that is no
    float, `QuantKV`, `PagedKV` and a non-trivial mesh: each is one
    `cache_update` call lowered the old way, with the interpreter forced."""
    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    cache, u, pos = case()
    _, routes = _update(cache, u, pos)
    assert routes == {"kernel": 0, "scatter": 1, "fused": 0}
