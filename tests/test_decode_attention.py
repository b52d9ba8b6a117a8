"""The decode step's attention as the `decode_attention` kernel that stops
at each slot's live length (ISSUE 36): in the Pallas interpreter on the
CPU the kernel gives `cached_attention`'s dense form to float32 rounding
on the same arrays, rows past `pos[b]` are never weighed, and
`cached_attention` sends only the decode step's plain, unsharded,
one-row-a-slot read to it (`observability.metrics
.cached_attention_routes()` tells the two ways apart), by the one
predicate that routes the write."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import comm
from paddle_tpu.distributed import quantized_comm as qc
from paddle_tpu.nn.functional import attention as attn_route
from paddle_tpu.observability.metrics import (cached_attention_routes,
                                              kv_append_routes)
from paddle_tpu.ops.pallas.decode_attention import TILE, decode_attention
from paddle_tpu.serving import paged_kv as pk

B, CAP, D = 8, 4 * TILE, 64


@pytest.fixture(autouse=True)
def _no_mesh():
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    yield
    comm._state.hybrid_mesh = prev


def _positions(kind):
    if kind == "edges":      # a tile's first, last and middle row, the ends
        return np.array([0, TILE - 1, TILE, TILE + TILE // 2, CAP - 1,
                         2 * TILE - 1, 2 * TILE, 3 * TILE + 5])
    if kind == "equal":
        return np.full(B, TILE + 72)
    return np.random.default_rng(11).permutation(CAP)[:B]


def _operands(heads, dtype, pos, seed=0):
    """q, K, V with every row past `pos[b]` filled with large finite
    garbage: a key that is weighed at all shows."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, heads, 1, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, heads, CAP, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, heads, CAP, D), jnp.float32)
    dead = (jnp.arange(CAP)[None, None, :, None]
            > jnp.asarray(pos)[:, None, None, None])
    k, v = jnp.where(dead, 1e30, k), jnp.where(dead, 1e30, v)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def _attend(q, k, v, pos, **kw):
    """`cached_attention` on Tensors, and how its call was lowered."""
    before = cached_attention_routes()
    out = attn_route.cached_attention(
        Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v),
        Tensor._wrap(jnp.asarray(pos, jnp.int32)), **kw)
    after = cached_attention_routes()
    return out._data, {r: after[r] - before[r] for r in after}


@pytest.mark.parametrize("scale", [None, 0.2], ids=["default", "given"])
@pytest.mark.parametrize("positions", ["edges", "equal", "different"])
@pytest.mark.parametrize("heads", [16, 20])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_the_dense_form(dtype, heads, positions, scale,
                                      monkeypatch):
    pos = _positions(positions)
    q, k, v = _operands(heads, dtype, pos)
    kw = {} if scale is None else {"scale": scale}
    monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    want, routes = _attend(q, k, v, pos, **kw)
    assert routes == {"kernel": 0, "dense": 1}
    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    got, routes = _attend(q, k, v, pos, **kw)
    assert routes == {"kernel": 1, "dense": 0}
    assert got.shape == want.shape == (B, heads, 1, D)
    assert got.dtype == want.dtype == dtype
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_one_key_is_its_value_whatever_else_the_cache_holds():
    """At `pos` 0 a slot sees one key: the result is that row of V bit
    for bit, with the other 511 rows at 1e30."""
    pos = np.zeros(B, np.int64)
    q, k, v = _operands(16, jnp.float32, pos)
    got = decode_attention(q, k, v, jnp.asarray(pos, jnp.int32),
                           interpret=True)
    assert bool((got == v[:, :, :1, :]).all())


def test_result_dtype_is_the_dense_forms():
    """A float32 query over a bfloat16 cache: float32 out, as `core`'s
    einsums give; the cache is read as stored."""
    pos = _positions("different")
    q, k, v = _operands(16, jnp.bfloat16, pos)
    got = decode_attention(q.astype(jnp.float32), k, v,
                           jnp.asarray(pos, jnp.int32), interpret=True)
    assert got.dtype == jnp.float32


def test_positions_past_the_capacity_see_every_key():
    q, k, v = _operands(16, jnp.float32, np.full(B, CAP - 1))
    pos = jnp.asarray([CAP, CAP + 7, 1 << 20] + [CAP - 1] * (B - 3),
                      jnp.int32)
    got = decode_attention(q, k, v, pos, interpret=True)
    want = decode_attention(q, k, v, jnp.full(B, CAP - 1, jnp.int32),
                            interpret=True)
    assert bool((got == want).all())


@pytest.mark.parametrize("k_shape,q_shape,v_dtype", [
    ((2, 2, 100, 64), (2, 2, 1, 64), jnp.float32),   # no whole lane tiles
    ((2, 2, 128, 64), (2, 2, 2, 64), jnp.float32),   # two query rows a slot
    ((2, 2, 128, 64), (2, 2, 1, 64), jnp.bfloat16),  # V unlike K
    ((1, 136, 128, 8), (1, 136, 1, 8), jnp.float32),  # heads past a tile
])
def test_kernel_refuses_what_it_cannot_tile(k_shape, q_shape, v_dtype):
    with pytest.raises(ValueError, match="decode_attention"):
        decode_attention(jnp.zeros(q_shape), jnp.zeros(k_shape),
                         jnp.zeros(k_shape, v_dtype),
                         jnp.zeros(k_shape[0], jnp.int32), interpret=True)


# -- routing ---------------------------------------------------------------


def _plain(shape=(2, 2, 256, 64), sq=1):
    c = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(5),
                          (shape[0], shape[1], sq, shape[3]), jnp.float32)
    return q, Tensor._wrap(c), Tensor._wrap(c + 1)


def _sq_2():
    return _plain(sq=2)


def _d_128():
    return _plain(shape=(2, 2, 256, 128))


def _cap_1000():
    return _plain(shape=(2, 2, 1000, 64))


def _heads_136():
    return _plain(shape=(2, 136, 128, 8))


def _quantized():
    q, s = qc.kv_zero((2, 2, 256, 64), "int8")
    cache = qc.QuantKV(Tensor._wrap(q), Tensor._wrap(s))
    return _plain()[0], cache, cache


def _paged():
    raw = pk.paged_zero(2, 2, 256, 64, block=128, dtype=jnp.float32)
    cache = pk.PagedKV(Tensor._wrap(raw.kv), Tensor._wrap(raw.table))
    return _plain()[0], cache, cache


def _meshed():
    from jax.sharding import Mesh

    comm._state.hybrid_mesh = Mesh(
        np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    return _plain()


def _cpu_without_the_interpreter():
    return _plain()


@pytest.mark.parametrize("case", [
    _sq_2, _d_128, _cap_1000, _heads_136, _quantized, _paged, _meshed,
    _cpu_without_the_interpreter], ids=lambda f: f.__name__.strip("_"))
def test_every_other_call_keeps_the_dense_form(case, monkeypatch):
    """Prefill and speculative steps (Sq > 1), a lane-wide head, a
    capacity that is no whole number of lane tiles, more heads than the
    kernel turns in one tile, `QuantKV`, `PagedKV`,
    a 2-device mesh, and the CPU without the interpreter: each is one
    `cached_attention` call lowered the old way."""
    if case is _cpu_without_the_interpreter:
        monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    else:
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    q, key, value = case()
    before = cached_attention_routes()
    attn_route.cached_attention(
        Tensor._wrap(q), key, value,
        Tensor._wrap(jnp.asarray([3, 99], jnp.int32)))
    after = cached_attention_routes()
    assert {r: after[r] - before[r] for r in after} == {
        "kernel": 0, "dense": 1}


def test_write_and_read_answer_from_the_one_predicate(monkeypatch):
    """`cache_update` and `cached_attention` ask `_lane_cache_route` and
    nothing else of their own: what it is handed is the cache and the
    slot's one row, and its answer moves both counters together."""
    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    q, key, value = _plain()
    pos = Tensor._wrap(jnp.asarray([3, 199], jnp.int32))
    asked = []
    route = attn_route._lane_cache_route

    def step(answer):
        def predicate(c, u):
            asked.append((c.shape, u.shape))
            return answer(c, u)

        monkeypatch.setattr(attn_route, "_lane_cache_route", predicate)
        del asked[:]
        w0, r0 = kv_append_routes(), cached_attention_routes()
        k = attn_route.cache_update(key, Tensor._wrap(q), pos)
        attn_route.cached_attention(Tensor._wrap(q), k, value, pos)
        w1, r1 = kv_append_routes(), cached_attention_routes()
        return (w1["kernel"] - w0["kernel"], r1["kernel"] - r0["kernel"],
                w1["scatter"] - w0["scatter"], r1["dense"] - r0["dense"])

    assert step(route) == (1, 1, 0, 0)
    # the write asks of its cache and its new row, the read of K and q
    assert asked == [((2, 2, 256, 64), (2, 2, 1, 64))] * 2
    assert step(lambda c, u: None) == (0, 0, 1, 1)


# -- the append folded in: decode_append_attention ---------------------------


from paddle_tpu.ops.pallas.decode_attention import (  # noqa: E402
    decode_append_attention)
from paddle_tpu.ops.pallas.kv_append import kv_append  # noqa: E402

APPEND_CAP = 3 * TILE
# a tile's first and last row, the next tile's first, the last row
APPEND_POS = np.array([0, TILE - 1, TILE, APPEND_CAP - 1])


def _append_operands(heads, dtype, seed=7, slots=len(APPEND_POS)):
    """q, K, V at the cache dtype and float32 new rows (the kernel casts
    them, as `kv_append` does)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, shape = slots, (slots, heads, APPEND_CAP, D)
    q = jax.random.normal(ks[0], (b, heads, 1, D), jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], shape, jnp.float32).astype(dtype)
    kn = jax.random.normal(ks[3], (b, heads, 1, D), jnp.float32)
    vn = jax.random.normal(ks[4], (b, heads, 1, D), jnp.float32)
    return q.astype(dtype), k, v, kn, vn


@pytest.mark.parametrize("heads", [16, 20, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_append_attention_is_the_two_kernels_bit_for_bit(dtype, heads):
    """`kv_append` of K, of V, then `decode_attention`: the attention and
    both caches, bit for bit, with `pos` at a tile's first and last row,
    the next tile's first and the capacity's last in one batch; and
    nothing changed in either cache but the rows written at `pos`."""
    q, k, v, kn, vn = _append_operands(heads, dtype)
    pos = jnp.asarray(APPEND_POS, jnp.int32)
    out, k2, v2 = decode_append_attention(q, k, v, kn, vn, pos,
                                          interpret=True)
    k1, v1 = kv_append(k, kn, pos, True), kv_append(v, vn, pos, True)
    want = decode_attention(q, k1, v1, pos, interpret=True)
    assert out.dtype == want.dtype == dtype and out.shape == want.shape
    assert bool((out == want).all())
    assert bool((k2 == k1).all()) and bool((v2 == v1).all())
    rows = (np.arange(APPEND_CAP)[None, :] == APPEND_POS[:, None])
    for new, old, written in ((k2, k, kn), (v2, v, vn)):
        changed = np.asarray((new != old).any(axis=(1, 3)))
        assert not (changed & ~rows).any()
        at = new[np.arange(len(APPEND_POS)), :, APPEND_POS, :]
        assert bool((at == written[:, :, 0, :].astype(dtype)).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_append_attention_matches_the_dense_form(dtype, monkeypatch):
    """Through `cached_append_attention`: the fused kernel's attention
    gives the dense form's (`cache_update`'s scatter, then `core` over
    the capacity) to its rounding, and its caches are the scatter's bit
    for bit."""
    q, k, v, kn, vn = (Tensor._wrap(a) for a in _append_operands(20, dtype))
    pos = Tensor._wrap(jnp.asarray(APPEND_POS, jnp.int32))
    monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    want = attn_route.cached_append_attention(q, k, v, kn, vn, pos)
    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    got = attn_route.cached_append_attention(q, k, v, kn, vn, pos)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[0]._data, np.float32),
                               np.asarray(want[0]._data, np.float32),
                               rtol=tol, atol=tol)
    for a, b in zip(got[1:], want[1:]):
        assert bool((a._data == b._data).all())


@pytest.mark.parametrize("k_shape,q_shape,new_shape", [
    ((2, 2, 100, 64), (2, 2, 1, 64), (2, 2, 1, 64)),  # no whole lane tiles
    ((2, 2, 128, 64), (2, 2, 2, 64), (2, 2, 2, 64)),  # two rows a slot
    ((2, 2, 128, 64), (2, 2, 1, 64), (2, 2, 1, 32)),  # rows unlike the cache
    ((1, 41, 128, 8), (1, 41, 1, 8), (1, 41, 1, 8)),  # past one turn
])
def test_append_attention_refuses_what_it_cannot_tile(k_shape, q_shape,
                                                      new_shape):
    with pytest.raises(ValueError, match="decode_append_attention"):
        decode_append_attention(
            jnp.zeros(q_shape), jnp.zeros(k_shape), jnp.zeros(k_shape),
            jnp.zeros(new_shape), jnp.zeros(new_shape),
            jnp.zeros(k_shape[0], jnp.int32), interpret=True)


# -- the live mask: a dead slot's grid step moves nothing --------------------

# eight slots, each pattern below asks of them: a tile's first and last
# row, the next tile's first, past two tiles, the capacity's last, just
# past one tile, inside the first, and the second tile's second row
MASK_POS = np.array([0, TILE - 1, TILE, 2 * TILE + 5, APPEND_CAP - 1,
                     TILE + 2, 7, TILE + 1])
MASKS = {
    "all_live": [1, 1, 1, 1, 1, 1, 1, 1],
    "all_dead": [0, 0, 0, 0, 0, 0, 0, 0],
    "one_live": [0, 0, 0, 1, 0, 0, 0, 0],
    "first_dead": [0, 1, 1, 1, 1, 1, 1, 1],
    "last_dead": [1, 1, 1, 1, 1, 1, 1, 0],
    "alternating": [1, 0, 1, 0, 1, 0, 1, 0],
    # slots 3 and 7 write and read past their first tile, each after a
    # dead slot: the next live slot's first tiles are fetched across it
    "dead_then_past_a_tile": [1, 1, 0, 1, 0, 0, 0, 1],
}


def _masked_operands(heads):
    return (*_append_operands(heads, jnp.float32, 13, len(MASK_POS)),
            jnp.asarray(MASK_POS, jnp.int32))


def _assert_masked(got, want, operands, live):
    """Live rows and live slots' caches bit for bit the unmasked
    kernel's; dead rows zero, dead slots' caches as they came in."""
    (out, k, v), (out0, k0, v0) = got, want
    _, k_in, v_in = operands[:3]
    dead = ~live
    assert bool((out[live] == out0[live]).all())
    assert bool((k[live] == k0[live]).all())
    assert bool((v[live] == v0[live]).all())
    assert bool((out[dead] == 0).all())
    assert bool((k[dead] == k_in[dead]).all())
    assert bool((v[dead] == v_in[dead]).all())


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("heads", [16, 20], ids=["chat", "batch"])
def test_masked_kernel_skips_dead_slots(heads, mask):
    """At the chat and the batch cell's head counts, whatever slots the
    mask leaves live: their attention and caches are the unmasked
    kernel's bit for bit, a dead slot's row of the attention is zero and
    its caches are left as they were (its new rows are not written)."""
    operands = _masked_operands(heads)
    live = np.asarray(MASKS[mask], bool)
    want = decode_append_attention(*operands, interpret=True)
    got = decode_append_attention(*operands, jnp.asarray(live),
                                  interpret=True)
    assert got[0].shape == want[0].shape and got[0].dtype == want[0].dtype
    _assert_masked(got, want, operands, live)


def test_masked_kernel_copies_under_the_chips_rules(capfd):
    """The same patterns in the interpreter that keeps the chip's rules
    for copies (`pltpu.InterpretParams`): a copy lands only when it is
    waited for, so a write that the pipeline never waits for leaves its
    tile unwritten, and a buffer that two slots' copies share at once is
    reported as a race, as is a semaphore left counting at the kernel's
    end; fresh scratch is NaN, so a row the kernel never writes shows. (A
    wait for a copy that was never started would spin for ever: the run
    has a thread and a time limit of its own.)"""
    import threading

    from jax.experimental.pallas import tpu as pltpu

    rules = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                  detect_races=True,
                                  uninitialized_memory="nan")
    operands = _masked_operands(4)
    want = decode_append_attention(*operands, interpret=True)
    got, failed = {}, []

    def run():
        try:
            for name, mask in MASKS.items():
                got[name] = jax.block_until_ready(decode_append_attention(
                    *operands, jnp.asarray(mask, bool), interpret=rules))
        except Exception as e:  # noqa: BLE001
            failed.append(e)

    worker = threading.Thread(target=run, daemon=True)
    worker.start()
    worker.join(300)
    assert not worker.is_alive(), "a wait that no copy signals"
    assert not failed, failed
    said = capfd.readouterr().out
    assert "RACE DETECTED" not in said and "non-zero count" not in said
    for name, mask in MASKS.items():
        _assert_masked(got[name], want, operands, np.asarray(mask, bool))
