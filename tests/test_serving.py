"""Serving subsystem (ISSUE 9): KV-cache decode, compiled DecodeStep,
sampling ops, and the continuous-batching engine.

Acceptance contracts tested here:
- cache-on decode logits are identical (per-dtype tolerance) to the
  cache-off full-forward recompute at EVERY generated position, on a
  single chip and on a dp2 x mp2 mesh;
- the decode loop makes ZERO per-token host syncs (counted-transfer
  assert, same pattern as the step_metrics cadence test) and
  DecodeStep compiles ONCE (prefill once per bucket) — recompile-ledger
  asserts;
- the end-aligned dense decode-append path and the new offset flash
  kernel are checked against the SAME full-sequence oracle;
- sampling ops match numpy references (greedy/temperature/top-k/top-p,
  per-slot parameter vectors);
- decode_metrics telemetry rides the engine readback cadence with zero
  extra device reads.
"""
import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import comm
from paddle_tpu.jit import DecodeState, DecodeStep, PrefillStep
from paddle_tpu.jit.decode_step import _raw_tree
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import attention as attn_route
from paddle_tpu.observability import bus
from paddle_tpu.serving import (
    InferenceEngine, Request, TransformerLM, generate, sampling,
)

rng = np.random.RandomState(9)


@pytest.fixture(autouse=True, scope="module")
def _restore_mesh():
    """The serving model installs a trivial hybrid mesh; restore the
    prior mesh so later test files see their own state (the ISSUE 7
    lingering-mesh lesson)."""
    prev = comm._state.hybrid_mesh
    yield
    comm._state.hybrid_mesh = prev


@pytest.fixture()
def trivial_mesh():
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    comm.init_hybrid_mesh(dp=1, mp=1, pp=1, sp=1)
    yield
    comm._state.hybrid_mesh = prev


@pytest.fixture()
def dp2mp2():
    prev = comm._state.hybrid_mesh
    comm._state.hybrid_mesh = None
    mesh = comm.init_hybrid_mesh(dp=2, mp=2)
    yield mesh
    comm._state.hybrid_mesh = prev


def _tiny_lm(vocab=48, cap=24, layers=2, heads=4, d=32):
    m = TransformerLM(vocab, d_model=d, num_heads=heads,
                      num_layers=layers, max_position=cap)
    m.eval()
    return m


def _unfused_append_attention(query, k_cache, v_cache, k_new, v_new, pos,
                               *, scale=None, live=None):
    """`cached_append_attention` as its three calls, never folded: the
    write of K, of V, then the read, which take no notice of `live`."""
    k = attn_route.cache_update(k_cache, k_new, pos)
    v = attn_route.cache_update(v_cache, v_new, pos)
    return attn_route.cached_attention(query, k, v, pos, scale=scale), k, v


def _ref_greedy(model, prompts, n):
    """Cache-OFF reference: full forward over the growing sequence at
    every step — the oracle the cached decode must match exactly."""
    seq = np.asarray(prompts, np.int64).copy()
    toks, logits = [], []
    for _ in range(n):
        out = model(paddle.to_tensor(seq))
        lg = np.asarray(out._data)[:, -1, :]
        logits.append(lg)
        nxt = lg.argmax(-1).astype(np.int32)
        toks.append(nxt)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int64)], 1)
    return np.stack(toks, 1), np.stack(logits, 1)


# ---------------------------------------------------------------------------
# sampling ops vs numpy references
# ---------------------------------------------------------------------------


class TestSamplingOps:
    def _logits(self, B=5, V=17):
        return rng.randn(B, V).astype(np.float32) * 2.0

    def test_greedy_matches_numpy(self):
        lg = self._logits()
        got = np.asarray(sampling.greedy(jnp.asarray(lg)))
        assert (got == lg.argmax(-1)).all()

    def test_temperature_scales_rows(self):
        lg = self._logits(B=3)
        t = np.asarray([0.5, 1.0, 2.0], np.float32)
        got = np.asarray(sampling.apply_temperature(jnp.asarray(lg), t))
        np.testing.assert_allclose(got, lg / t[:, None], rtol=1e-6)

    def test_top_k_matches_numpy(self):
        lg = self._logits(B=4, V=11)
        k = np.asarray([3, 1, 0, 11], np.int32)  # 0 = off, 11 = all
        got = np.asarray(sampling.top_k_mask(jnp.asarray(lg), k))
        for b in range(4):
            if k[b] <= 0:
                np.testing.assert_array_equal(got[b], lg[b])
                continue
            thr = np.sort(lg[b])[::-1][k[b] - 1]
            keep = lg[b] >= thr
            assert np.isneginf(got[b][~keep]).all()
            np.testing.assert_array_equal(got[b][keep], lg[b][keep])

    def test_top_p_matches_numpy(self):
        lg = self._logits(B=4, V=9)
        p = np.asarray([0.3, 0.7, 1.0, 0.0], np.float32)
        got = np.asarray(sampling.top_p_mask(jnp.asarray(lg), p))
        for b in range(4):
            if p[b] >= 1.0:
                np.testing.assert_array_equal(got[b], lg[b])
                continue
            order = np.argsort(-lg[b])
            probs = np.exp(lg[b][order] - lg[b][order].max())
            probs = probs / probs.sum()
            csum = np.cumsum(probs)
            keep_sorted = (csum - probs) < p[b]
            keep_sorted[0] = True
            keep = np.zeros(lg.shape[1], bool)
            keep[order] = keep_sorted
            assert np.isneginf(got[b][~keep]).all()
            np.testing.assert_array_equal(got[b][keep], lg[b][keep])

    def test_sample_greedy_rows_deterministic(self):
        lg = self._logits(B=4)
        temp = np.asarray([0.0, 0.0, 1.0, 1.0], np.float32)
        key = jax.random.PRNGKey(0)
        got = np.asarray(
            sampling.sample(jnp.asarray(lg), key, temp, 0, 1.0))
        # greedy rows exactly argmax; sampled rows are valid ids
        assert (got[:2] == lg.argmax(-1)[:2]).all()
        assert ((got >= 0) & (got < lg.shape[1])).all()
        again = np.asarray(
            sampling.sample(jnp.asarray(lg), key, temp, 0, 1.0))
        assert (got == again).all()  # same key -> same draw

    def test_sample_top_k1_is_argmax(self):
        lg = self._logits()
        got = np.asarray(sampling.sample(
            jnp.asarray(lg), jax.random.PRNGKey(3), 1.0, 1, 1.0))
        assert (got == lg.argmax(-1)).all()

    def test_sample_respects_top_k_support(self):
        lg = self._logits(B=2, V=12)
        top3 = np.argsort(-lg, -1)[:, :3]
        for seed in range(8):
            got = np.asarray(sampling.sample(
                jnp.asarray(lg), jax.random.PRNGKey(seed), 1.5, 3, 1.0))
            for b in range(2):
                assert got[b] in top3[b]

    # -- the in-graph greedy branch (ISSUE 31) --------------------------

    @pytest.mark.parametrize("top_k,top_p", [
        ("vec", "vec"), ("vec", None), (None, "vec"), (None, None),
        (0, 1.0)])
    def test_sample_sorts_only_inside_the_branch(self, top_k, top_p):
        """Structure: with a temperature vector, every sort, cumsum and
        random draw of `sample` sits inside a `cond`'s branches, so an
        all-greedy step runs none of them."""
        B, V = 4, 33
        k = jnp.zeros((B,), jnp.int32) if top_k == "vec" else top_k
        p = jnp.ones((B,), jnp.float32) if top_p == "vec" else top_p
        jaxpr = jax.make_jaxpr(sampling.sample)(
            jnp.zeros((B, V), jnp.float32), jax.random.PRNGKey(0),
            jnp.zeros((B,), jnp.float32), k, p).jaxpr
        heavy = {"sort", "argsort", "cumsum", "random_bits",
                 "threefry2x32"}
        outside = set(_primitives(jaxpr, into_cond=False))
        everywhere = set(_primitives(jaxpr, into_cond=True))
        assert not (outside & heavy), outside & heavy
        assert "cond" in outside and "argmax" in outside
        # not vacuous: the draw is still in the program, in the branch
        assert {"random_bits", "threefry2x32"} & everywhere
        if top_k is not None or top_p is not None:
            assert "sort" in everywhere

    @pytest.mark.parametrize("temp", [0.0, -1.0, "mixed_nonpositive"])
    @pytest.mark.parametrize("B,V", [(1, 17), (5, 17), (3, 301)])
    def test_sample_all_greedy_rows_return_argmax(self, temp, B, V):
        """Greedy rows that also carry top_k > 0 and top_p < 1 (a
        request may set them and still ask for temperature 0)."""
        lg = self._logits(B=B, V=V)
        t = (np.linspace(-2.0, 0.0, B) if temp == "mixed_nonpositive"
             else np.full((B,), temp)).astype(np.float32)
        k = np.arange(1, B + 1, dtype=np.int32)
        p = np.linspace(0.1, 0.9, B).astype(np.float32)
        for seed in range(3):
            got = np.asarray(sampling.sample(
                jnp.asarray(lg), jax.random.PRNGKey(seed), t, k, p))
            np.testing.assert_array_equal(got, lg.argmax(-1))
            assert got.dtype == np.int32

    @pytest.mark.parametrize("rows", [
        # (temperature, top_k, top_p) a row
        pytest.param([(1.0, 0, 1.0), (0.7, 0, 1.0), (1.5, 0, 1.0)],
                     id="all_sampling_filters_off"),
        pytest.param([(1.0, 3, 1.0), (0.7, 1, 1.0), (2.0, 40, 1.0)],
                     id="all_sampling_top_k"),
        pytest.param([(1.0, 0, 0.9), (0.7, 0, 0.3), (2.0, 0, 0.0)],
                     id="all_sampling_top_p"),
        pytest.param([(1.0, 5, 0.9), (0.5, 2, 0.5), (1.3, 0, 1.0),
                      (0.9, 7, 1.0), (1.1, 0, 0.6)],
                     id="all_sampling_both"),
        pytest.param([(0.0, 4, 0.5), (1.0, 0, 1.0), (0.0, 0, 1.0),
                      (0.8, 0, 1.0)],
                     id="mixed_filters_on_greedy_rows_only"),
        pytest.param([(0.0, 0, 1.0), (1.2, 6, 0.8), (-1.0, 2, 0.2),
                      (0.6, 3, 1.0), (1.0, 0, 0.7)],
                     id="mixed_both"),
        pytest.param([(0.0, 0, 1.0), (0.0, 0, 1.0), (0.9, 0, 0.5)],
                     id="mixed_one_sampling_row"),
    ])
    def test_sample_draws_match_the_unbranched_formula(self, rows):
        """For the same key a sampling row gets exactly the token the
        sampler gave before the branch: temperature -> top-k -> top-p
        -> categorical, greedy rows swapped in by `where`."""
        t, k, p = (np.asarray(c, d) for c, d in zip(
            zip(*rows), (np.float32, np.int32, np.float32)))
        lg = jnp.asarray(self._logits(B=len(rows), V=57))
        for seed in range(6):
            key = jax.random.PRNGKey(seed)
            want = _unbranched_sample(lg, key, t, k, p)
            got = sampling.sample(lg, key, jnp.asarray(t),
                                  jnp.asarray(k), jnp.asarray(p))
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        assert (np.asarray(got)[t <= 0]
                == np.asarray(lg).argmax(-1)[t <= 0]).all()

    @pytest.mark.parametrize("temperature,top_k,top_p", [
        (None, None, None), (None, 3, 0.5), (1.0, None, None),
        (0.0, None, None), (0.0, 3, 0.5), (1.0, 1, 1.0),
        (0.8, None, 0.9), (0.8, 4, None), (1.3, 0, 1.0)])
    def test_sample_scalars_and_nones(self, temperature, top_k, top_p):
        lg = jnp.asarray(self._logits(B=4, V=23))
        key = jax.random.PRNGKey(11)
        got = np.asarray(sampling.sample(lg, key, temperature, top_k,
                                         top_p))
        if temperature is None:
            want = np.asarray(lg).argmax(-1)
        else:
            want = np.asarray(_unbranched_sample(
                lg, key, temperature, top_k, top_p))
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and got.shape == (4,)

    def test_eager_sample_compiles_once(self):
        """The eager callers (the engine's first token, `generate`) get
        ONE cached program a shape: a second and third call on fresh
        arrays compile nothing."""
        V = 29

        def call(seed):
            return sampling.sample(
                jnp.asarray(rng.randn(1, V).astype(np.float32)),
                jax.random.PRNGKey(seed),
                jnp.asarray([0.0 if seed % 2 else 0.7], jnp.float32),
                jnp.asarray([seed], jnp.int32),
                jnp.asarray([0.9], jnp.float32))

        call(0).block_until_ready()
        with _count_backend_compiles() as compiled:
            call(1).block_until_ready()
            call(2).block_until_ready()
        assert compiled == [], compiled


def _primitives(jaxpr, into_cond):
    """Names of every equation's primitive in ``jaxpr`` and the jaxprs
    nested in it; a ``cond``'s branches only when ``into_cond``."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield name
        if name == "cond" and not into_cond:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub, into_cond)


def _unbranched_sample(lg, key, t, k, p):
    """`sampling.sample` as it was before it branched, from its public
    pieces (a filter given as None is left out, as it was)."""
    tt = jnp.broadcast_to(jnp.asarray(t, jnp.float32), lg.shape[:1])
    f = sampling.apply_temperature(lg.astype(jnp.float32), tt)
    if k is not None:
        f = sampling.top_k_mask(f, k)
    if p is not None:
        f = sampling.top_p_mask(f, p)
    drawn = jax.random.categorical(key, f, axis=-1).astype(jnp.int32)
    return jnp.where(tt <= 0.0, sampling.greedy(lg), drawn)


@contextlib.contextmanager
def _count_backend_compiles():
    """The `jax.monitoring` duration events of XLA backend compiles
    inside the block, as a list of their keys."""
    keys = []

    def on_duration(key, _secs, **kw):
        if "backend_compile" in key:
            keys.append(key)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield keys
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


# ---------------------------------------------------------------------------
# decode-append parity: dense fallback and offset flash vs ONE oracle
# ---------------------------------------------------------------------------


class TestDecodeAppendParity:
    """attention.py's end-aligned dense qpos path and the new flash
    q_offset route, both against the full-sequence reference."""

    def _oracle(self, q_full, k, v, Sq):
        """Dense causal attention over the FULL sequence, sliced to the
        last Sq query rows — the ground truth for any decode-append."""
        D = q_full.shape[-1]
        s = np.einsum("bhqd,bhkd->bhqk", q_full, k) * (D ** -0.5)
        Sk = k.shape[2]
        pos = np.arange(Sk)
        s = np.where(pos[None, :] > pos[:, None], -1e9, s)
        s = s - s.max(-1, keepdims=True)
        w = np.exp(s)
        w = w / w.sum(-1, keepdims=True)
        out = np.einsum("bhqk,bhkd->bhqd", w, v)
        return out[:, :, -Sq:]

    @pytest.mark.parametrize("Sq,Sk", [(1, 9), (3, 16), (8, 32),
                                       (16, 128), (5, 24)])
    def test_dense_end_aligned(self, Sq, Sk, monkeypatch):
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "0")
        B, H, D = 2, 2, 8
        qf, k, v = [rng.randn(B, H, Sk, D).astype(np.float32)
                    for _ in range(3)]
        out = F.scaled_dot_product_attention(
            Tensor(jnp.asarray(qf[:, :, -Sq:])), Tensor(jnp.asarray(k)),
            Tensor(jnp.asarray(v)), is_causal=True, training=False)
        np.testing.assert_allclose(
            np.asarray(out._data), self._oracle(qf, k, v, Sq),
            atol=2e-5)

    @pytest.mark.parametrize("Sq,Sk", [(8, 32), (16, 128), (32, 64)])
    def test_flash_offset_routed(self, Sq, Sk, monkeypatch):
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        assert attn_route.flash_routable(Sq, Sk, causal=True)
        B, H, D = 2, 2, 8
        qf, k, v = [rng.randn(B, H, Sk, D).astype(np.float32)
                    for _ in range(3)]
        out = F.scaled_dot_product_attention(
            Tensor(jnp.asarray(qf[:, :, -Sq:])), Tensor(jnp.asarray(k)),
            Tensor(jnp.asarray(v)), is_causal=True, training=False)
        np.testing.assert_allclose(
            np.asarray(out._data), self._oracle(qf, k, v, Sq),
            atol=2e-5)

    def test_append_hatch_restores_dense_decline(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        monkeypatch.setenv("PADDLE_FLASH_APPEND", "0")
        assert not attn_route.flash_routable(8, 32, causal=True)
        assert attn_route.flash_routable(32, 32, causal=True)

    def test_single_token_stays_dense(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        assert not attn_route.flash_routable(1, 128, causal=True)


# ---------------------------------------------------------------------------
# the MHA static-capacity cache seam
# ---------------------------------------------------------------------------


class TestStaticCacheSeam:
    def test_mha_static_cache_matches_causal_full_forward(self):
        from paddle_tpu import nn

        paddle.seed(11)
        mha = nn.MultiHeadAttention(32, 4, causal=True)
        mha.eval()
        B, L, NEW, CAP = 2, 4, 3, 12
        x = rng.randn(B, L + NEW, 32).astype(np.float32)
        full = np.asarray(mha(Tensor(jnp.asarray(x)))._data)

        cache = mha.gen_cache(batch_size=B, max_length=CAP)
        assert cache.k.shape == [B, 4, CAP, 8]
        pos = Tensor(jnp.zeros((B,), jnp.int32))
        out, cache = mha(Tensor(jnp.asarray(x[:, :L])), cache=cache,
                         pos=pos)
        np.testing.assert_allclose(np.asarray(out._data), full[:, :L],
                                   atol=1e-5)
        for t in range(NEW):
            pos = Tensor(jnp.full((B,), L + t, jnp.int32))
            out, cache = mha(
                Tensor(jnp.asarray(x[:, L + t: L + t + 1])),
                cache=cache, pos=pos)
            np.testing.assert_allclose(
                np.asarray(out._data)[:, 0], full[:, L + t], atol=1e-5)

    def test_legacy_concat_cache_unchanged(self):
        from paddle_tpu import nn

        paddle.seed(11)
        mha = nn.MultiHeadAttention(32, 4, causal=True)
        mha.eval()
        x = Tensor(jnp.asarray(rng.randn(2, 4, 32).astype(np.float32)))
        cache = mha.gen_cache(x)
        assert cache.k.shape[2] == 0
        out, cache = mha(x, x, x, cache=cache)
        assert cache.k.shape[2] == 4  # concat semantics: grows


# ---------------------------------------------------------------------------
# e2e: generate() cache-on vs cache-off, checkpoint round trip
# ---------------------------------------------------------------------------


class TestGenerateE2E:
    def test_checkpoint_prefill_decode_parity(self, trivial_mesh,
                                              tmp_path):
        """The reference script shape: build GPT -> save checkpoint ->
        load into a fresh model -> prefill -> decode N, asserting
        cache-on logits == full-forward recompute at EVERY step."""
        paddle.seed(23)
        src = _tiny_lm()
        paddle.save(src.state_dict(), str(tmp_path / "gpt.pdparams"))

        paddle.seed(99)  # fresh (different) init, then restore
        model = _tiny_lm()
        model.set_state_dict(paddle.load(str(tmp_path / "gpt.pdparams")))

        B, L, NEW = 2, 5, 6
        prompts = rng.randint(0, 48, size=(B, L)).astype(np.int32)
        ref_toks, ref_logits = _ref_greedy(model, prompts, NEW)

        toks, logits = generate(model, prompts, NEW, max_length=24,
                                return_logits=True)
        np.testing.assert_array_equal(toks, ref_toks)
        np.testing.assert_allclose(logits, ref_logits, atol=1e-5)

    def test_decode_compiles_once_prefill_once_per_bucket(
            self, trivial_mesh, monkeypatch):
        monkeypatch.setenv("PADDLE_SERVE_BUCKETS", "8,16")
        paddle.seed(5)
        model = _tiny_lm()
        pre, dec = PrefillStep(model), DecodeStep(model)
        p1 = rng.randint(0, 48, size=(2, 5)).astype(np.int32)
        p2 = rng.randint(0, 48, size=(2, 12)).astype(np.int32)
        generate(model, p1, 6, max_length=24, prefill=pre, decode=dec)
        assert dec.compiles == 1 and pre.compiles == 1
        # same bucket again: both cached
        generate(model, p1, 6, max_length=24, prefill=pre, decode=dec)
        assert dec.compiles == 1 and pre.compiles == 1
        # longer prompt -> second bucket: ONE more prefill compile, the
        # decode step is bucket-independent
        generate(model, p2, 6, max_length=24, prefill=pre, decode=dec)
        assert dec.compiles == 1 and pre.compiles == 2

    def test_eos_stops_and_pads_sentinel(self, trivial_mesh):
        paddle.seed(31)
        model = _tiny_lm()
        prompts = rng.randint(0, 48, size=(1, 4)).astype(np.int32)
        ref, _ = _ref_greedy(model, prompts, 6)
        row = ref[0].tolist()
        # stop token must not occur EARLIER in the stream (decode stops
        # at its first occurrence)
        j = next(i for i in range(1, 6) if row[i] not in row[:i])
        toks = generate(model, prompts, 6, eos_id=row[j],
                        max_length=24, sync_every=2)
        got = toks[0]
        assert (got[: j + 1] == ref[0, : j + 1]).all()
        assert (got[j + 1:] == -1).all()

    def test_dp_mp_mesh_parity(self, dp2mp2):
        """Acceptance: cache-on == cache-off on a dp2 x mp2 mesh (the
        same GSPMD program shape a pod slice runs)."""
        paddle.seed(17)
        model = _tiny_lm()
        B, L, NEW = 2, 5, 4
        prompts = rng.randint(0, 48, size=(B, L)).astype(np.int32)
        ref_toks, ref_logits = _ref_greedy(model, prompts, NEW)
        dec = DecodeStep(model)
        toks, logits = generate(model, prompts, NEW, max_length=24,
                                decode=dec, return_logits=True)
        np.testing.assert_array_equal(toks, ref_toks)
        np.testing.assert_allclose(logits, ref_logits, atol=1e-5)
        assert dec.compiles == 1


# ---------------------------------------------------------------------------
# zero per-token host syncs (the step_metrics counted-transfer pattern)
# ---------------------------------------------------------------------------


class TestZeroPerTokenSyncs:
    def _count_reads(self, fn, monkeypatch):
        counted = {"n": 0}
        real = np.asarray

        def counting(a, *args, **kw):
            if isinstance(a, jax.Array):
                counted["n"] += 1
            return real(a, *args, **kw)

        monkeypatch.setattr(np, "asarray", counting)
        try:
            fn()
        finally:
            monkeypatch.setattr(np, "asarray", real)
        return counted["n"]

    def test_decode_loop_transfer_count_independent_of_tokens(
            self, trivial_mesh, monkeypatch):
        """THE serving cadence contract: decoding 4x more tokens makes
        exactly the same number of device->host reads (the single final
        readback) — zero per-token syncs."""
        paddle.seed(41)
        model = _tiny_lm(cap=40)
        pre, dec = PrefillStep(model), DecodeStep(model)
        prompts = rng.randint(0, 48, size=(2, 4)).astype(np.int32)
        # compile outside the counted window
        generate(model, prompts, 2, max_length=40, prefill=pre,
                 decode=dec)

        def run(n):
            return self._count_reads(
                lambda: generate(model, prompts, n, max_length=40,
                                 prefill=pre, decode=dec), monkeypatch)

        n_short = run(6)
        n_long = run(24)
        assert n_short == n_long
        assert n_short <= 2  # the final stacked-token readback only

    def test_engine_reads_scale_with_windows_not_tokens(
            self, trivial_mesh, monkeypatch):
        """The engine syncs once per PADDLE_SERVE_SYNC_EVERY window (+
        one small read per request insert), never per token."""
        paddle.seed(43)
        model = _tiny_lm(cap=40)
        engine = InferenceEngine(model, slots=2, max_length=40,
                                 sync_every=4)
        warm = Request(rng.randint(0, 48, size=(3,)), max_new_tokens=2)
        engine.submit(warm)
        engine.run()  # compile outside the counted window

        def run_one(n_new):
            req = Request(rng.randint(0, 48, size=(3,)),
                          max_new_tokens=n_new)
            engine.submit(req)
            return self._count_reads(engine.run, monkeypatch)

        reads_8 = run_one(9)    # 2 windows of 4
        reads_16 = run_one(17)  # 4 windows of 4
        # doubling the windows adds their readbacks, NOT 8 more
        # per-token reads
        assert reads_16 - reads_8 <= 2 * 3
        assert reads_8 < 9


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------


class TestInferenceEngine:
    def test_multi_request_matches_sequential_generate(
            self, trivial_mesh):
        paddle.seed(53)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=3)
        reqs = [
            Request(rng.randint(0, 48, size=(n,)), max_new_tokens=m)
            for n, m in [(2, 5), (6, 4), (3, 6), (5, 3), (4, 5)]
        ]
        for q in reqs:
            engine.submit(q)
        results = engine.run()
        assert sorted(results) == sorted(q.rid for q in reqs)
        for q in reqs:
            want = generate(model, [q.prompt_ids], q.max_new_tokens,
                            max_length=32)[0]
            want = [t for t in want.tolist() if t >= 0]
            assert results[q.rid].tokens == want, q.rid

    def test_per_request_stop_conditions(self, trivial_mesh):
        paddle.seed(59)
        model = _tiny_lm(cap=32)
        prompt = rng.randint(0, 48, size=(4,))
        ref = generate(model, [prompt], 6, max_length=32)[0]
        row = ref.tolist()
        # stop token must have no EARLIER occurrence (decode stops at
        # its first appearance)
        j = next(i for i in range(1, 6) if row[i] not in row[:i])
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=2)
        engine.submit(Request(prompt, max_new_tokens=6, eos_id=row[j],
                              rid="stopped"))
        engine.submit(Request(prompt, max_new_tokens=6, rid="full"))
        results = engine.run()
        assert results["stopped"].tokens == row[: j + 1]
        assert results["full"].tokens == row

    def test_greedy_requests_compile_nothing_after_the_first(
            self, trivial_mesh):
        """The first request compiles what serving needs (its bucket's
        prefill, the insert, the decode step, the eager [1, V] sampler);
        two more greedy requests of that bucket compile nothing, the
        first-token sample included."""
        paddle.seed(67)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=3)
        engine.submit(Request(rng.randint(0, 48, size=(5,)),
                              max_new_tokens=5))
        engine.run()
        assert engine._decode.compiles == 1
        with _count_backend_compiles() as compiled:
            for n in (5, 5):
                engine.submit(Request(rng.randint(0, 48, size=(n,)),
                                      max_new_tokens=6))
            results = engine.run()
        assert len(results) == 2
        assert compiled == [], compiled
        assert engine._decode.compiles == 1

    @pytest.mark.parametrize("sampled", [False, True])
    def test_sampler_steps_counter(self, trivial_mesh, sampled):
        """`observability.metrics.sampler_steps()` says which side of
        the sampler's branch the dispatched decode steps called for:
        all on `greedy` while every active request is greedy; a
        request with temperature > 0 moves the windows it is active in
        to `sampling`."""
        from paddle_tpu.observability import metrics

        paddle.seed(71)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=2)
        # 12 decoded tokens after the first: 6 windows of 2 steps
        engine.submit(Request(rng.randint(0, 48, size=(4,)),
                              max_new_tokens=13))
        # 4 decoded tokens after the first: active for 2 windows
        engine.submit(Request(rng.randint(0, 48, size=(3,)),
                              max_new_tokens=5,
                              temperature=0.8 if sampled else 0.0,
                              top_k=5))
        before = metrics.sampler_steps()
        engine.run()
        after = metrics.sampler_steps()
        moved = {k: after[k] - before[k] for k in after}
        assert sorted(moved) == ["greedy", "sampling"]
        dispatched = engine._decode._n_steps
        assert moved["greedy"] + moved["sampling"] == dispatched == 12
        assert moved["sampling"] == (4 if sampled else 0)

    def test_kv_append_kernel_serves_the_same_tokens(
            self, trivial_mesh, monkeypatch):
        """With the `kv_append` kernel forced in the interpreter (and the
        decode step's writes and read made as separate calls, so that
        they are not folded into one kernel) a greedy run emits the
        tokens of XLA's scatter, `DecodeStep` compiles once, and
        `kv_append_routes()` says that the decode program's 2 x layers
        appends took the kernel and none of prefill's did."""
        from paddle_tpu.nn.functional import attention as attn_route
        from paddle_tpu.observability import metrics

        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        monkeypatch.setattr(attn_route, "cached_append_attention",
                            _unfused_append_attention)
        layers = 2
        prompts = [rng.randint(0, 48, size=(n,)) for n in (5, 11, 3)]

        def serve():
            paddle.seed(73)
            engine = InferenceEngine(_tiny_lm(cap=128, layers=layers),
                                     slots=2, max_length=128, sync_every=3)
            reqs = [Request(p, max_new_tokens=m)
                    for p, m in zip(prompts, (7, 4, 6))]
            for q in reqs:
                engine.submit(q)
            before = metrics.kv_append_routes()
            results = engine.run()
            after = metrics.kv_append_routes()
            return (engine, [results[q.rid].tokens for q in reqs],
                    {k: after[k] - before[k] for k in after})

        engine, tokens, routes = serve()
        assert engine._decode.compiles == 1
        assert routes == {"kernel": 2 * layers,
                          "scatter": 2 * layers * engine._prefill.compiles,
                          "fused": 0}
        with monkeypatch.context() as m:
            m.setattr(attn_route, "_lane_cache_route", lambda c, u: None)
            _, want, old = serve()
        assert old["kernel"] == 0 and old["scatter"] > routes["scatter"]
        assert tokens == want and [len(t) for t in tokens] == [7, 4, 6]

    def test_decode_attention_kernel_serves_the_same_tokens(
            self, trivial_mesh, monkeypatch):
        """With the `decode_attention` kernel forced in the interpreter a
        greedy run emits the dense form's tokens, `DecodeStep` compiles
        once, and `cached_attention_routes()` says that the decode
        program's `layers` reads took the kernel and prefill's the dense
        form; with the route answering None every read is dense."""
        from paddle_tpu.nn.functional import attention as attn_route
        from paddle_tpu.observability import metrics

        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        layers = 2
        prompts = [rng.randint(0, 48, size=(n,)) for n in (9, 2, 130, 6)]

        def serve():
            paddle.seed(79)
            # three lane tiles a slot: the 130-token prompt decodes past
            # a tile's edge, the others inside their first
            engine = InferenceEngine(_tiny_lm(cap=384, layers=layers),
                                     slots=2, max_length=384, sync_every=3)
            reqs = [Request(p, max_new_tokens=m)
                    for p, m in zip(prompts, (6, 5, 4, 7))]
            for q in reqs:
                engine.submit(q)
            before = metrics.cached_attention_routes()
            results = engine.run()
            after = metrics.cached_attention_routes()
            return (engine, [results[q.rid].tokens for q in reqs],
                    {k: after[k] - before[k] for k in after})

        engine, tokens, routes = serve()
        assert engine._decode.compiles == 1
        assert routes == {"kernel": layers,
                          "dense": layers * engine._prefill.compiles}
        with monkeypatch.context() as m:
            m.setattr(attn_route, "_lane_cache_route", lambda c, u: None)
            _, want, old = serve()
        assert old["kernel"] == 0 and old["dense"] == routes["dense"] + layers
        assert tokens == want and [len(t) for t in tokens] == [6, 5, 4, 7]

    def test_append_attention_kernel_serves_the_same_tokens(
            self, trivial_mesh, monkeypatch):
        """With the decode step's cache work forced through the fused
        `decode_append_attention` kernel in the interpreter a greedy run
        emits the tokens of XLA's scatter and dense read and of the two
        kernels it replaces, `DecodeStep` compiles once, and the counters
        say that the decode program wrote its 2 x layers rows inside the
        kernel (none through `kv_append`) and read `layers` times through
        it, and that prefill kept XLA's write and read."""
        from paddle_tpu.observability import metrics

        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        layers = 2
        prompts = [rng.randint(0, 48, size=(n,)) for n in (9, 2, 130, 6)]

        def serve():
            paddle.seed(83)
            # three lane tiles a slot: the 130-token prompt writes and
            # reads past a tile's edge, the others inside their first
            engine = InferenceEngine(_tiny_lm(cap=384, layers=layers),
                                     slots=2, max_length=384, sync_every=3)
            reqs = [Request(p, max_new_tokens=m)
                    for p, m in zip(prompts, (6, 5, 4, 7))]
            for q in reqs:
                engine.submit(q)
            w0 = metrics.kv_append_routes()
            r0 = metrics.cached_attention_routes()
            results = engine.run()
            w1 = metrics.kv_append_routes()
            r1 = metrics.cached_attention_routes()
            return (engine, [results[q.rid].tokens for q in reqs],
                    {k: w1[k] - w0[k] for k in w1},
                    {k: r1[k] - r0[k] for k in r1})

        engine, tokens, writes, reads = serve()
        prefills = engine._prefill.compiles
        assert engine._decode.compiles == 1
        assert writes == {"kernel": 0, "scatter": 2 * layers * prefills,
                          "fused": 2 * layers}
        assert reads == {"kernel": layers, "dense": layers * prefills}
        with monkeypatch.context() as m:
            m.setattr(attn_route, "cached_append_attention",
                      _unfused_append_attention)
            _, two_kernels, split, _ = serve()
        assert split["fused"] == 0 and split["kernel"] == 2 * layers
        with monkeypatch.context() as m:
            m.setattr(attn_route, "_lane_cache_route", lambda c, u: None)
            _, want, old, _ = serve()
        assert old["fused"] == 0 and old["kernel"] == 0
        assert tokens == two_kernels == want
        assert [len(t) for t in tokens] == [6, 5, 4, 7]

    def test_dead_slots_skipped_serve_the_same_tokens(
            self, trivial_mesh, monkeypatch):
        """The decode kernel handed the step's live mask serves the
        tokens of the kernel without it and of XLA's dense form, in a
        run where a slot finishes early in a window, stays dead for the
        rest of it and is then given a new request, and a third slot
        stays empty until the queue reaches it. `decode_slot_steps()`
        counts every slot of every step the masked program ran, a token
        emitted as live and a -1 as dead, and nothing on the other two
        routes."""
        from paddle_tpu.observability import metrics
        from paddle_tpu.serving import TransformerLM

        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        prompts = [rng.randint(0, 48, size=(n,)) for n in (9, 130, 4, 6)]
        budgets = (3, 9, 5, 4)

        def serve():
            paddle.seed(89)
            engine = InferenceEngine(_tiny_lm(cap=384, layers=2), slots=3,
                                     max_length=384, sync_every=4)
            reqs = [Request(p, max_new_tokens=m)
                    for p, m in zip(prompts[:2], budgets[:2])]
            for q in reqs:
                engine.submit(q)
            before, results = metrics.decode_slot_steps(), {}
            engine.turn(results)
            # the first request's slot was dead for the last two steps of
            # that window; the queue refills it and the empty third slot
            for p, m in zip(prompts[2:], budgets[2:]):
                reqs.append(Request(p, max_new_tokens=m))
                engine.submit(reqs[-1])
            while engine.turn(results):
                pass
            after = metrics.decode_slot_steps()
            return (engine, [results[q.rid].tokens for q in reqs],
                    {k: after[k] - before[k] for k in after},
                    engine._decode._n_steps)

        engine, tokens, steps, dispatched = serve()
        assert engine._decode.masks_dead_slots
        assert engine._decode.compiles == 1
        # every token but each request's first (prefill's) is a live
        # slot-step; every other slot of every step dispatched is dead
        live = sum(len(t) for t in tokens) - len(tokens)
        assert steps == {"live": live, "dead": 3 * dispatched - live}
        assert steps["dead"] > 0
        with monkeypatch.context() as m:
            m.setattr(TransformerLM, "takes_live", False)
            plain, unmasked, none, _ = serve()
        assert not plain._decode.masks_dead_slots
        with monkeypatch.context() as m:
            m.setattr(attn_route, "_lane_cache_route", lambda c, u: None)
            dense_engine, dense, also_none, _ = serve()
        assert not dense_engine._decode.masks_dead_slots
        assert none == also_none == {"live": 0, "dead": 0}
        assert tokens == unmasked == dense
        assert [len(t) for t in tokens] == list(budgets)

    def test_decode_slot_steps_count_a_turn(self, trivial_mesh,
                                            monkeypatch):
        """One request of three tokens in three slots, four steps a
        window: prefill gives its first token, the first two steps its
        other two, and then it is done. Of the window's 12 slot-steps 2
        are live and 10 dead; without the interpreter (the CPU's dense
        form) nothing is counted."""
        from paddle_tpu.observability import metrics

        def one_turn():
            paddle.seed(97)
            engine = InferenceEngine(_tiny_lm(cap=128, layers=2), slots=3,
                                     max_length=128, sync_every=4)
            req = Request(rng.randint(0, 48, size=(5,)), max_new_tokens=3)
            engine.submit(req)
            before, results = metrics.decode_slot_steps(), {}
            assert not engine.turn(results)
            after = metrics.decode_slot_steps()
            assert len(results[req.rid].tokens) == 3
            return {k: after[k] - before[k] for k in after}

        monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
        assert one_turn() == {"live": 0, "dead": 0}
        monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
        assert one_turn() == {"live": 2, "dead": 10}

    @pytest.mark.slow
    def test_insert_on_free_many_requests(self, trivial_mesh):
        """More requests than slots with heterogeneous lengths, budgets
        and sampling params: every request completes, freed slots are
        re-filled, and greedy requests still match the sequential
        reference even while sharing the batch with sampled ones."""
        paddle.seed(61)
        model = _tiny_lm(cap=40)
        engine = InferenceEngine(model, slots=3, max_length=40,
                                 sync_every=4)
        reqs = []
        for i in range(11):
            n = int(rng.randint(2, 9))
            if i % 3 == 2:   # sampled slot riding alongside greedy ones
                reqs.append(Request(
                    rng.randint(0, 48, size=(n,)), max_new_tokens=5,
                    temperature=0.8, top_k=5))
            else:
                reqs.append(Request(
                    rng.randint(0, 48, size=(n,)), max_new_tokens=6))
        for q in reqs:
            engine.submit(q)
        results = engine.run()
        assert sorted(results) == sorted(q.rid for q in reqs)
        for i, q in enumerate(reqs):
            got = results[q.rid].tokens
            assert len(got) == q.max_new_tokens
            assert all(0 <= t < 48 for t in got)
            if i % 3 != 2:
                want = generate(model, [q.prompt_ids],
                                q.max_new_tokens, max_length=40)[0]
                assert got == [t for t in want.tolist() if t >= 0]


# ---------------------------------------------------------------------------
# an admission's batch-1 scratch cache: one compiled program
# ---------------------------------------------------------------------------


def _tiny_latent():
    from paddle_tpu.serving import LatentMoELM

    return LatentMoELM(48, 32, 2, 2, nope_dim=8, rope_dim=8, v_dim=8,
                       kv_rank=16, dense_ffn=32, expert_ffn=16,
                       num_experts=4, top_k=2, max_position=32)


def _tiny_sparse():
    from paddle_tpu.serving import SparseMoELM

    return SparseMoELM(48, 32, 4, 2, 8, 2, index_heads=2, index_dim=8,
                       topk=4, expert_ffn=16, num_experts=4, top_k=2,
                       max_position=32)


class TestSlotCache:
    @pytest.mark.parametrize(
        "build", [lambda: _tiny_lm(cap=32), _tiny_latent, _tiny_sparse],
        ids=["transformer", "latent", "sparse"])
    def test_compiled_scratch_is_gen_cache(self, trivial_mesh, build):
        """`_slot_cache` hands back what the model's own `gen_cache`
        builds, leaf for leaf, committed on the mesh as `_commit_tree`
        commits a step's state."""
        from jax.sharding import NamedSharding

        paddle.seed(83)
        model = build()
        model.eval()
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 block_size=0)
        got = engine._slot_cache(Request(np.arange(3), max_new_tokens=2), 0)
        want = _raw_tree(model.gen_cache(1, 32, block_size=0))
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            assert g.committed and isinstance(g.sharding, NamedSharding)
        assert engine._slot_cache_jitted.compiles == 1

    def test_scratch_keeps_the_mesh_layout(self, dp2mp2):
        """On a real mesh the compiled scratch is laid out as the eager
        `gen_cache` lays it out: heads over 'mp'."""
        paddle.seed(89)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32)
        got = engine._slot_cache(Request(np.arange(3), max_new_tokens=2), 0)
        want = _raw_tree(model.gen_cache(1, 32, block_size=0))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.sharding.is_equivalent_to(w.sharding, w.ndim)
            assert g.sharding.spec[1] == "mp"

    def _mixed_admissions(self, engine, seed=0):
        """Two one-shot prompts (under the chunk) and two chunked ones."""
        r = np.random.default_rng(seed)
        reqs = [Request(r.integers(0, 48, size=n), max_new_tokens=m)
                for n, m in [(5, 4), (19, 5), (7, 3), (13, 6)]]
        for q in reqs:
            engine.submit(q)
        return reqs, engine.run()

    def test_both_paths_share_one_slot_cache_and_prefill_program(
            self, trivial_mesh, monkeypatch):
        """A one-shot prompt in bucket 8 and a chunk of 8 hand
        `PrefillStep` the same signature: one compile between them, and
        `SlotCache` compiles once over every admission."""
        monkeypatch.setenv("PADDLE_SERVE_BUCKETS", "8,16")
        paddle.seed(97)
        engine = InferenceEngine(_tiny_lm(cap=32), slots=2, max_length=32,
                                 sync_every=4, prefill_chunk=8)
        for seed in (0, 1):
            self._mixed_admissions(engine, seed)
            assert engine._slot_cache_jitted.compiles == 1
            assert engine._prefill.compiles == 1
            assert engine._decode.compiles == 1

    def test_no_eager_gen_cache_on_the_admission_path(self, trivial_mesh):
        """The model's `gen_cache` runs once, inside the trace of the
        compiled program; later admissions call nothing eager."""
        paddle.seed(101)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=4, prefill_chunk=8)
        real, calls = model.gen_cache, []

        def spy(*args, **kwargs):
            out = real(*args, **kwargs)
            leaves = jax.tree_util.tree_leaves(_raw_tree(out))
            calls.append(all(isinstance(v, jax.core.Tracer)
                             for v in leaves))
            return out

        model.gen_cache = spy
        for seed in (2, 3):
            self._mixed_admissions(engine, seed)
        assert calls == [True]

    def test_one_shot_and_chunked_tokens_match_generate(self, trivial_mesh):
        paddle.seed(103)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=4, prefill_chunk=8)
        reqs, results = self._mixed_admissions(engine, seed=4)
        for q in reqs:
            want = generate(model, [q.prompt_ids], q.max_new_tokens,
                            max_length=32)[0]
            assert results[q.rid].tokens == [t for t in want.tolist()
                                             if t >= 0], q.rid


# ---------------------------------------------------------------------------
# decode telemetry on the bus
# ---------------------------------------------------------------------------


class TestDecodeTelemetry:
    def _run_engine(self, tmp_path, monkeypatch, tag, metrics_on=True):
        busf = str(tmp_path / f"bus_{tag}.jsonl")
        monkeypatch.setenv("PADDLE_OBS_BUS_FILE", busf)
        monkeypatch.setenv("PADDLE_OBS_DECODE_METRICS",
                           "1" if metrics_on else "0")
        paddle.seed(67)
        model = _tiny_lm(cap=32)
        engine = InferenceEngine(model, slots=2, max_length=32,
                                 sync_every=3)
        for n, m in [(3, 5), (4, 4), (2, 6)]:
            engine.submit(Request(rng.randint(0, 48, size=(n,)),
                                  max_new_tokens=m))
        engine.run()
        return busf, engine

    def test_decode_metrics_rows(self, trivial_mesh, tmp_path,
                                 monkeypatch):
        busf, _ = self._run_engine(tmp_path, monkeypatch, "on")
        rows = bus.read_stream(busf)
        windows = [r for r in rows if r["kind"] == "decode_metrics"]
        assert windows
        p = windows[0]["payload"]
        for field in ("steps", "tokens", "inflight_slots",
                      "queue_depth", "tokens_per_sec"):
            assert field in p, field
        done = [r for r in rows if r["kind"] == "decode_request"]
        assert len(done) == 3
        for r in done:
            assert r["payload"]["tokens"] > 0
            assert r["payload"]["latency_ms"] >= r["payload"][
                "prefill_ms"] * 0.5
            assert "ms_per_token" in r["payload"]

    def test_knob_disables_rows(self, trivial_mesh, tmp_path,
                                monkeypatch):
        busf, _ = self._run_engine(tmp_path, monkeypatch, "off",
                                   metrics_on=False)
        kinds = {r["kind"] for r in bus.read_stream(busf)}
        assert "decode_metrics" not in kinds
        assert "decode_request" not in kinds
        assert "recompile" in kinds  # the rest of the bus still works

    def test_zero_extra_syncs_vs_metrics_off(self, trivial_mesh,
                                             tmp_path, monkeypatch):
        """Enabling decode_metrics changes the loop's device-read count
        by exactly zero (rows are built from the readback the engine
        already does — the step_metrics discipline)."""
        def count(metrics_on, tag):
            paddle.seed(71)
            model = _tiny_lm(cap=32)
            if metrics_on:
                monkeypatch.setenv("PADDLE_OBS_BUS_FILE",
                                   str(tmp_path / f"b{tag}.jsonl"))
                monkeypatch.setenv("PADDLE_OBS_DECODE_METRICS", "1")
            else:
                monkeypatch.delenv("PADDLE_OBS_BUS_FILE", raising=False)
                monkeypatch.setenv("PADDLE_OBS_DECODE_METRICS", "0")
            engine = InferenceEngine(model, slots=2, max_length=32,
                                     sync_every=3)
            engine.submit(Request(rng.randint(0, 48, size=(3,)),
                                  max_new_tokens=2))
            engine.run()  # compile outside the counted window
            engine.submit(Request(rng.randint(0, 48, size=(3,)),
                                  max_new_tokens=6))
            counted = {"n": 0}
            real = np.asarray

            def counting(a, *args, **kw):
                if isinstance(a, jax.Array):
                    counted["n"] += 1
                return real(a, *args, **kw)

            monkeypatch.setattr(np, "asarray", counting)
            try:
                engine.run()
            finally:
                monkeypatch.setattr(np, "asarray", real)
            return counted["n"]

        base = count(False, 0)
        with_metrics = count(True, 1)
        assert with_metrics == base
        rows = [r for r in bus.read_stream(str(tmp_path / "b1.jsonl"))
                if r["kind"] == "decode_metrics"]
        assert rows


# ---------------------------------------------------------------------------
# refcounted CoW prefix cache — host-side units (ISSUE 18; the engine
# E2E half lives in test_serving_multitenant.py)
# ---------------------------------------------------------------------------


class TestPrefixCacheUnit:
    """Pure-host index semantics over a real BlockPool — no jax, no
    engine: the fast early-sorting half of the round-18 contract."""

    def _cache_pool(self, blocks=16, bs=4, capacity=None):
        from paddle_tpu.serving.paged_kv import BlockPool
        from paddle_tpu.serving.prefix_cache import PrefixCache

        return PrefixCache(bs, capacity=capacity), BlockPool(blocks)

    def _publish(self, px, pool, prompt):
        n = len(prompt) // px.block
        table = pool.alloc(n + 1)  # +1: the decode tail block
        px.publish(pool, prompt, table)
        return table

    def test_chain_hash_commits_to_whole_prefix(self):
        from paddle_tpu.serving.prefix_cache import chain_hash

        a = chain_hash(0, [1, 2, 3, 4])
        b = chain_hash(a, [5, 6, 7, 8])
        # same second block under a different first block: the chained
        # key differs — block j commits to every token before it
        a2 = chain_hash(0, [9, 2, 3, 4])
        assert chain_hash(a2, [5, 6, 7, 8]) != b
        assert chain_hash(a, [5, 6, 7, 8]) == b  # deterministic

    def test_lookup_partial_and_full_match_plans(self):
        px, pool = self._cache_pool()
        prompt = list(range(10, 22))  # 3 full blocks of 4
        table = self._publish(px, pool, prompt)
        # cold different prompt: miss
        assert px.lookup([1, 2, 3, 4, 5]) is None
        # longer prompt sharing the first 2 blocks: partial match,
        # no CoW, tail starts at the first unshared position
        sh = px.lookup(prompt[:8] + [40, 41, 42, 43, 44])
        assert sh.src_blocks == table[:2]
        assert sh.ref_blocks == table[:2]
        assert sh.cow_src is None and sh.tail_start == 8
        # the exact prompt: full match — last shared block must CoW
        # (the decode loop re-runs the final prompt token's forward)
        sh = px.lookup(list(prompt))
        assert sh.src_blocks == table[:3]
        assert sh.ref_blocks == table[:2]
        assert sh.cow_src == table[2] and sh.tail_start == len(prompt) - 1
        # a prompt diverging INSIDE block 0 misses entirely
        assert px.lookup([99] + prompt[1:]) is None

    def test_publish_refcounts_and_release_on_evict(self):
        px, pool = self._cache_pool()
        prompt = list(range(8))  # 2 full blocks
        table = self._publish(px, pool, prompt)
        assert pool.refcount(table[0]) == 2  # slot + index
        assert pool.refcount(table[1]) == 2
        assert len(px) == 2
        # re-publishing the same chain only touches LRU: no new refs
        px.publish(pool, prompt, table)
        assert pool.refcount(table[0]) == 2
        # the slot retires: blocks survive, held by the index alone
        pool.release(table)
        assert pool.refcount(table[0]) == 1
        free0 = pool.free
        px.clear(pool)
        assert pool.refcount(table[0]) == 0
        assert pool.free == free0 + 2  # both cached entries freed

    def test_eviction_is_lru_and_idle_only(self):
        px, pool = self._cache_pool(blocks=32)
        a = self._publish(px, pool, list(range(0, 8)))
        b = self._publish(px, pool, list(range(100, 108)))
        # `a`'s slot keeps its refs (busy); `b`'s slot retires (idle)
        pool.release(b)
        need = pool.free + 1
        px.evict_for(pool, need)
        # only b's entries were evictable; a's (refcount 2) survived
        assert px.lookup(list(range(0, 8))) is not None
        assert px.lookup(list(range(100, 108))) is None

    def test_capacity_bound_evicts_oldest_subtree(self):
        px, pool = self._cache_pool(blocks=32, capacity=2)
        a = self._publish(px, pool, list(range(0, 8)))
        pool.release(a)  # idle: evictable
        self._publish(px, pool, list(range(100, 108)))
        assert len(px) == 2
        # the oldest (a's) chain was cascaded out root-first: evicting
        # the parent never strands an unreachable child
        assert px.lookup(list(range(0, 8))) is None
        assert px.lookup(list(range(100, 108))) is not None

    def test_poison_forces_miss_never_wrong_kv(self):
        px, pool = self._cache_pool()
        prompt = list(range(8))
        self._publish(px, pool, prompt)
        assert px.lookup(list(prompt)) is not None
        assert px.poison(0) is True
        assert px.poisoned == 1
        # the chain walk computes the TRUE hash and finds nothing: a
        # full prefill, not stale KV
        assert px.lookup(list(prompt)) is None


class TestAdapterSetUnit:
    """Adapter-fleet residency + delta math vs the dense per-slot
    numpy reference (ISSUE 18 pillar 3 units; E2E mixed-batch parity
    lives in test_serving_multitenant.py)."""

    def _fleet(self, n=4, rank=3, scale=0.25):
        from paddle_tpu.serving.adapters import AdapterSet

        m = _tiny_lm()
        return m, AdapterSet(m, n_adapters=n, rank=rank, scale=scale)

    def test_lifecycle_and_id_checks(self, trivial_mesh):
        from paddle_tpu.serving.adapters import AdapterSet

        m, ad = self._fleet()
        assert ad.resident == [0]
        assert ad.is_loaded(0) and not ad.is_loaded(1)
        ad.load(1, seed=11)
        ad.load(3, seed=12)
        assert ad.resident == [0, 1, 3]
        with pytest.raises(ValueError, match="out of range"):
            ad.load(0)  # row 0 is the reserved base row
        with pytest.raises(ValueError, match="out of range"):
            ad.load(4)
        ad.unload(1)
        assert not ad.is_loaded(1)
        with pytest.raises(ValueError, match="n_adapters"):
            AdapterSet(_tiny_lm(), n_adapters=1)

    def test_delta_matches_dense_reference(self, trivial_mesh):
        m, ad = self._fleet()
        ad.load(2, seed=5)
        blk = m.blocks[0]
        rng = np.random.RandomState(0)
        x = rng.normal(size=(3, 4, 32)).astype(np.float32)
        ids = np.array([0, 2, 2], np.int32)
        out = np.asarray(blk._adapter_delta(
            paddle.to_tensor(x), paddle.to_tensor(ids))._data)
        a, b = ad.weights[2][0]
        want = 0.25 * np.einsum(
            "btr,fr->btf", np.einsum("btd,rd->btr", x, a), b)
        assert np.all(out[0] == 0.0)  # id 0 adds EXACT zeros
        assert np.allclose(out[1:], want[1:], atol=1e-5)
        # unloading zeroes the resident rows: the compiled step (which
        # re-reads the same buffers) collapses to the base path
        ad.unload(2)
        out2 = np.asarray(blk._adapter_delta(
            paddle.to_tensor(x), paddle.to_tensor(ids))._data)
        assert np.all(out2 == 0.0)


# ---------------------------------------------------------------------------
# cached_append_attention: one seam for a decode layer's writes and read
# ---------------------------------------------------------------------------


def _seam_operands(shape=(2, 2, 256, 64), sq=1):
    B, H, cap, D = shape
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    c = jax.random.normal(ks[0], shape, jnp.float32)
    q, kn, vn = (jax.random.normal(k, (B, H, sq, D), jnp.float32)
                 for k in ks[1:])
    return (Tensor._wrap(q), Tensor._wrap(c), Tensor._wrap(c + 1),
            Tensor._wrap(kn), Tensor._wrap(vn))


def _seam_decode():
    return _seam_operands()


def _seam_sq_2():
    return _seam_operands(sq=2)


def _seam_heads_48():
    # more heads than the fused kernel turns with the two new rows
    return _seam_operands(shape=(2, 48, 128, 8))


def _seam_quantized():
    from paddle_tpu.distributed import quantized_comm as qc

    q, _, _, kn, vn = _seam_operands()
    p, s = qc.kv_zero((2, 2, 256, 64), "int8")
    cache = qc.QuantKV(Tensor._wrap(p), Tensor._wrap(s))
    return q, cache, cache, kn, vn


def _seam_paged():
    from paddle_tpu.serving import paged_kv as pk

    q, _, _, kn, vn = _seam_operands()
    raw = pk.paged_zero(2, 2, 256, 64, block=128, dtype=jnp.float32)
    cache = pk.PagedKV(Tensor._wrap(raw.kv), Tensor._wrap(raw.table))
    return q, cache, cache, kn, vn


def _arrays(x):
    if isinstance(x, tuple):
        return [a for part in x for a in _arrays(part)]
    return [x._data]


@pytest.mark.parametrize("case,writes,reads", [
    (_seam_decode, {"kernel": 0, "scatter": 0, "fused": 2},
     {"kernel": 1, "dense": 0}),
    (_seam_heads_48, {"kernel": 2, "scatter": 0, "fused": 0},
     {"kernel": 1, "dense": 0}),
    (_seam_sq_2, {"kernel": 0, "scatter": 2, "fused": 0},
     {"kernel": 0, "dense": 1}),
    (_seam_quantized, {"kernel": 0, "scatter": 2, "fused": 0},
     {"kernel": 0, "dense": 1}),
    (_seam_paged, {"kernel": 0, "scatter": 2, "fused": 0},
     {"kernel": 0, "dense": 1}),
], ids=["decode", "heads_48", "sq_2", "quantized", "paged"])
def test_cached_append_attention_routes(case, writes, reads, trivial_mesh,
                                        monkeypatch):
    """With the kernels forced in the interpreter, the decode step's one
    row a slot over a plain float cache is one fused kernel (two rows
    written inside it, one read); more heads than its turn holds keep
    the two kernels it replaces; prefill and speculative steps (Sq > 1),
    `QuantKV` and `PagedKV` keep XLA's write and read. Each gives what
    the three calls give, bit for bit."""
    from paddle_tpu.observability import metrics

    monkeypatch.setenv("PADDLE_FLASH_DEFAULT", "interpret")
    q, kc, vc, kn, vn = case()
    pos = Tensor._wrap(jnp.asarray([3, 127], jnp.int32))
    w0, r0 = metrics.kv_append_routes(), metrics.cached_attention_routes()
    got = attn_route.cached_append_attention(q, kc, vc, kn, vn, pos)
    w1, r1 = metrics.kv_append_routes(), metrics.cached_attention_routes()
    assert {k: w1[k] - w0[k] for k in w1} == writes
    assert {k: r1[k] - r0[k] for k in r1} == reads
    want = _unfused_append_attention(q, kc, vc, kn, vn, pos)
    for a, b in zip(_arrays(got), _arrays(want), strict=True):
        assert a.shape == b.shape and bool((a == b).all())


def test_cached_append_attention_off_the_chip_keeps_xla(trivial_mesh,
                                                       monkeypatch):
    """Without the interpreter forced, the CPU keeps XLA's write and
    dense read."""
    from paddle_tpu.observability import metrics

    monkeypatch.delenv("PADDLE_FLASH_DEFAULT", raising=False)
    q, kc, vc, kn, vn = _seam_decode()
    pos = Tensor._wrap(jnp.asarray([3, 127], jnp.int32))
    w0, r0 = metrics.kv_append_routes(), metrics.cached_attention_routes()
    attn_route.cached_append_attention(q, kc, vc, kn, vn, pos)
    w1, r1 = metrics.kv_append_routes(), metrics.cached_attention_routes()
    assert {k: w1[k] - w0[k] for k in w1} == {
        "kernel": 0, "scatter": 2, "fused": 0}
    assert {k: r1[k] - r0[k] for k in r1} == {"kernel": 0, "dense": 1}
