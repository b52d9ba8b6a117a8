"""The learned-sparse-attention, routed-expert decoder (`serving.SparseMoELM`,
`nn.IndexedAttention`, the functionals of `nn.functional.dsa`, softmax
scoring in `nn.RoutedExperts`) against the benchmark family's plain
reference (`benchmarks/families/keye_dsa/reference.py`: float32 at
`highest`, `jax.lax.top_k` for both selections, no cache, attention over
gathered rows, the held experts a plain loop, importing nothing of the
program) at a small size on the CPU: contexts of 100 tokens over a `topk`
of 16 and key tiles of 32, so the selection bites and the blockwise forms
walk four tiles."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
for _p in (_BENCH, _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import find  # noqa: E402
import weights as bench_weights  # noqa: E402

F = find.load("families", "keye_dsa")
REF = sys.modules[F.__name__ + ".reference"]
MODEL = sys.modules[F.__name__ + ".model"]

SEED = 11
PROMPT, NEW, CAP = 90, 10, 128
TOPK = F.TOY_CFG["sa_config"]["topk"]


def _cfg(**over):
    cfg = dict(bench_weights.load_config("keye-vl-2.0-30b-a3b"), **F.TOY_CFG)
    cfg.update(over)
    return cfg


def _program(cfg, dtype):
    from paddle_tpu.distributed import comm

    comm.set_hybrid_mesh(None)
    lm = MODEL.build(cfg, dtype=dtype)
    MODEL.load(lm, F.make(cfg, SEED))
    lm.eval()
    return lm


def _ref_logits(cfg, ids, precision="highest"):
    s = REF._sizes(cfg, attn_block=32)
    return np.asarray(REF.logits(F.make(cfg, SEED), jnp.asarray(ids), s,
                                 precision))


def _through_the_cache(lm, ids, n_prompt, chunk):
    """Logits of positions n_prompt - 1 .. len(ids) - 1 from `PrefillStep`
    (whole, or in `chunk`-token chunks at `start`) and then teacher-forced
    `DecodeStep`s over the same cache."""
    from paddle_tpu.jit import DecodeState, DecodeStep, PrefillStep

    pre, dec = PrefillStep(lm), DecodeStep(lm)
    cache = lm.gen_cache(1, CAP)
    if chunk is None:
        padded = np.zeros((1, CAP), np.int32)
        padded[0, :n_prompt] = ids[:n_prompt]
        last, cache, _ = pre(cache, padded, [n_prompt])
    else:
        for at in range(0, n_prompt, chunk):
            take = min(chunk, n_prompt - at)
            piece = np.zeros((1, chunk), np.int32)
            piece[0, :take] = ids[at:at + take]
            last, cache, _ = pre(cache, piece, [take], start=[at])
    rows = [np.asarray(last)[0]]
    state = DecodeState.make(cache, [0], [n_prompt])
    for t in range(n_prompt, len(ids)):
        state.tok = jnp.asarray([ids[t]], jnp.int32)
        _, logits, state = dec(state)
        rows.append(np.asarray(logits)[0])
    return np.stack(rows)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(
        0, F.TOY_CFG["vocab_size"], size=PROMPT + NEW).astype(np.int32)


@pytest.mark.parametrize("chunk", [None, 32, 8],
                         ids=["whole", "chunks_over_topk",
                              "chunks_under_topk"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        ids, chunk):
    """float32 parameters: the masked blockwise prefill (the whole prompt
    in one 128-wide bucket, 32-token chunks, 8-token chunks shorter than
    `topk`) and the gathering decode step against the reference's full
    forward, at contexts of 90-100 tokens: five to six times `topk`. Both
    sides are float32 at `highest` and select the same sets; what is left
    is the order of the sums (the running softmax over key tiles, the
    fused projections), a few ulp of the largest activation over two
    layers: 1e-5 of the largest logit (read: 2.3e-7 of it)."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "float32"), ids, PROMPT, chunk)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    tol = 1e-5 * np.abs(want).max()
    assert np.abs(got - want).max() < tol
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the fp8 control lies far outside that tolerance
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).max() > 1000 * tol


def test_whole_forward_matches_the_reference(ids):
    import paddle_tpu as paddle

    cfg = _cfg()
    want = _ref_logits(cfg, ids)
    got = np.asarray(_program(cfg, "float32")(
        paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def _attention_inputs(rng, B, T, S, H=8, G=2, Dh=16, Hi=4, Di=16):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    from paddle_tpu.nn.functional.dsa import IndexedKVCache

    return (f(B, T, H, Dh), f(B, T, Hi, Di), f(B, T, Hi),
            IndexedKVCache(f(B, S, G * Dh), f(B, S, G * Dh), f(B, S, Di)))


def _attend(q, qi, w, cache, start, **kw):
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.functional import dsa as S

    wrap = Tensor._wrap
    out, keys = S.indexed_attention(
        wrap(q), wrap(qi), wrap(w), S.IndexedKVCache(*map(wrap, cache)),
        wrap(jnp.asarray(start, jnp.int32)), kv_heads=2, scale=0.25, **kw)
    return np.asarray(out._data), np.asarray(keys._data)


def _plain_attention(q, qi, w, cache, start, topk, head_map):
    """A loop a (slot, query): numpy, `jax.lax.top_k` for the set."""
    q, qi, w = (np.asarray(a, np.float64) for a in (q, qi, w))
    k, v, ix = (np.asarray(a, np.float64) for a in cache)
    B, T, H, Dh = q.shape
    out = np.zeros((B, T, H, Dh))
    for b in range(B):
        for t in range(T):
            n = start[b] + t + 1
            score = (np.maximum(qi[b, t] @ ix[b, :n].T, 0)
                     * w[b, t][:, None]).sum(0).astype(np.float32)
            _, sel = jax.lax.top_k(jnp.asarray(score), min(topk, n))
            sel = np.asarray(sel)
            for j in range(H):
                g = head_map(j)
                kk = k[b, sel, g * Dh:(g + 1) * Dh]
                vv = v[b, sel, g * Dh:(g + 1) * Dh]
                a = kk @ q[b, t, j] * 0.25
                a = np.exp(a - a.max())
                out[b, t, j] = (a / a.sum()) @ vv
    return out


def test_chunk_form_and_decode_form_are_one_function_and_heads_are_grouped():
    """The masked form (a chunk of 24 queries a slot over 128 rows in
    tiles of 32, and as one dense tile) and the gather form (each of the
    same queries alone) give what a plain loop gives, at two slots with
    different starts; query head j reads K/V head j // 4, and a loop
    that reads head j mod 2 instead lies far off."""
    rng = np.random.default_rng(0)
    start = np.array([30, 97])
    q, qi, w, cache = _attention_inputs(rng, 2, 24, 128)
    want = _plain_attention(q, qi, w, cache, start, TOPK, lambda j: j // 4)
    for kb in (32, 128):
        got, keys = _attend(q, qi, w, cache, start, topk=TOPK, key_block=kb)
        assert np.abs(got - want).max() < 1e-5
        visible = sum(s + t + 1 for s in start for t in range(24))
        assert keys.tolist() == [visible, 2 * 24 * TOPK]
    for t in (0, 11, 23):
        one, keys = _attend(q[:, t:t + 1], qi[:, t:t + 1], w[:, t:t + 1],
                            cache, start + t, topk=TOPK, key_block=32)
        assert np.abs(one[:, 0] - want[:, t]).max() < 1e-5
        assert keys.tolist() == [int((start + t + 1).sum()), 2 * TOPK]
    wrong = _plain_attention(q, qi, w, cache, start, TOPK, lambda j: j % 2)
    assert np.abs(wrong - want).max() > 0.1


@pytest.mark.parametrize("start", [(0, 70), (120, 85)])
def test_the_selected_set_is_top_ks_with_ties(start):
    """Scores on a grid of halves (every row ties many times over, at the
    threshold too, and holds both zeros' signs after `_score_tile`'s
    rule): the set `kth_largest` marks, read as the masked form reads it
    (above the k-th value, and of its equals the first `room` by
    position), is `jax.lax.top_k`'s, for queries that see fewer than k
    keys, exactly k and many more, over five blocks of which the last
    is never visited (first case) or every one is (second)."""
    from paddle_tpu.nn.functional import dsa as S

    old, S.COUNT_BLOCK = S.COUNT_BLOCK, 32
    try:
        rng = np.random.default_rng(1)
        B, T, n, k = 2, 40, 160, 16
        start = np.array(start)
        score = (np.round(rng.standard_normal((B, T, n)) * 2) / 2).astype(
            np.float32)
        score = np.where(score == 0, np.float32(0), score)
        qpos = start[:, None] + np.arange(T)[None]
        score = np.where(np.arange(n)[None, None] > qpos[..., None],
                         -np.inf, score).astype(np.float32)
        u = S._ukey(jnp.asarray(score))
        v, room = S.kth_largest(u, k, jnp.asarray(start, jnp.int32))
        u, v, room = (np.asarray(a) for a in (u, v, room))
        eq = u == v[..., None]
        got = (u > v[..., None]) | (eq & (np.cumsum(eq, -1) - eq
                                          < room[..., None]))
        _, idx = jax.lax.top_k(jnp.asarray(score), k)
        want = np.zeros_like(got)
        np.put_along_axis(want, np.asarray(idx), True, -1)
        # nearly every row ties at its threshold
        assert (eq.sum(-1) > 1).mean() > 0.9 and (room >= 1).all()
        assert (got == want).all()
    finally:
        S.COUNT_BLOCK = old
    # monotone keys: the order of the scores is the order of the keys
    x = jnp.asarray([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf],
                    jnp.float32)
    assert (np.diff(np.asarray(S._ukey(x)).astype(np.int64)) > 0).all()


def test_a_zero_score_has_one_sign():
    """Every head at rest under negative weights sums to -0.0: the score
    is given as 0.0, so a sort that tells the zeros apart cannot move
    the set."""
    from paddle_tpu.nn.functional import dsa as S

    qi = -jnp.ones((1, 1, 2, 4), jnp.float32)
    rows = jnp.ones((1, 3, 4), jnp.float32)
    s = S._score_tile(qi, -jnp.ones((1, 1, 2), jnp.float32), rows)
    assert not np.signbit(np.asarray(s)).any() and (np.asarray(s) == 0).all()


def test_softmax_routing_against_a_plain_loop_and_sigmoid_unchanged():
    from paddle_tpu.nn.functional import latent as L

    rng = np.random.default_rng(3)
    N, D, E, k = 48, 16, 8, 3
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((D, E)) * 0.3, jnp.float32)
    idx, w = L.route_top_k(x, gate, None, k, 1.0, "softmax")
    z = np.asarray(x @ gate, np.float64)
    for t in range(N):
        p = np.exp(z[t] - z[t].max())
        p /= p.sum()
        best = np.argsort(-p, kind="stable")[:k]
        assert sorted(np.asarray(idx)[t]) == sorted(best)
        want = p[np.asarray(idx)[t]] / p[best].sum()
        assert np.abs(np.asarray(w)[t] - want).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 1).max() < 1e-6
    # the default rule is the sigmoid one, to the last bit
    bias = jnp.asarray(rng.standard_normal(E) * 0.1, jnp.float32)
    i0, w0 = L.route_top_k(x, gate, bias, k, 2.5)
    i1, w1 = L.route_top_k(x, gate, bias, k, 2.5, "sigmoid")
    s = jax.nn.sigmoid(jnp.dot(x, gate,
                               preferred_element_type=jnp.float32))
    _, i2 = jax.lax.top_k(s + bias, k)
    c = jnp.take_along_axis(s, i2, -1)
    for i, ww in ((i0, w0), (i1, w1)):
        assert (np.asarray(i) == np.asarray(i2)).all()
        assert (np.asarray(ww)
                == np.asarray(2.5 * c / c.sum(-1, keepdims=True))).all()
    w_in = jnp.asarray(rng.standard_normal((E, D, 2 * 8)) * 0.3, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, 8, D)) * 0.3, jnp.float32)
    y, load = L.routed_experts(x, gate, None, w_in, w_out, top_k=k,
                               scaling=1.0, score="softmax")
    assert int(np.asarray(load).sum()) == N * k and np.asarray(load)[-1] == 0
    want = np.zeros((N, D))
    for t in range(N):
        for e, we in zip(np.asarray(idx)[t], np.asarray(w)[t]):
            want[t] += we * np.asarray(REF._ffn(x[t:t + 1], w_in[e],
                                                w_out[e], "highest"))[0]
    assert np.abs(np.asarray(y) - want).max() < 1e-5
    with pytest.raises(ValueError, match="score"):
        from paddle_tpu.nn.layers.latent import RoutedExperts

        RoutedExperts(D, 8, E, k, score="tanh")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds a quarter of the 16 experts (there is no
    shared expert): the routed parts of the four partial results add up
    to the uncut reference layer, and each is the share the reference
    gives the same chip."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layers.latent import RoutedExperts

    cfg = _cfg(num_experts=16)              # the uncut layer's weights
    s = REF._sizes(cfg)
    p = {k: v.astype(jnp.float32) for k, v in F.make(cfg, SEED).items()}
    b = "blocks.1."
    x = jnp.asarray(np.random.default_rng(2).standard_normal((37, s["d"])),
                    jnp.float32)
    whole, _ = REF.experts(x, p, b, s, "highest")
    total = np.zeros_like(np.asarray(whole))
    for first in (0, 4, 8, 12):
        layer = RoutedExperts(s["d"], s["expert_ffn"], 16, s["top_k"],
                              held=(first, 4), score="softmax",
                              select_bias=False, dtype="float32")
        layer.gate._data = p[b + "mlp.gate"]
        layer.w_in._data = p[b + "mlp.w_in"][first:first + 4]
        layer.w_out._data = p[b + "mlp.w_out"][first:first + 4]
        part = np.asarray(layer(Tensor._wrap(x[None]))._data)[0]
        want, _ = REF.experts(
            x, dict(p, **{b + "mlp.w_in": p[b + "mlp.w_in"][first:first + 4],
                          b + "mlp.w_out":
                              p[b + "mlp.w_out"][first:first + 4]}),
            b, s, "highest", first_held=first)
        assert np.abs(part - np.asarray(want)).max() < 1e-5
        total += part
    assert np.abs(np.asarray(whole)).max() > 1e-3
    assert np.abs(total - np.asarray(whole)).max() < 1e-5


def test_bfloat16_earns_its_band_and_fp8_does_not(ids):
    """The cell's precision: bfloat16 parameters and cache through the
    same steps. With 16 keys a query, one near-tied selection that
    bfloat16 rounds the other way moves a sixteenth of a query's
    attention, so the *largest* gap of a logit is no measure here (read:
    0.52 of the logits' spread, fp8 0.87); the mean gap is: bfloat16
    stays within 5 % of the spread (read 1.8 %; 2.3 % and 4.0 % on two
    other prompts) and the fp8 control lies outside 10 % (read 13.9 %;
    13.2 % and 17.1 %)."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "bfloat16"), ids, PROMPT, 32)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    spread = want.std()
    assert np.abs(got - want).mean() < 0.05 * spread
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).mean() > 0.10 * spread


def test_engine_serves_the_references_greedy_tokens_and_counts_keys(ids):
    """Through `InferenceEngine` itself (admission, chunked prefill
    between decode windows, insert, decode), float32: every served token
    is the reference's best at its position, and both kinds of device
    counter come back with the readbacks. A second engine whose contexts
    stay under `topk` selects every key it sees."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import InferenceEngine, Request

    cfg = _cfg()
    lm = _program(cfg, "float32")
    eng = InferenceEngine(lm, slots=2, max_length=CAP, prefill_chunk=32)
    reqs = [Request(ids[:n], max_new_tokens=8) for n in (70, 33, 90)]
    for r in reqs:
        eng.submit(r)
    out = eng.run()
    assert (eng._decode.compiles, eng._prefill.compiles) == (1, 1)
    for r in reqs:
        toks = np.asarray(out[r.rid].tokens)
        seq = np.concatenate([r.prompt_ids, toks])
        want = _ref_logits(cfg, seq)[len(r.prompt_ids) - 1:-1].argmax(-1)
        assert (toks == want).all()
    load = metrics.expert_load()
    assert sorted(load) == [0, 1]           # every block is routed
    for rows in load.values():
        assert rows.shape == (2, 4 + 1)
        assert rows[0].sum() % 4 == 0 and rows[0].sum() >= 4 * (70 + 33 + 90)
    keys = metrics.selected_keys()
    assert sorted(keys) == [0, 1]
    for rows in keys.values():
        assert rows.shape == (2, 2) and rows.dtype == np.int64
        visible, selected = rows[:, 0], rows[:, 1]
        assert (0 < selected).all() and (selected < visible).all()
        # a chunk of 32 queries counts whole: 3 + 2 + 3 chunks
        assert selected[0] <= 8 * 32 * TOPK
    assert (keys[0] == keys[1]).all()       # the layers see the same queries
    short = InferenceEngine(_program(cfg, "float32"), slots=2, max_length=CAP)
    short.submit(Request(ids[:6], max_new_tokens=TOPK - 8))
    short.run()
    for rows in metrics.selected_keys().values():
        # a 16-wide prefill bucket and decode positions 6 .. 13: no query
        # sees more than topk = 16 keys
        assert (rows[:, 0] == rows[:, 1]).all() and rows[1, 0] > 0


def test_a_wide_counter_does_not_wrap():
    from paddle_tpu.nn.functional import dsa as S

    c = jnp.zeros((2, 2), jnp.int32)
    step = jnp.asarray([(1 << 30) - 1, 12345], jnp.int32)
    for _ in range(9):
        c = S.advance_wide(c, step)
    assert S.read_wide(c).tolist() == [9 * ((1 << 30) - 1), 9 * 12345]
    assert int(np.asarray(c)[:, 1].max()) < 1 << 30


def test_paged_pool_prefix_cache_and_migration_refuse_an_indexed_cache():
    from paddle_tpu.nn.functional.dsa import IndexedKVCache
    from paddle_tpu.serving import InferenceEngine, kv_migration, paged_kv

    lm = _program(_cfg(), "bfloat16")
    with pytest.raises(NotImplementedError, match="indexed cache"):
        InferenceEngine(lm, slots=2, max_length=CAP, block_size=16)
    with pytest.raises(TypeError, match="prefix cache.*indexed cache"):
        InferenceEngine(lm, slots=2, max_length=CAP, prefix_cache=True)
    cache = lm.gen_cache(1, CAP)
    assert isinstance(cache[0], IndexedKVCache)
    assert [c.shape for c in cache[0]] == [[1, CAP, 2 * 16], [1, CAP, 2 * 16],
                                          [1, CAP, 16]]
    with pytest.raises(TypeError, match="gather_leaves.*indexed cache"):
        kv_migration.gather_leaves(cache, [0])
    for fn, args in ((paged_kv.paged_splice, (0, None)),
                     (paged_kv.paged_fetch, (None,)),
                     (paged_kv.paged_splice_tail, (0, None, 0, 0, 0, 0))):
        with pytest.raises(TypeError, match="paged_kv.*indexed cache"):
            fn(None, cache[0], *args)
    assert InferenceEngine(lm, slots=2, max_length=CAP).extract_kv(0) is None


def test_the_plan_and_the_counts():
    from paddle_tpu.nn.functional.dsa import indexed_attend_plan

    assert indexed_attend_plan(2048, 32768, 512) == ("masked", "blockwise")
    assert indexed_attend_plan(1, 32768, 512) == ("gather", "dense")
    assert indexed_attend_plan(24, 100, 32) == ("masked", "dense")
    s = F.sizes(bench_weights.load_config("keye-vl-2.0-30b-a3b"))
    # the issue's own arithmetic: 1,017.6 M parameters, 10,880 B a token
    assert F.total_params(s) == 1_017_569_152
    assert F.kv_bytes_per_token(s) == 10_880
    # a prompt of 3,000 tokens with 3 tokens out: queries 0 .. 3,001
    visible, selected = F.request_pairs(s, 3000, 3)
    assert visible == 3002 * 3003 // 2
    assert selected == 2048 * 2049 // 2 + (3002 - 2048) * 2048
    assert F.request_pairs(s, 3000, 0) == (0, 0)
