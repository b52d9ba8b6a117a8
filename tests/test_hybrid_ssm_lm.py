"""The state-space hybrid decoder (`serving.HybridSSMLM`, `nn.Mamba2Mixer`,
`nn.GroupedAttention`, the functionals of `nn.functional.ssm`) against the
benchmark family's plain reference (`benchmarks/families/granite_hybrid/
reference.py`: float32 at `highest`, the state-space layer as its
recurrence token by token, no cache, importing nothing of the program) at
the family's toy size on the CPU: two Mamba-2 layers of 4 heads x 32 over
a state of 16 and two attention layers of 4 query heads on 2 K/V heads,
scan chunks of 16 tokens, so prompts of 90 tokens cross six chunks."""
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

F = find.load("families", "granite_hybrid")
REF = sys.modules[F.__name__ + ".reference"]
MODEL = sys.modules[F.__name__ + ".model"]

SEED = 11
PROMPT, NEW, CAP = 90, 10, 128


def _cfg(**over):
    cfg = dict(bench_weights.load_config("granite-4.0-h-micro"), **F.TOY_CFG)
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


def _prefill(lm, ids, n_prompt, chunk):
    """(last logits, cache) of `PrefillStep` over the prompt: whole in a
    CAP-wide bucket, or in `chunk`-token chunks at `start` (the last one
    padded)."""
    from paddle_tpu.jit import PrefillStep

    pre = PrefillStep(lm)
    cache = lm.gen_cache(1, CAP)
    if chunk is None:
        padded = np.zeros((1, CAP), np.int32)
        padded[0, :n_prompt] = ids[:n_prompt]
        last, cache, _ = pre(cache, padded, [n_prompt])
        return last, cache
    for at in range(0, n_prompt, chunk):
        take = min(chunk, n_prompt - at)
        piece = np.zeros((1, chunk), np.int32)
        piece[0, :take] = ids[at:at + take]
        last, cache, _ = pre(cache, piece, [take], start=[at])
    return last, cache


def _through_the_cache(lm, ids, n_prompt, chunk):
    """Logits of positions n_prompt - 1 .. len(ids) - 1: the prefill's
    last row, then teacher-forced `DecodeStep`s over the same cache."""
    from paddle_tpu.jit import DecodeState, DecodeStep

    dec = DecodeStep(lm)
    last, cache = _prefill(lm, ids, n_prompt, chunk)
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


def test_whole_forward_matches_the_reference(ids):
    """float32: the chunked scan from a zero state and the attention over
    a cache of the prompt's own length against the reference's recurrence
    and blockwise attention. What is left is the order of the sums (the
    scan's products inside a chunk, the fused projections): 1e-5 of the
    largest logit (read: 7.8e-7 of it)."""
    import paddle_tpu as paddle

    cfg = _cfg()
    want = _ref_logits(cfg, ids)
    got = np.asarray(_program(cfg, "float32")(
        paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the served tokens depend on their context: the tied head does not
    # answer a token with itself
    assert (want.argmax(-1) == ids).mean() < 0.1


@pytest.mark.parametrize("chunk", [None, 32, 3],
                         ids=["whole", "chunks_padded_last",
                              "chunks_under_d_conv"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        ids, chunk):
    """float32 parameters: the whole prompt in one 128-wide bucket (38
    padded positions), 32-token chunks (the last one 26 valid of 32) or
    3-token chunks (shorter than the convolution's 4 taps, so the carried
    tail reaches back two chunks), then the one-token steps, against the
    reference's full forward at contexts of 90-100 tokens. Same tolerance
    and reason as the whole forward (read: 8.0e-7 to 9.3e-7 of the largest
    logit)."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "float32"), ids, PROMPT, chunk)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    tol = 1e-5 * np.abs(want).max()
    assert np.abs(got - want).max() < tol
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the fp8 control lies far outside that tolerance
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).max() > 100 * tol


@pytest.mark.parametrize("chunk", [32, 7, 3])
def test_a_chunked_prefill_is_one_prefill(ids, chunk):
    """The state, the convolution's tail and the K/V rows a prompt leaves
    behind do not depend on how it was cut: chunks of 32 (the last
    padded), 7 and 3 (under the 4 taps) against one whole prefill in a
    padded bucket, float32, to a few ulp of each leaf's largest value."""
    cfg = _cfg()
    lm = _program(cfg, "float32")
    last0, whole = _prefill(lm, ids, PROMPT, None)
    last1, cut = _prefill(lm, ids, PROMPT, chunk)
    np.testing.assert_allclose(np.asarray(last1), np.asarray(last0),
                               atol=1e-6 * np.abs(np.asarray(last0)).max())
    for a, b in zip(jax.tree_util.tree_leaves(cut),
                    jax.tree_util.tree_leaves(whole)):
        a, b = np.asarray(a), np.asarray(b)
        if a.ndim == 4 and a.shape[2] == CAP:     # K/V rows: the prompt's
            a, b = a[:, :, :PROMPT], b[:, :, :PROMPT]
        np.testing.assert_allclose(a, b, atol=1e-5 * max(np.abs(b).max(),
                                                          1e-30))


def _recurrence(x, dt, A, Bm, Cm, h):
    """The SSD layer's recurrence one token after another, in numpy."""
    ys = []
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t] * A)[..., None, None] * h \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * Bm[:, t, None, None, :]
        ys.append((h * Cm[:, t, None, None, :]).sum(-1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("T,chunk", [(64, 16), (37, 16), (37, 64), (5, 8),
                                     (48, 1)],
                         ids=["whole_chunks", "partial_chunk",
                              "one_partial_chunk", "under_a_chunk",
                              "chunks_of_one"])
def test_the_chunked_scan_is_the_recurrence(T, chunk):
    """`ssd_scan` from a carried state, with the last positions' step
    sizes at zero (a padded row), against the recurrence: the same state
    after them and the same outputs, to float32 rounding of sums over up
    to 64 positions (1e-5 of the largest value)."""
    from paddle_tpu.nn.functional import ssm as S

    rng = np.random.default_rng(T * 100 + chunk)
    B, H, P, N = 2, 3, 4, 5
    x = rng.standard_normal((B, T, H, P)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (B, T, H)).astype(np.float32)
    dt[1, T - 3:] = 0.0                      # padding leaves the state be
    x[1, T - 3:] = 0.0
    A = -rng.uniform(0.5, 4.0, H).astype(np.float32)
    Bm = rng.standard_normal((B, T, N)).astype(np.float32)
    Cm = rng.standard_normal((B, T, N)).astype(np.float32)
    h0 = rng.standard_normal((B, H, P, N)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, h = S.ssd_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm, h0)),
                          chunk=chunk)
    want_y, want_h = _recurrence(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), want_y,
                               atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(h), want_h,
                               atol=1e-5 * np.abs(want_h).max())
    # the padded row's state is the one after its last valid position
    _, at_valid = _recurrence(x[1:, :T - 3], dt[1:, :T - 3], A,
                              Bm[1:, :T - 3], Cm[1:, :T - 3], h0[1:])
    np.testing.assert_allclose(np.asarray(h)[1], at_valid[0],
                               atol=1e-5 * np.abs(at_valid).max())
    # one step is the recurrence's first
    y1, h1 = S.ssd_step(*map(jnp.asarray, (x[:, 0], dt[:, 0], A, Bm[:, 0],
                                            Cm[:, 0], h0)))
    w1, wh1 = _recurrence(x[:, :1], dt[:, :1], A, Bm[:, :1], Cm[:, :1], h0)
    np.testing.assert_allclose(np.asarray(y1), w1[:, 0], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), wh1, rtol=1e-5, atol=1e-6)


def test_bfloat16_earns_its_band_and_fp8_does_not(ids):
    """The cell's precision: bfloat16 parameters, bfloat16 K/V and
    convolution tail, the state float32, through 16-token chunks and the
    steps. The mean gap of a logit stays within 3 % of the logits' spread
    (read 0.77 %, 0.88 % and 0.82 % on three prompts) and the fp8 control
    lies outside 6 % (read 10.3 %, 11.8 % and 12.9 %)."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "bfloat16"), ids, PROMPT, 16)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    spread = want.std()
    assert np.abs(got - want).mean() < 0.03 * spread
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).mean() > 0.06 * spread


@pytest.mark.parametrize("seed", [7, 1, 2])
def test_a_bfloat16_state_leaves_the_float32_band(monkeypatch, seed):
    """The planted fault `bf16_state` of `benchmarks/tests/
    broken_longgen.py` (the state rounded to bfloat16 each time a scan or
    a step hands it on), on three seeds of weights and prompt: float32
    parameters through 16-token chunks and the steps leave the band the
    sound program holds (1e-5 of the largest logit, as
    `test_prefill_then_decode_through_the_cache_matches_the_reference`):
    read 6.5e-4 to 1.8e-3 of it on seeds 1-5 and 7, the sound program
    7.7e-7 to 1.2e-6; the limit asks ten times the band. At this size the fault seldom flips a served token, so the rehearsal's
    comparison of served tokens alone cannot be relied on to see it."""
    sys.path.insert(0, os.path.join(_BENCH, "tests"))
    import broken_longgen

    cfg = _cfg()
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=PROMPT + NEW).astype(np.int32)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    tol = 1e-5 * np.abs(want).max()
    sound = _through_the_cache(_program(cfg, "float32"), ids, PROMPT, 16)
    with monkeypatch.context() as m:
        jax.clear_caches()     # the mixers' programs were traced sound
        broken_longgen.plant("bf16_state", m.setattr)
        got = _through_the_cache(_program(cfg, "float32"), ids, PROMPT, 16)
    jax.clear_caches()         # nor may a later test reuse the fault's
    assert np.abs(sound - want).max() < tol
    assert np.abs(got - want).max() > 10 * tol


def test_the_engine_reuses_a_slot_and_serves_the_references_tokens(ids):
    """Through `InferenceEngine` itself (admission, chunked prefill
    between decode windows, insert, the steps), float32, 2 slots and 4
    requests, so two requests take a slot another one left: a state or a
    tail left behind would show. Every served token is the reference's
    best at its position, and the reference's logit of it lies within
    1e-5 of the largest logit of its best (the program's float32
    rounding). Both shapes of the state-space call were traced."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import InferenceEngine, Request

    cfg = _cfg()
    before = metrics.ssm_routes()
    lm = _program(cfg, "float32")
    eng = InferenceEngine(lm, slots=2, max_length=CAP, prefill_chunk=32,
                          sync_every=3)
    reqs = [Request(ids[:n], max_new_tokens=m)
            for n, m in ((70, 9), (33, 4), (90, 8), (21, 6))]
    for r in reqs:
        eng.submit(r)
    out = eng.run()
    assert (eng._decode.compiles, eng._prefill.compiles) == (1, 1)
    after = metrics.ssm_routes()
    assert after["scan"] > before["scan"] and after["step"] > before["step"]
    for r in reqs:
        toks = np.asarray(out[r.rid].tokens)
        assert len(toks) == r.max_new_tokens
        seq = np.concatenate([r.prompt_ids, toks])
        want = _ref_logits(cfg, seq)[len(r.prompt_ids) - 1:-1]
        best = want.max(-1)
        served = want[np.arange(len(toks)), toks]
        assert (best - served <= 1e-5 * np.abs(want).max()).all()


@pytest.mark.parametrize("where", ["paged_pool", "prefix_cache", "migration",
                                   "speculative"])
def test_a_state_cache_is_refused_by_name(where):
    """Blocks, shared prefixes, migration bundles and a speculative
    step's roll-back by position are all made for rows a token: each
    refuses a layer's `SSMState` by name, and a state cache is what the
    model's cache holds."""
    from paddle_tpu.jit import SpecDecodeState, SpeculativeDecodeStep
    from paddle_tpu.nn.functional.ssm import SSMState
    from paddle_tpu.serving import InferenceEngine, kv_migration

    lm = _program(_cfg(), "float32")
    cache = lm.gen_cache(1, CAP)
    assert isinstance(cache[0], SSMState)
    assert [list(c.shape) for c in cache[0]] == [[1, 4, 32, 16],
                                                 [1, 3, 4 * 32 + 2 * 16]]
    if where == "paged_pool":
        with pytest.raises(TypeError, match="paged pool.*state cache"):
            InferenceEngine(lm, slots=2, max_length=CAP, block_size=16)
    elif where == "prefix_cache":
        with pytest.raises(TypeError, match="prefix cache.*state cache"):
            InferenceEngine(lm, slots=2, max_length=CAP, prefix_cache=True)
    elif where == "migration":
        with pytest.raises(TypeError, match="gather_leaves.*state cache"):
            kv_migration.gather_leaves(cache, [0])
    else:
        step = SpeculativeDecodeStep(lm, lm, k=2)
        state = SpecDecodeState.make(cache, lm.gen_cache(1, CAP), [0], [4])
        with pytest.raises(TypeError, match="Speculative.*state cache"):
            step(state)


def test_the_valid_length_reaches_only_a_model_that_asks():
    """`PrefillStep` hands `length` to a model that declares
    `takes_length` and to no other: a model whose forward has no such
    keyword keeps its call, and its program."""
    from paddle_tpu import nn
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import PrefillStep

    seen = {}

    class Plain(nn.Layer):
        def __init__(self):
            super().__init__()
            self.w = self.create_parameter(shape=[2], dtype="float32")

        def forward(self, ids, cache=None, pos=None):
            seen["plain"] = True
            out = ids.astype("float32").unsqueeze(-1) * self.w
            return out, [c + 1 for c in cache]

    class Asks(Plain):
        takes_length = True

        def forward(self, ids, cache=None, pos=None, length=None):
            seen["length"] = length
            return super().forward(ids, cache, pos)

    cache = [Tensor._wrap(jnp.zeros((1, 3)))]
    PrefillStep(Plain())(cache, np.ones((1, 4), np.int32), [3])
    assert seen == {"plain": True}
    PrefillStep(Asks())([Tensor._wrap(jnp.zeros((1, 3)))],
                        np.ones((1, 4), np.int32), [3])
    assert seen["length"] is not None


@pytest.mark.parametrize("Sq,cap,pos", [
    (3, 16, (4, 9)),            # one tile of keys
    (1, 1536, (0, 1300)),       # a decode step over 3 tiles; slot 0 sees 1
    (5, 1024, (509, 700)),      # a chunk whose queries cross a tile
], ids=["one_tile", "decode_tiles", "chunk_tiles"])
def test_grouped_queries_read_their_kv_head(Sq, cap, pos):
    """`cached_attention` with 6 query heads on 2 K/V heads (head j reads
    K/V head j // 3) is the plain form with each K/V head repeated, and
    keeps the mask by position, whether the keys are one tile or several
    (an online softmax over the tiles up to the furthest query)."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.functional.attention import cached_attention

    rng = np.random.default_rng(0)
    B, H, G, D = 2, 6, 2, 8
    q = rng.standard_normal((B, H, Sq, D)).astype(np.float32)
    k = rng.standard_normal((B, G, cap, D)).astype(np.float32)
    v = rng.standard_normal((B, G, cap, D)).astype(np.float32)
    pos = np.asarray(pos, np.int32)
    w = lambda a: Tensor._wrap(jnp.asarray(a))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cached_attention(w(q), w(k), w(v), w(pos),
                                          scale=0.2)._data)
        want = np.asarray(cached_attention(
            w(q), w(np.repeat(k, H // G, 1)), w(np.repeat(v, H // G, 1)),
            w(pos), scale=0.2)._data)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_counts_at_the_published_sizes():
    """The configuration's sizes and the family's counts: 3,191.4 M
    parameters (6.38 GB in bfloat16), a cached token 8,192 B, a slot's
    state 76.4 MB, a 32-slot step's state update 4.91 GB."""
    cfg = bench_weights.load_config("granite-4.0-h-micro")
    s = F.sizes(cfg)
    assert (s["mamba_layers"], s["attention_layers"]) == (36, 4)
    assert (s["head_dim"], s["d_inner"], s["conv_dim"]) == (64, 4096, 4352)
    assert F.total_params(s) == 3_191_396_096
    assert F.kv_bytes_per_token(s) == 8192
    assert F.state_bytes_per_slot(s) == 36 * (64 * 64 * 128 * 4
                                              + 3 * 4352 * 2)
    assert F.ssm_step_bytes(s, 32) == 4_912_349_184
    names = [n for n, _, _ in sys.modules[F.__name__ + ".weights"].leaves(s)]
    lm_names = ["embedding.weight", "norm_f.weight"]
    assert names[0] == lm_names[0] and names[-1] == lm_names[1]
    assert len(names) == 2 + 36 * 12 + 4 * 6
