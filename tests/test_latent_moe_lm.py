"""The latent-attention, routed-expert decoder (`serving.LatentMoELM` and
the layers under `nn.layers.latent`) against the benchmark family's plain
reference (`benchmarks/families/sarvam_mla/reference.py`: float32 at
`highest`, expanded attention, no cache, the held experts a plain loop,
importing nothing of the program) at a small size on the CPU."""
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

F = find.load("families", "sarvam_mla")
REF = sys.modules[F.__name__ + ".reference"]
MODEL = sys.modules[F.__name__ + ".model"]

SEED = 11
PROMPT, NEW, CAP = 70, 10, 128


def _cfg(**over):
    cfg = dict(bench_weights.load_config("sarvam-105b"), **F.TOY_CFG)
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


@pytest.mark.parametrize("chunk", [None, 32], ids=["whole", "chunked"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        ids, chunk):
    """float32 parameters: the expanded blockwise prefill (whole prompt
    or 32-token chunks over 32-row key blocks) and the absorbed decode
    against the reference's full forward. Both sides are float32 at
    `highest`; what is left is the order of the sums (the running softmax
    over key blocks, W_uk moved onto the query in the absorbed form, the
    fused projections), a few ulp of the largest activation over three
    layers: 1e-4 of the largest logit."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "float32"), ids, PROMPT, chunk)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    tol = 1e-4 * np.abs(want).max()
    assert np.abs(got - want).max() < tol
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the fp8 control lies far outside that tolerance
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).max() > 50 * tol


def test_whole_forward_and_absorbed_against_expanded(ids):
    """`model(ids)` (no cache) matches the reference, and the two forms
    of the attention are one function: same rows, same queries, float32."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional import latent as L

    cfg = _cfg()
    lm = _program(cfg, "float32")
    want = _ref_logits(cfg, ids)
    got = np.asarray(lm(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 5, 4, 24)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((2, 64, 40)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 4, 32)) * 0.2, jnp.float32)
    start = jnp.asarray([3, 59], jnp.int32)
    out = {(form, kb): np.asarray(L._attend(
        q, rows, w, start, kv_rank=32, nope=16, scale=0.2, form=form,
        key_block=kb)) for form in ("expanded", "absorbed")
        for kb in (16, 64)}
    base = out["expanded", 64]
    for key, o in out.items():
        assert np.abs(o - base).max() < 1e-5 * np.abs(base).max(), key


def test_bfloat16_earns_its_tolerance_and_fp8_does_not(ids):
    """The cell's precision: bfloat16 parameters and cache through the
    same steps. A bfloat16 product carries 2^-9 of relative rounding and
    three layers of residual sums compound it: the logits stay within 4 %
    of their spread, and the fp8 control (2^-4) lies several times
    outside."""
    cfg = _cfg()
    got = _through_the_cache(_program(cfg, "bfloat16"), ids, PROMPT, 32)
    want = _ref_logits(cfg, ids)[PROMPT - 1:]
    spread = want.std()
    assert np.abs(got - want).max() < 0.04 * spread
    low = _ref_logits(cfg, ids, "fp8")[PROMPT - 1:]
    assert np.abs(low - want).max() > 0.12 * spread


def test_engine_serves_the_references_greedy_tokens(ids):
    """Through `InferenceEngine` itself (admission, chunked prefill
    between decode windows, insert, decode), float32: every served token
    is the reference's best at its position, and the device counters come
    back with the readbacks."""
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
    assert sorted(load) == [1, 2]          # the two routed blocks
    for rows in load.values():
        assert rows.shape == (2, 4 + 1)
        # every assignment is counted: 4 choices a computed token
        assert rows[0].sum() % 4 == 0 and rows[0].sum() >= 4 * (70 + 33 + 90)
        assert rows[1].sum() > 0


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds a quarter of the 16 experts and the whole
    shared expert: the routed parts of the four partial results, and the
    shared expert counted once, add up to the uncut reference layer."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layers.latent import RoutedExperts

    cfg = _cfg(num_experts=16)              # the uncut layer's weights
    s = REF._sizes(cfg)
    p = {k: v.astype(jnp.float32) for k, v in F.make(cfg, SEED).items()}
    b = "blocks.1."
    x = jnp.asarray(np.random.default_rng(2).standard_normal((37, s["d"])),
                    jnp.float32)
    whole, _ = REF.experts(x, p, b, s, "highest")
    shared = REF._ffn(x, p[b + "mlp.shared.gate_up"],
                      p[b + "mlp.shared.down"], "highest")
    total = np.zeros_like(np.asarray(whole))
    for first in (0, 4, 8, 12):
        layer = RoutedExperts(s["d"], s["expert_ffn"], 16, s["top_k"],
                              held=(first, 4), scaling=s["scaling"],
                              shared_hidden=s["shared_ffn"], dtype="float32")
        layer.gate._data = p[b + "mlp.gate"]
        layer.select_bias._data = p[b + "mlp.select_bias"]
        layer.w_in._data = p[b + "mlp.w_in"][first:first + 4]
        layer.w_out._data = p[b + "mlp.w_out"][first:first + 4]
        layer.shared.gate_up._data = p[b + "mlp.shared.gate_up"]
        layer.shared.down._data = p[b + "mlp.shared.down"]
        part = np.asarray(layer(Tensor._wrap(x[None]))._data)[0]
        # the share the reference gives the same chip
        want, _ = REF.experts(
            x, dict(p, **{b + "mlp.w_in": p[b + "mlp.w_in"][first:first + 4],
                          b + "mlp.w_out":
                              p[b + "mlp.w_out"][first:first + 4]}),
            b, s, "highest", first_held=first)
        assert np.abs(part - np.asarray(want)).max() < 1e-5
        total += part - np.asarray(shared)
    total += np.asarray(shared)
    assert np.abs(total - np.asarray(whole)).max() < 1e-5


def test_routing_is_dropless_and_the_bias_selects_but_does_not_weigh():
    from paddle_tpu.nn.functional import latent as L

    rng = np.random.default_rng(3)
    N, D, E, k = 64, 16, 8, 2
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((D, E)) * 0.1, jnp.float32)
    # a bias that sends every token to experts 0 and 1: GShard's capacity
    # (2 N / E * 1.25 = 20 a expert) would drop 44 of each one's 64
    bias = jnp.asarray([9.0, 8.0] + [0.0] * (E - 2), jnp.float32)
    idx, w = L.route_top_k(x, gate, bias, k, 2.5)
    assert (np.sort(np.asarray(idx), -1) == [0, 1]).all()
    s = np.asarray(jax.nn.sigmoid(x @ gate))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    want = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    assert np.abs(np.asarray(w) - want).max() < 1e-6      # no bias in w
    w_in = jnp.asarray(rng.standard_normal((E, D, 2 * 8)) * 0.3, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, 8, D)) * 0.3, jnp.float32)
    y, load = L.routed_experts(x, gate, bias, w_in, w_out, top_k=k,
                               scaling=2.5)
    assert np.asarray(load).tolist() == [N, N] + [0] * (E - 1)
    ffn = [np.asarray(REF._ffn(x, w_in[e], w_out[e], "highest"))
           for e in (0, 1)]
    pos = np.argsort(np.asarray(idx), -1)       # where experts 0, 1 sit
    w0 = np.take_along_axis(want, pos, -1)
    assert np.abs(np.asarray(y) - (w0[:, :1] * ffn[0]
                                   + w0[:, 1:] * ffn[1])).max() < 1e-5


def test_yarn_at_the_published_numbers():
    from paddle_tpu.nn.functional import latent as L

    inv = L.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # the correction dimensions of beta_fast 32 and beta_slow 1 over 4,096
    # positions are 10 and 23: kept below, divided by 40 above, a ramp
    # between
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert (np.diff(inv) < 0).all()
    assert abs(L.yarn_mscale(40.0, 1.0) - 1.3689) < 1e-4
    ref_inv, factor, m = REF.yarn(REF._sizes(_cfg(**{
        "rope_scaling": bench_weights.load_config(
            "sarvam-105b")["rope_scaling"]}))["rope_cfg"], 64)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert factor == 1.0 and abs(m - 1.3689) < 1e-4
    from paddle_tpu.nn.layers.latent import LatentAttention

    attn = LatentAttention(64, 4, nope_dim=128, rope_dim=64, v_dim=16,
                           kv_rank=32, rope=dict(
                               base=10000, factor=40,
                               original_max_position=4096, beta_fast=32,
                               beta_slow=1, mscale=1, mscale_all_dim=1))
    assert abs(attn.scale - 192 ** -0.5 * 1.3689 ** 2) < 1e-4
    assert attn.rope_scale == 1.0


def test_paged_pool_prefix_cache_and_migration_refuse_a_latent_cache():
    from paddle_tpu.nn.functional.latent import LatentCache
    from paddle_tpu.serving import InferenceEngine, kv_migration, paged_kv

    lm = _program(_cfg(), "bfloat16")
    with pytest.raises(NotImplementedError, match="latent cache"):
        InferenceEngine(lm, slots=2, max_length=CAP, block_size=16)
    with pytest.raises(TypeError, match="prefix cache.*latent cache"):
        InferenceEngine(lm, slots=2, max_length=CAP, prefix_cache=True)
    cache = lm.gen_cache(1, CAP)
    assert isinstance(cache[0], LatentCache)
    assert cache[0].rows.shape == [1, CAP, 32 + 16]
    with pytest.raises(TypeError, match="gather_leaves.*latent cache"):
        kv_migration.gather_leaves(cache, [0])
    for fn, args in ((paged_kv.paged_splice, (0, None)),
                     (paged_kv.paged_fetch, (None,)),
                     (paged_kv.paged_splice_tail, (0, None, 0, 0, 0, 0))):
        with pytest.raises(TypeError, match="paged_kv.*latent cache"):
            fn(None, cache[0], *args)
    assert InferenceEngine(lm, slots=2, max_length=CAP).extract_kv(0) is None
