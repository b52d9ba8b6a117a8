"""The plain reference against `serving.TransformerLM` at a tiny size on
the CPU: the same seeded weights through the program's full forward, and
through its prefill and then its decode step over the engine's cache, and
through the reference give the same logits; the lower precisions that
serve as controls do not."""
import numpy as np
import pytest

import find
import weights as configs

G = find.load("families", "gpt2")
R, W = G.reference, G.weights

CFG = {"vocab_size": 211, "n_positions": 64, "n_embd": 64, "n_layer": 3,
       "n_head": 4, "n_inner": 256, "layer_norm_epsilon": 1e-5,
       "initializer_range": 0.02}


@pytest.fixture(scope="module")
def program_logits():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving import TransformerLM

    s = W.sizes(CFG)
    lm = TransformerLM(s["vocab"], d_model=s["d"], num_heads=s["heads"],
                       num_layers=s["layers"], max_position=s["positions"],
                       dim_feedforward=s["ffn"])
    lm.eval()
    w = W.make(CFG, 9)
    for name, p in lm.named_parameters():
        p._data = w[name]
    ids = np.random.default_rng(0).integers(0, 211, size=(2, 48))
    with jax.default_matmul_precision("highest"):
        out = lm(paddle.to_tensor(ids.astype(np.int32)))
    return ids.astype(np.int32), np.asarray(out._data)


def test_reference_matches_the_program(program_logits):
    ids, want = program_logits
    s = W.sizes(CFG)
    got = np.asarray(R.logits(W.make(CFG, 9, form="stacked"), ids,
                              heads=s["heads"], eps=s["eps"]))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() + 1e-5


def test_controls_differ_from_the_reference(program_logits):
    ids, want = program_logits
    s = W.sizes(CFG)
    p = W.make(CFG, 9, form="stacked")
    for prec in ("bf16", "fp8"):
        low = np.asarray(R.logits(p, ids, heads=s["heads"], eps=s["eps"],
                                  precision=prec))
        assert np.abs(low - want).max() > 1e-3 * np.abs(want).max()


def test_stacked_and_named_weights_agree_and_seeds_differ():
    a, b = W.make(CFG, 9), W.make(CFG, 9, form="stacked")
    assert np.array_equal(np.asarray(a["blocks.2.fc1.weight"]),
                          np.asarray(b["blocks.fc1.weight"][2]))
    c = W.make(CFG, 2 ** 31 + 9)
    assert not np.array_equal(np.asarray(a["head.weight"]),
                              np.asarray(c["head.weight"]))
    parts = W.split_fused({"blocks.0.attn.qkv.bias": a["blocks.0.attn.qkv.bias"]})
    assert sorted(parts) == ["blocks.0.attn.qkv.bias." + x for x in "kqv"]


#: each real configuration at a small size under its own keys: the depth
#: and the widths shrink, the heads keep their kind (a power of two in
#: medium, 5 x 4 in large)
SMALL = {
    "gpt2-medium": {"vocab_size": 211, "n_positions": 64, "n_ctx": 64,
                    "n_embd": 64, "n_layer": 3, "n_head": 4, "n_inner": 256},
    "gpt2-large": {"vocab_size": 211, "n_positions": 64, "n_ctx": 64,
                   "n_embd": 80, "n_layer": 3, "n_head": 5, "n_inner": 320},
}


@pytest.mark.parametrize("config", sorted(SMALL))
def test_prefill_then_decode_through_the_cache_matches_the_reference(config):
    """The configuration's family at a small size: the program's model,
    loaded by the family, through `PrefillStep` and then `DecodeStep` over
    the engine's cache (`generate`, greedy) against the family's reference
    run once over each prompt with its served tokens."""
    import jax

    from paddle_tpu.distributed import comm
    from paddle_tpu.serving.engine import generate

    cfg = dict(configs.load_config(config), **SMALL[config])
    family = find.family(cfg)
    comm.set_hybrid_mesh(None)
    lm = family.serving_model(cfg, {"weights": "float32"}, 9)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 211, size=n).astype(np.int32)
               for n in (19, 30)]
    with jax.default_matmul_precision("highest"):
        tokens, got = generate(lm, prompts, max_new_tokens=12,
                               max_length=64, return_logits=True)
    s = family.sizes(cfg)
    params = family.make(cfg, 9, form="stacked")
    for prompt, toks, lg in zip(prompts, tokens, got):
        ids = np.concatenate([prompt, toks[:-1]])[None]
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
        want = np.asarray(family.reference.logits(
            params, ids, heads=s["heads"], eps=s["eps"]))[0, rows]
        # float32 at `highest` on both sides: what is left is the order of
        # the sums (fused QKV, the cache's padded keys), a few ulp of the
        # largest logit over three layers
        tol = 2e-5 * np.abs(want).max() + 1e-5
        assert np.abs(lg - want).max() < tol
        assert (toks == want.argmax(-1)).all()
        # the bf16 control lies far outside that tolerance
        low = np.asarray(family.reference.logits(
            params, ids, heads=s["heads"], eps=s["eps"],
            precision="bf16"))[0, rows]
        assert np.abs(low - want).max() > 50 * tol
