"""The program's model of a `granite_hybrid` configuration:
`serving.HybridSSMLM` (Mamba-2 mixers beside grouped-query attention with
no position encoding, a dense gated MLP in every layer), loaded with the
seed's weights a leaf at a time."""
from __future__ import annotations

from . import weights

#: the values of a serving mix's `weights` that this family has proven on
#: the chip, with limits and a control (PERF.md)
PROVEN_WEIGHTS = ("bfloat16",)


def build(cfg: dict, dtype: str = "bfloat16"):
    """An unloaded `HybridSSMLM` of the configuration's sizes (zeros)."""
    from paddle_tpu.serving import HybridSSMLM  # a program without it stops here

    from paddle_tpu.nn import ParamAttr
    from paddle_tpu.nn.initializer import Constant

    s = weights.sizes(cfg)
    return HybridSSMLM(
        s["vocab"], s["d"], s["layer_types"], num_heads=s["heads"],
        kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        ssm_heads=s["ssm_heads"], ssm_head_dim=s["ssm_head_dim"],
        state_dim=s["state"], conv_kernel=s["conv_kernel"], ffn=s["ffn"],
        embedding_multiplier=s["embedding_multiplier"],
        residual_multiplier=s["residual_multiplier"],
        attention_multiplier=s["attention_multiplier"],
        logits_scaling=s["logits_scaling"], chunk=s["chunk"],
        max_position=s["positions"], epsilon=s["eps"], dtype=dtype,
        weight_attr=ParamAttr(initializer=Constant(0.0)))


def load(lm, leaves, dtype=None) -> None:
    """Give every parameter of `lm` the leaf of its name; `leaves` is a
    dict or an iterator of (name, array), taken one at a time."""
    named = dict(lm.named_parameters())
    seen = set()
    for name, w in (leaves.items() if isinstance(leaves, dict) else leaves):
        if name not in named:
            raise KeyError(f"the seed has a leaf {name!r} the model lacks")
        p = named[name]
        if tuple(w.shape) != tuple(p._data.shape):
            raise ValueError(f"{name}: {w.shape} for {p._data.shape}")
        p._data = w.astype(dtype or p._data.dtype)
        seen.add(name)
    if seen != set(named):
        raise KeyError(f"the model has leaves the seed lacks: "
                       f"{sorted(set(named) - seen)}")


def serving_model(cfg: dict, mix: dict, seed: int):
    """The `Layer` a serving driver hands to `InferenceEngine`."""
    # the toy of `--rehearse` names its own precision (`TOY_CFG` says why)
    lm = build(cfg, dtype=cfg.get("rehearsal_weights", "bfloat16"))
    if mix["weights"] not in PROVEN_WEIGHTS:
        raise ValueError("only bfloat16 serving of this family has run on "
                         "this chip; a mix in another precision needs its "
                         "own proof")
    load(lm, weights.each_leaf(cfg, seed))
    lm.eval()
    assert_routes(lm, cfg, mix, rehearse=False)
    return lm


def assert_routes(model, cfg: dict, mix: dict, rehearse: bool) -> None:
    """Set-up fails if the scan's chunk is not the configuration's, or if
    the cell's `PrefillStep` (a chunk of one slot) would not run the
    chunked scan in every state-space layer or its `DecodeStep` (every
    slot) the one-token step: `observability.metrics.ssm_routes()` is
    read around a trace of each step's own function at the cell's shapes
    (`jax.eval_shape`: nothing is compiled, run or allocated)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit import DecodeState, DecodeStep, PrefillStep
    from paddle_tpu.jit.decode_step import _raw_tree
    from paddle_tpu.observability import metrics

    s = weights.sizes(cfg)
    eng = mix["engine"]
    mamba = s["layer_types"].count("mamba")
    chunks = {b.attn.chunk for b, kind in zip(model.blocks, s["layer_types"])
              if kind == "mamba"}
    if chunks != {s["chunk"]}:
        raise RuntimeError(f"the scan runs chunks of {sorted(chunks)}, the "
                           f"configuration {s['chunk']}")
    cap = int(eng["max_length"])
    chunk = int(eng.get("prefill_chunk") or cap)
    slots = int(eng["slots"])

    def routes(step, args):
        p = tuple(q._data for q in step._p_objs)
        b = tuple(q._data for q in step._b_objs)
        before = metrics.ssm_routes()
        jax.eval_shape(lambda p, b: step._step_fn(p, b, *args()), p, b)
        after = metrics.ssm_routes()
        return {k: after[k] - before[k] for k in after}

    def prefill_args():
        one = jnp.zeros((1,), jnp.int32)
        return (_raw_tree(model.gen_cache(1, cap, block_size=0)),
                jnp.zeros((1, chunk), jnp.int32), one + chunk, one, one)

    def decode_args():
        zeros = jnp.zeros((slots,), jnp.int32)
        return DecodeState.make(model.gen_cache(slots, cap, block_size=0),
                                zeros, zeros).astuple()

    got = routes(PrefillStep(model), prefill_args)
    if got != {"scan": mamba, "step": 0}:
        raise RuntimeError(f"a {chunk}-token chunk would run {got} of the "
                           f"{mamba} state-space layers")
    got = routes(DecodeStep(model), decode_args)
    if got != {"scan": 0, "step": mamba}:
        raise RuntimeError(f"a decode step would run {got} of the {mamba} "
                           f"state-space layers")
