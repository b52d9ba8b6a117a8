"""The program's model of a `keye_dsa` configuration:
`serving.SparseMoELM` (grouped-query attention over the cached keys a
learned indexer selects, softmax top-k routed experts), loaded with the
seed's weights a leaf at a time."""
from __future__ import annotations

from . import weights

#: the values of a serving mix's `weights` that this family has proven on
#: the chip, with limits and a control (PERF.md)
PROVEN_WEIGHTS = ("bfloat16",)


def build(cfg: dict, dtype: str = "bfloat16", first_held: int = 0):
    """An unloaded `SparseMoELM` of the configuration's sizes (zeros)."""
    from paddle_tpu.serving import SparseMoELM  # a program without it stops here

    from paddle_tpu.nn import ParamAttr
    from paddle_tpu.nn.initializer import Constant

    s = weights.sizes(cfg)
    return SparseMoELM(
        s["vocab"], s["d"], s["heads"], s["kv_heads"], s["head_dim"],
        s["layers"], index_heads=s["index_heads"],
        index_dim=s["index_dim"], topk=s["topk"],
        expert_ffn=s["expert_ffn"], num_experts=s["experts"],
        top_k=s["top_k"], held=(first_held, s["held"]),
        rope_base=s["rope_base"], max_position=s["positions"],
        epsilon=s["eps"], key_block=s["key_block"], dtype=dtype,
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
    """Set-up fails if the cell's prefill would not run the masked
    blockwise form over the capacity, a decode step would not gather, the
    cell's contexts never pass `topk` (the selection would never bite),
    or the experts are not the dropless grouped product under softmax
    scoring."""
    from paddle_tpu.nn.functional.dsa import indexed_attend_plan
    from paddle_tpu.nn.layers.latent import RoutedExperts

    eng = mix["engine"]
    cap = int(eng["max_length"])
    attn = model.blocks[0].attn
    chunk = int(eng.get("prefill_chunk") or 0)
    if chunk:
        plan = indexed_attend_plan(chunk, cap, attn.key_block)
        if plan != ("masked", "blockwise"):
            raise RuntimeError(f"a {chunk}-token chunk over {cap} rows "
                               f"would attend as {plan}")
    plan = indexed_attend_plan(1, cap, attn.key_block)
    if plan[0] != "gather":
        raise RuntimeError(f"a decode step would attend as {plan}")
    if cap <= attn.topk:
        raise RuntimeError(f"a capacity of {cap} never passes topk = "
                           f"{attn.topk}: every visible key is selected")
    mlps = [b.mlp for b in model.blocks]
    if not all(type(m) is RoutedExperts and m.score == "softmax"
               and m.select_bias is None and m.shared is None
               for m in mlps):
        raise RuntimeError("the expert layers are not nn.RoutedExperts "
                           "under softmax scoring with no bias and no "
                           "shared expert")
