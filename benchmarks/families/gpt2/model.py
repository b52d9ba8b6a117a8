"""The program's model of a GPT-2 configuration: `serving.TransformerLM`
(`ParallelGPTBlock`, learned positions, pre-LN, final LayerNorm, GELU MLP),
the one decoder of the program whose sizes are arguments, loaded with the
seed's weights."""
from __future__ import annotations

from . import weights

#: the values of a serving mix's `weights` that this family has proven on
#: the chip, with limits and a control (PERF.md)
PROVEN_WEIGHTS = ("float32",)


def _loaded_lm(cfg: dict, seed: int):
    from paddle_tpu.serving import TransformerLM

    s = weights.sizes(cfg)
    lm = TransformerLM(s["vocab"], d_model=s["d"], num_heads=s["heads"],
                       num_layers=s["layers"], max_position=s["positions"],
                       dim_feedforward=s["ffn"])
    w = weights.make(cfg, seed)
    names = {}
    for name, p in lm.named_parameters():
        p._data = w[name].astype(p._data.dtype)
        names[id(p)] = name
    return lm, names


def serving_model(cfg: dict, mix: dict, seed: int):
    """The `Layer` a serving driver hands to `InferenceEngine`."""
    if mix["weights"] not in PROVEN_WEIGHTS:
        raise ValueError("only float32 serving has run on this chip; a "
                         "mix in another precision needs its own proof")
    lm, _ = _loaded_lm(cfg, seed)
    lm.eval()
    return lm


def training_model(cfg: dict, mix: dict, seed: int) -> dict:
    """What a training driver hands to `TrainStep`: the `layer` (the
    model's own parts up to the final LayerNorm), the `loss` of its output
    and the labels (the head sits there, where the blockwise cross-entropy
    streams it), the `parameters` the optimizer gets and their `names`
    ({id(parameter): leaf name}, as `weights.by_name` names them)."""
    from paddle_tpu import nn
    from paddle_tpu.ops.creation import arange

    lm, names = _loaded_lm(cfg, seed)

    class Trunk(nn.Layer):

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            lm = self.lm
            h = lm.embed(ids) + lm.pos_embed(
                arange(int(ids.shape[1]), dtype="int64"))
            for blk in lm.blocks:
                h = blk(h)
            return lm.ln_f(h)

    trunk = Trunk(lm)
    trunk.train()

    def lm_loss(h, labels):
        d = h.shape[-1]
        return nn.functional.fused_linear_cross_entropy(
            h.reshape([-1, d]), lm.head.weight, lm.head.bias,
            labels.reshape([-1]))

    return {"lm": lm, "layer": trunk, "loss": lm_loss,
            "parameters": lm.parameters(), "names": names}


def assert_routes(model: dict, cfg: dict, mix: dict, rehearse: bool) -> None:
    """The cell's shapes must take the Pallas kernels, as chip_smoke
    asserts: a cell that falls off the kernel path measures another
    program."""
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import flash_plan
    from paddle_tpu.nn.functional.norm import _fused_ln_route

    s = weights.sizes(cfg)
    plan = flash_plan(mix["seq"], mix["seq"], causal=True, mesh=None,
                      batch=mix["batch"], heads=s["heads"])
    blk = model["lm"].blocks[0]
    route = _fused_ln_route(
        jnp.zeros((mix["batch"], mix["seq"], s["d"]), jnp.bfloat16),
        (s["d"],), blk.ln1.weight, blk.ln1.bias, mesh=blk.mesh)
    if plan is None or plan[0] != "plain":
        raise RuntimeError(f"flash_plan is {plan}, the cell expects plain")
    if route is None or route[0] is not rehearse:
        raise RuntimeError(f"_fused_ln_route is {route}: the cell's "
                           "LayerNorm is off the kernel path")
