"""Seeded weights of a `sarvam_mla` configuration, made on the device a
leaf at a time (the whole model is 9 GB in bfloat16: a second copy in
flight would not fit beside it), under the names `serving.LatentMoELM`
gives its parameters. The program's model is loaded with them and the
reference makes the same values again from the same seed: every leaf is
`normal(fold_in(seed key, leaf index))`, rounded to the dtype it is stored
in, which is bfloat16 for everything but the float32 selection bias; the
selection bias alone is drawn from a fixed seed, the same for every run. The
reference widens what it is given; it is handed nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import seed_key


#: the seed of every run's selection bias
BIAS_SEED = 0


def sizes(cfg: dict) -> dict:
    pub = cfg.get("published", {})
    rs = cfg["rope_scaling"]
    return {
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "d": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v": int(cfg["v_head_dim"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "dense_ffn": int(cfg["intermediate_size"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        # the experts this chip holds, and the router's published width
        "held": int(cfg["num_experts"]),
        "experts": int(pub.get("num_experts", cfg["num_experts"])),
        "top_k": int(cfg["num_experts_per_tok"]),
        "shared_ffn": int(cfg["num_shared_experts"])
        * int(cfg["moe_intermediate_size"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "qk_norm": bool(cfg["use_qk_norm"]),
        "select_bias": bool(cfg["moe_router_enable_expert_bias"]),
        "rope_cfg": {
            "base": float(cfg["rope_theta"]), "factor": float(rs["factor"]),
            "original_max_position":
                int(rs["original_max_position_embeddings"]),
            "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
            "mscale": float(rs["mscale"]),
            "mscale_all_dim": float(rs["mscale_all_dim"])},
        # keys a block of the program's blockwise attention (the toy
        # sets it, so that the rehearsal walks more than one block)
        "key_block": cfg.get("key_block"),
        "std": 0.02, "bias_std": 0.1,
    }


def leaves(s: dict) -> list:
    """[(name, shape, kind)] of every leaf, in the order that numbers
    them. Kinds: `matrix` normal(0, std); `residual` (an output
    projection) normal(0, std / sqrt(2 * layers)); `gain` 1 + normal(0,
    0.02); `select_bias` float32 normal(0, bias_std), wide enough that
    dropping it changes the chosen set."""
    d, H = s["d"], s["heads"]
    qd = s["nope"] + s["rope"]
    out = [("embedding.weight", (s["vocab"], d), "matrix")]
    for i in range(s["layers"]):
        b = f"blocks.{i}."
        out += [
            (b + "norm1.weight", (d,), "gain"),
            (b + "attn.q_proj", (d, H * qd), "matrix"),
            (b + "attn.kv_down", (d, s["kv_rank"] + s["rope"]), "matrix"),
            (b + "attn.kv_up", (s["kv_rank"], H * (s["nope"] + s["v"])),
             "matrix"),
            (b + "attn.o_proj", (H * s["v"], d), "residual"),
            (b + "attn.q_norm.weight", (qd,), "gain"),
            (b + "attn.kv_norm.weight", (s["kv_rank"],), "gain"),
            (b + "norm2.weight", (d,), "gain"),
        ]
        if i < s["dense_layers"]:
            out += [(b + "mlp.gate_up", (d, 2 * s["dense_ffn"]), "matrix"),
                    (b + "mlp.down", (s["dense_ffn"], d), "residual")]
        else:
            f = s["expert_ffn"]
            out += [
                (b + "mlp.gate", (d, s["experts"]), "matrix"),
                (b + "mlp.select_bias", (s["experts"],), "select_bias"),
                (b + "mlp.w_in", (s["held"], d, 2 * f), "matrix"),
                (b + "mlp.w_out", (s["held"], f, d), "residual"),
                (b + "mlp.shared.gate_up", (d, 2 * s["shared_ffn"]),
                 "matrix"),
                (b + "mlp.shared.down", (s["shared_ffn"], d), "residual"),
            ]
    out += [("norm_f.weight", (d,), "gain"), ("head", (d, s["vocab"]),
                                            "matrix")]
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, kind: str, std: float, bias_std: float,
             layers: int):
    def f(key):
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "select_bias":
            return x * bias_std
        if kind == "gain":
            x = 1.0 + 0.02 * x
        elif kind == "residual":
            x = x * (std / (2.0 * layers) ** 0.5)
        else:
            x = x * std
        return x.astype(jnp.bfloat16)

    return jax.jit(f)


def each_leaf(cfg: dict, seed: int):
    """(name, array) of the seed's weights, one leaf at a time: whoever
    loads a model with them can let go of each before the next is made."""
    s = sizes(cfg)
    # the selection bias is the same draw for every seed (`BIAS_SEED`):
    # which share of a token's eight choices falls on the experts held
    # here follows the bias, and a share that moved with the seed moved
    # the grouped product's rows, and the cell's rate, by +-2 %
    keys = {False: seed_key(seed), True: seed_key(BIAS_SEED)}
    for i, (name, shape, kind) in enumerate(leaves(s)):
        yield name, _leaf_fn(tuple(shape), kind, s["std"], s["bias_std"],
                             s["layers"])(
            jax.random.fold_in(keys[kind == "select_bias"], i))


def make(cfg: dict, seed: int, form: str = "by_name") -> dict:
    """{leaf name: array} of the seed's weights on the default device.
    Both forms are this one dict: the layers differ in kind, so the
    reference walks them by name as the program does."""
    return dict(each_leaf(cfg, seed))


def split_fused(tree: dict) -> dict:
    """The leaves as a comparison of leaves would take them: as they are
    (the fused gate | up matrices have no part that behaves apart)."""
    return dict(tree)
