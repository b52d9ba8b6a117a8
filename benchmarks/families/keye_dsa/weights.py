"""Seeded weights of a `keye_dsa` configuration, made on the device a
leaf at a time under the names `serving.SparseMoELM` gives its
parameters. The program's model is loaded with them and the reference
makes the same values again from the same seed: every leaf is
`normal(fold_in(seed key, leaf index))`, rounded to bfloat16. The
reference widens what it is given; it is handed nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import seed_key


def sizes(cfg: dict) -> dict:
    pub = cfg.get("published", {})
    sa = cfg["sa_config"]
    return {
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "d": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
        # keys a tile of the program's blockwise forms: the config's own
        # kv_chunk_size (the toy sets a smaller one, so that the rehearsal
        # walks more than one tile)
        "key_block": int(sa["kv_chunk_size"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        # the experts this chip holds, and the router's published width
        "held": int(cfg["num_experts"]),
        "experts": int(pub.get("num_experts", cfg["num_experts"])),
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
        "rope_base": float(cfg["rope_theta"]),
        "std": 0.02,
    }


def leaves(s: dict) -> list:
    """[(name, shape, kind)] of every leaf, in the order that numbers
    them. Kinds: `matrix` normal(0, std); `residual` (an output
    projection) normal(0, std / sqrt(2 * layers)); `gain` 1 + normal(0,
    0.02); `shift` (the LayerNorm's bias) normal(0, 0.02)."""
    d, H, G, Dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    Hi, Di, f = s["index_heads"], s["index_dim"], s["expert_ffn"]
    out = [("embedding.weight", (s["vocab"], d), "matrix")]
    for i in range(s["layers"]):
        b = f"blocks.{i}."
        out += [
            (b + "norm1.weight", (d,), "gain"),
            (b + "attn.qkv_proj", (d, (H + 2 * G) * Dh), "matrix"),
            (b + "attn.o_proj", (H * Dh, d), "residual"),
            (b + "attn.index_proj", (d, Hi * Di + Di + Hi), "matrix"),
            (b + "attn.q_norm.weight", (Dh,), "gain"),
            (b + "attn.k_norm.weight", (Dh,), "gain"),
            (b + "attn.index_norm_weight", (Di,), "gain"),
            (b + "attn.index_norm_bias", (Di,), "shift"),
            (b + "norm2.weight", (d,), "gain"),
            (b + "mlp.gate", (d, s["experts"]), "matrix"),
            (b + "mlp.w_in", (s["held"], d, 2 * f), "matrix"),
            (b + "mlp.w_out", (s["held"], f, d), "residual"),
        ]
    out += [("norm_f.weight", (d,), "gain"), ("head", (d, s["vocab"]),
                                            "matrix")]
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, kind: str, std: float, layers: int):
    def f(key):
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "gain":
            x = 1.0 + 0.02 * x
        elif kind == "shift":
            x = 0.02 * x
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
    key = seed_key(seed)
    for i, (name, shape, kind) in enumerate(leaves(s)):
        yield name, _leaf_fn(tuple(shape), kind, s["std"], s["layers"])(
            jax.random.fold_in(key, i))


def make(cfg: dict, seed: int, form: str = "by_name") -> dict:
    """{leaf name: array} of the seed's weights on the default device.
    Both forms are this one dict: the reference walks the layers by name
    as the program does."""
    return dict(each_leaf(cfg, seed))


def split_fused(tree: dict) -> dict:
    """The leaves as a comparison of leaves would take them: as they are
    (a fused matrix's parts are columns the reference slices itself)."""
    return dict(tree)
