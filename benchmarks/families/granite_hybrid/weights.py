"""Seeded weights of a `granite_hybrid` configuration, made on the device
a leaf at a time under the names `serving.HybridSSMLM` gives its
parameters. The program's model is loaded with them and the reference
makes the same values again from the same seed: every leaf is drawn from
`fold_in(seed key, leaf index)` and rounded to bfloat16. The reference
widens what it is given; it is handed nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import seed_key


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    s = {
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "d": d,
        "layers": int(cfg["num_hidden_layers"]),
        "layer_types": list(cfg["layer_types"]),
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        # the config gives no head_dim: hidden_size / heads, as the
        # source's attention computes it
        "head_dim": d // heads,
        "ssm_heads": int(cfg["mamba_n_heads"]),
        "ssm_head_dim": int(cfg["mamba_d_head"]),
        "state": int(cfg["mamba_d_state"]),
        "conv_kernel": int(cfg["mamba_d_conv"]),
        "chunk": int(cfg["mamba_chunk_size"]),
        # no experts (num_local_experts 0): every layer's MLP is the
        # shared MLP of shared_intermediate_size
        "ffn": int(cfg["shared_intermediate_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "attention_multiplier": float(cfg["attention_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        # the matrices' normal: the source's initializer_range (the toy of
        # `--rehearse` sets a wider one, so that its narrow layers matter)
        "std": float(cfg.get("initializer_range", 0.02)),
    }
    # the table is the head too: configs/granite-4.0-h-micro.json's
    # `assumed` says why it is drawn a quarter as wide as the matrices
    s["embedding_std"] = s["std"] / 4
    if len(s["layer_types"]) != s["layers"]:
        raise ValueError("layer_types does not list num_hidden_layers")
    if int(cfg["mamba_n_groups"]) != 1 or \
            s["ssm_heads"] * s["ssm_head_dim"] != int(cfg["mamba_expand"]) * d:
        raise ValueError("one group of B and C, heads x head size = "
                         "expand x hidden, is all this family computes")
    s["d_inner"] = s["ssm_heads"] * s["ssm_head_dim"]
    s["conv_dim"] = s["d_inner"] + 2 * s["state"]
    s["mamba_layers"] = s["layer_types"].count("mamba")
    s["attention_layers"] = s["layer_types"].count("attention")
    return s


def leaves(s: dict) -> list:
    """[(name, shape, kind)] of every leaf, in the order that numbers
    them. Kinds: `embedding` normal(0, embedding_std); `matrix` normal(0,
    std), the output projections included; `conv` (the convolution's taps and bias) uniform(-1 /
    sqrt(d_conv), 1 / sqrt(d_conv)); `gain` 1 + normal(0, 0.02); `a_log`
    log(1 .. H); `dt_bias` the inverse softplus of a step size drawn
    log-uniform in [0.001, 0.1]; `ones` 1. The configuration file's
    `assumed` says why the output projections and the convolution are not
    smaller."""
    d, f = s["d"], s["ffn"]
    H, G, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    Hs, N = s["ssm_heads"], s["state"]
    out = [("embedding.weight", (s["vocab"], d), "embedding")]
    for i, kind in enumerate(s["layer_types"]):
        b = f"blocks.{i}."
        out.append((b + "norm1.weight", (d,), "gain"))
        if kind == "mamba":
            m = b + "attn."
            out += [
                (m + "in_proj", (d, 2 * s["d_inner"] + 2 * N + Hs), "matrix"),
                (m + "conv_weight", (s["conv_kernel"], s["conv_dim"]),
                 "conv"),
                (m + "conv_bias", (s["conv_dim"],), "conv"),
                (m + "dt_bias", (Hs,), "dt_bias"),
                (m + "A_log", (Hs,), "a_log"),
                (m + "D", (Hs,), "ones"),
                (m + "norm_weight", (s["d_inner"],), "gain"),
                (m + "out_proj", (s["d_inner"], d), "matrix"),
            ]
        else:
            out += [
                (b + "attn.qkv_proj", (d, (H + 2 * G) * Dh), "matrix"),
                (b + "attn.o_proj", (H * Dh, d), "matrix"),
            ]
        out += [
            (b + "norm2.weight", (d,), "gain"),
            (b + "mlp.gate_up", (d, 2 * f), "matrix"),
            (b + "mlp.down", (f, d), "matrix"),
        ]
    out.append(("norm_f.weight", (d,), "gain"))
    return out


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, kind: str, std: float, conv_kernel: int):
    """`std` is the normal's of `matrix` and `embedding` leaves."""
    def f(key):
        if kind == "a_log":
            x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif kind == "dt_bias":
            lo, hi = np.log(1e-3), np.log(1e-1)
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "ones":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "conv":
            bound = conv_kernel ** -0.5
            x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif kind == "gain":
            x = 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            x = std * jax.random.normal(key, shape, jnp.float32)
        return x.astype(jnp.bfloat16)

    return jax.jit(f)


def each_leaf(cfg: dict, seed: int):
    """(name, array) of the seed's weights, one leaf at a time: whoever
    loads a model with them can let go of each before the next is made."""
    s = sizes(cfg)
    key = seed_key(seed)
    for i, (name, shape, kind) in enumerate(leaves(s)):
        std = s["embedding_std"] if kind == "embedding" else s["std"]
        yield name, _leaf_fn(tuple(shape), kind, std, s["conv_kernel"])(
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
