"""Seeded weights of a GPT-2 configuration, made on the device in one call.

Two forms of the same values:

* stacked  — the per-layer leaves as ``[n_layer, ...]`` arrays, which the
  reference scans over;
* by name  — one array per leaf under the names `serving.TransformerLM`
  gives its parameters (``blocks.3.attn.qkv.weight``).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from weights import seed_key

#: per-layer leaves in the program's parameter order: name -> (shape
#: as a function of the sizes, kind)
_BLOCK_LEAVES = (
    ("ln1.weight", lambda d, f: (d,), "gain"),
    ("ln1.bias", lambda d, f: (d,), "bias"),
    ("attn.qkv.weight", lambda d, f: (d, 3 * d), "matrix"),
    ("attn.qkv.bias", lambda d, f: (3 * d,), "bias"),
    ("attn.out_proj.weight", lambda d, f: (d, d), "residual"),
    ("attn.out_proj.bias", lambda d, f: (d,), "bias"),
    ("ln2.weight", lambda d, f: (d,), "gain"),
    ("ln2.bias", lambda d, f: (d,), "bias"),
    ("fc1.weight", lambda d, f: (d, f), "matrix"),
    ("fc1.bias", lambda d, f: (f,), "bias"),
    ("fc2.weight", lambda d, f: (f, d), "residual"),
    ("fc2.bias", lambda d, f: (d,), "bias"),
)
BLOCK_NAMES = tuple(n for n, _, _ in _BLOCK_LEAVES)


def sizes(cfg: dict) -> dict:
    d = int(cfg["n_embd"])
    return {
        "vocab": int(cfg["vocab_size"]), "d": d,
        "layers": int(cfg["n_layer"]), "heads": int(cfg["n_head"]),
        "ffn": int(cfg.get("n_inner") or 4 * d),
        "positions": int(cfg["n_positions"]),
        "eps": float(cfg["layer_norm_epsilon"]),
        "std": float(cfg["initializer_range"]),
    }


def _leaf(key, shape, kind, std, layers):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        return 1.0 + 0.02 * x
    if kind == "residual":
        return x * (std / (2.0 * layers) ** 0.5)
    if kind == "bias":
        return 0.02 * x
    return x * std


def stacked(cfg: dict, key):
    """Pure function: the weights as a dict of stacked float32 arrays."""
    s = sizes(cfg)
    d, f, L = s["d"], s["ffn"], s["layers"]
    out = {}
    spec = [("embed.weight", (s["vocab"], d), "matrix"),
            ("pos_embed.weight", (s["positions"], d), "matrix")]
    spec += [(f"blocks.{n}", (L,) + shp(d, f), kind)
             for n, shp, kind in _BLOCK_LEAVES]
    spec += [("ln_f.weight", (d,), "gain"), ("ln_f.bias", (d,), "bias"),
             ("head.weight", (d, s["vocab"]), "matrix"),
             ("head.bias", (s["vocab"],), "bias")]
    for i, (name, shape, kind) in enumerate(spec):
        out[name] = _leaf(jax.random.fold_in(key, i), shape, kind,
                          s["std"], L)
    return out


def by_name(tree: dict) -> dict:
    """Stacked tree -> one entry per program leaf (``blocks.<i>.<leaf>``)."""
    out = {}
    for name, arr in tree.items():
        if name.startswith("blocks."):
            leaf = name[len("blocks."):]
            for i in range(arr.shape[0]):
                out[f"blocks.{i}.{leaf}"] = arr[i]
        else:
            out[name] = arr
    return out


def split_fused(tree: dict) -> dict:
    """The leaves as `correct` compares them: a fused QKV leaf counts as
    its three projections (`...qkv.bias.k`), because the key's bias has
    no gradient under softmax while the query's and the value's have."""
    out = {}
    for name, a in tree.items():
        if name.endswith(("attn.qkv.weight", "attn.qkv.bias")):
            third = a.shape[-1] // 3
            for j, part in enumerate("qkv"):
                out[f"{name}.{part}"] = a[..., j * third:(j + 1) * third]
        else:
            out[name] = a
    return out


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, form: str):
    cfg = json.loads(cfg_json)
    if form == "stacked":
        return jax.jit(lambda key: stacked(cfg, key))
    return jax.jit(lambda key: by_name(stacked(cfg, key)))


def make(cfg: dict, seed: int, form: str = "by_name") -> dict:
    """The seed's weights on the default device, in one jitted call."""
    keep = {k: cfg[k] for k in ("vocab_size", "n_embd", "n_layer", "n_head",
                                "n_inner", "n_positions",
                                "layer_norm_epsilon", "initializer_range")}
    return _jitted(json.dumps(keep, sort_keys=True), form)(seed_key(seed))
