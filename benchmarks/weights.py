"""What every family's weights start from: the configuration file, found
by name, and the PRNG key of a seed. The weights themselves are the
family's (`families/<family>`): the benchmark owns them, the program's
model is loaded with them and the plain reference makes the same values
again from the same seed, so neither side hands the other anything.
"""
from __future__ import annotations

import json
import os

import jax

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    """The configuration file `configs/<name>.json`, found by name."""
    path = os.path.join(_HERE, "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return jax.random.fold_in(key, seed // (2 ** 31 - 1))
