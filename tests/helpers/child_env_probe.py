"""What a spawned child's jax was told (tests/test_bring_up.py): jax reads
JAX_PLATFORMS once, when it is first imported, so `jax.config.jax_platforms`
shows whether the variable was in the child's environment BEFORE that
import — the one thing that keeps a host-side worker off the chip."""
import json
import os

import numpy as np


def seen():
    import jax

    return {"env": os.environ.get("JAX_PLATFORMS"),
            "jax_platforms": jax.config.jax_platforms}


def report(path):
    """`distributed.spawn` target."""
    with open(path, "w") as f:
        json.dump(seen(), f)


class ProbeDataset:
    """Every sample is (env pinned to cpu?, jax captured cpu?) as the
    DataLoader worker that fetched it sees them."""

    def __len__(self):
        return 4

    def __getitem__(self, i):
        s = seen()
        return np.asarray([s["env"] == "cpu", s["jax_platforms"] == "cpu"],
                          np.int64)
