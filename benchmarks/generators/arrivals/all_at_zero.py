"""`count` requests all due at t = 0: the queue never empties."""
import numpy as np

#: more is offered than a window can finish, so what is still queued when
#: the window closes is withdrawn and not waited for
withdraw_at_close = True


def due(spec: dict, seconds: float, rng) -> np.ndarray:
    return np.zeros(int(spec["count"]))
