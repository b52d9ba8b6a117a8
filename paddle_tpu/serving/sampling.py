"""Token-sampling ops for the compiled decode loop (ISSUE 9 satellite).

Small pure functions over RAW jax arrays — traced-safe (they lower into
`jit.DecodeStep`'s single program) and RNG-key threaded (the key is an
explicit argument split by the caller; nothing here touches the global
RNG or the host). Per-slot parameters ride as [B] vectors so ONE
compiled program serves heterogeneous continuous-batching requests:

- ``temperature <= 0``  -> greedy for that slot,
- ``top_k <= 0``        -> top-k filter off for that slot,
- ``top_p >= 1``        -> nucleus filter off for that slot.

Filter semantics match the numpy references in tests/test_serving.py:
top-k keeps every logit >= the k-th largest (ties at the threshold are
kept); top-p keeps the shortest prefix of the descending-probability
sort whose mass reaches p (the argmax token is always kept).

:func:`sample` decides IN THE GRAPH whether any row samples at all: one
``lax.cond`` on ``any(temperature > 0)`` over the vector it is handed.
All-greedy batches return the argmax and run no sort, mask or draw;
a batch with one sampling row runs exactly the filters and the draw it
always ran (inside that side, a filter no sampling row turns on is
skipped by the same kind of branch). With a ``temperature`` the entry
is ONE cached program (a module-level ``jax.jit``): inside
``DecodeStep`` it inlines into the step's program, and an eager caller
(the engine's first token, ``generate``) compiles it once a shape —
an eager ``lax.cond`` would otherwise be traced and compiled anew on
every call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["greedy", "apply_temperature", "top_k_mask", "top_p_mask",
           "sample"]

_NEG = -jnp.inf


def greedy(logits):
    """[B, V] logits -> [B] int32 argmax token ids."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def apply_temperature(logits, temperature):
    """Divide each row by its temperature ([B] vector or scalar);
    non-positive entries are clamped to a tiny epsilon — rows meant to
    be greedy are selected in :func:`sample`, not here."""
    t = jnp.broadcast_to(
        jnp.asarray(temperature, logits.dtype), logits.shape[:1]
    )
    return logits / jnp.maximum(t, 1e-6)[:, None]


def top_k_mask(logits, k):
    """Mask every logit strictly below the row's k-th largest to -inf.
    ``k`` is a [B] int32 vector (or scalar); ``k <= 0`` leaves that row
    unfiltered."""
    V = int(logits.shape[-1])
    kk = jnp.broadcast_to(jnp.asarray(k, jnp.int32), logits.shape[:1])
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    idx = jnp.clip(kk - 1, 0, V - 1)
    thr = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    keep = (logits >= thr) | (kk <= 0)[:, None]
    return jnp.where(keep, logits, _NEG)


def top_p_mask(logits, p):
    """Nucleus filter: keep the shortest prefix of the descending-
    probability sort whose cumulative mass reaches ``p`` (the top token
    always survives). ``p`` is a [B] float vector (or scalar);
    ``p >= 1`` leaves that row unfiltered."""
    pp = jnp.broadcast_to(
        jnp.asarray(p, jnp.float32), logits.shape[:1]
    )
    order = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # keep while the mass BEFORE this token is still below p
    keep_sorted = (csum - probs) < pp[:, None]
    keep_sorted = keep_sorted.at[..., 0].set(True)
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    keep = keep | (pp >= 1.0)[:, None]
    return jnp.where(keep, logits, _NEG)


def _rows(x, dtype, logits):
    """A scalar or [B] vector as a [B] vector of ``dtype``."""
    return jnp.broadcast_to(jnp.asarray(x, dtype), logits.shape[:1])


@jax.jit
def _sample_rows(logits, key, t, top_k, top_p):
    """The cached program behind :func:`sample`: ``t`` is a [B] float32
    vector, ``top_k`` / ``top_p`` are [B] vectors or None (a filter the
    caller left out: a static choice)."""
    g = greedy(logits)
    draws = t > 0.0

    def draw():
        filtered = apply_temperature(logits.astype(jnp.float32), t)
        # a filter that no sampling row turns on leaves those rows as
        # they are, and greedy rows are replaced below: skip its sorts
        if top_k is not None:
            filtered = jax.lax.cond(
                jnp.any(draws & (top_k > 0)),
                lambda x: top_k_mask(x, top_k), lambda x: x, filtered)
        if top_p is not None:
            filtered = jax.lax.cond(
                jnp.any(draws & (top_p < 1.0)),
                lambda x: top_p_mask(x, top_p), lambda x: x, filtered)
        drawn = jax.random.categorical(key, filtered, axis=-1).astype(
            jnp.int32)
        return jnp.where(t <= 0.0, g, drawn)

    return jax.lax.cond(jnp.any(draws), draw, lambda: g)


def sample(logits, key, temperature=None, top_k=None, top_p=None):
    """One sampling step: [B, V] logits -> [B] int32 token ids.

    Greedy rows (``temperature`` None, or <= 0 per slot) take the
    argmax; the rest draw from the temperature-scaled, top-k- then
    top-p-filtered categorical using ``key`` (caller splits it per
    step — the standard decode-loop threading). When no row draws,
    neither the filters nor the draw run (see the module docstring)."""
    if temperature is None:
        return greedy(logits)
    return _sample_rows(
        logits, key, _rows(temperature, jnp.float32, logits),
        None if top_k is None else _rows(top_k, jnp.int32, logits),
        None if top_p is None else _rows(top_p, jnp.float32, logits))
