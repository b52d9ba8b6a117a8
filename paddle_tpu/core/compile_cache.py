"""Persistent XLA compilation cache.

The reference caches prepared programs per-process
(python/paddle/fluid/executor.py:1253 `_ExecutorCache`); on TPU the
expensive artifact is the XLA executable itself (tens of seconds for a
cold 24-layer step), so the TPU-native analog is jax's *persistent*
compilation cache: compiled executables keyed by (HLO, compile options,
backend) survive process restarts, making warm-process compile time a
disk read.

Placement is decided OUTSIDE the program: when `JAX_COMPILATION_CACHE_DIR`
is set jax reads it itself and nothing here names a directory. When it is
unset the cache lives at one fixed path inside the checkout
(`<repo>/.jax_cache`, git-ignored) — the directory is part of the cache
key, so a path built from `$HOME`, a temp name, a pid or the time would
never hit across machines or runs.
"""
from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def _setup() -> str:
    from_env = os.environ.get(_ENV)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # cache EVERY program, small ones too (eager ops, optimizer updates,
    # the engine's insert programs): a process otherwise recompiles each
    # of them at start-up, and a time threshold would make what the next
    # process finds depend on compile-time jitter
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return from_env or DEFAULT_DIR


cache_dir = _setup()
