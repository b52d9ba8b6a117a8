"""Static-graph Executor.

Reference: python/paddle/fluid/executor.py `Executor.run` (:916) →
`_run_impl` (:1112) → `_run_program` (:1253) feed/fetch + program cache,
over the C++ op-loop interpreter (framework/executor.cc:166,414).

TPU-native: `run` compiles the recorded Program (plus, when
`opt.minimize(loss)` was recorded, its backward + optimizer update — the
append_backward analog, fluid/backward.py:1337) into ONE jitted XLA
program per (program version, feed signature, fetch set), then executes
it. Feed/fetch ops are just function arguments/results; the program cache
is the jit cache.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Parameter, Tensor
from .program import Program, Variable, default_main_program

__all__ = ["CompiledProgram", "Executor", "global_scope"]


class CompiledProgram:
    """fluid/compiler.py:88 CompiledProgram.with_data_parallel analog.

    Wrapping a Program marks it for SPMD data parallelism: Executor.run
    feeds shard over the default mesh's dp axis and parameters replicate,
    so XLA partitions the one compiled program across devices and inserts
    the gradient all-reduce (the multi_devices_graph_pass +
    ParallelExecutor pipeline collapsed into sharding propagation)."""

    def __init__(self, program, build_strategy=None):
        self.program = program
        self._data_parallel = False
        self._loss_name = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, places=None):
        self._data_parallel = True
        self._loss_name = loss_name
        return self


class _Scope:
    def find_var(self, name):
        return None


_scope = _Scope()


def global_scope():
    return _scope


class Executor:
    """executor.py:916 parity surface (run/close); place is accepted for
    script parity — XLA owns placement."""

    def __init__(self, place=None):
        self.place = place
        self._cache: Dict = {}

    def close(self):
        self._cache.clear()

    # -- compile -------------------------------------------------------------
    def _build(self, program: Program, feed_names, fetch_vars):
        # leaf tensors: concrete Tensors recorded as op inputs (params +
        # captured constants); resolved from the live objects at call time
        leaves, leaf_idx = [], {}
        rng_vars, rng_pos = [], {}
        for op in program.ops:
            for t in op.inputs:
                if isinstance(t, Tensor) and id(t) not in leaf_idx:
                    leaf_idx[id(t)] = len(leaves)
                    leaves.append(t)
                elif isinstance(t, Variable) and t.is_rng \
                        and t.id not in rng_pos:
                    rng_pos[t.id] = len(rng_vars)
                    rng_vars.append(t)
        params = [
            t for t in leaves
            if isinstance(t, Parameter) and t.trainable
        ]
        # the optimizer trains ITS parameter subset (optimizer.py minimize
        # sets _parameter_list; frozen-backbone scripts rely on this)
        if program.optimize_directives:
            opt0 = program.optimize_directives[0][0]
            if opt0._parameter_list is not None:
                allowed = {id(p) for p in opt0._parameter_list}
                params = [p for p in params if id(p) in allowed]
        p_idx = {id(p): i for i, p in enumerate(params)}
        feed_pos = {n: i for i, n in enumerate(feed_names)}
        for v in fetch_vars:
            if isinstance(v, Tensor) and id(v) not in leaf_idx:
                raise ValueError(
                    "fetch_list contains a concrete Tensor that never "
                    "appears in the program; fetch program variables or "
                    "tensors the ops consume"
                )

        def replay(p_raws, leaf_raws, feed_raws, rng_raws):
            env = {}

            def resolve(inp):
                if isinstance(inp, Variable):
                    if inp.id in env:
                        return env[inp.id]
                    if inp.is_rng:
                        return rng_raws[rng_pos[inp.id]]
                    if inp.is_data:
                        return feed_raws[feed_pos[inp.name]]
                    raise KeyError(
                        f"variable '{inp.name}' has no producer op and is "
                        "not fed"
                    )
                i = id(inp)
                if i in p_idx:
                    return p_raws[p_idx[i]]
                return leaf_raws[leaf_idx[i]]

            for op in program.ops:
                outs = op.fn(*[resolve(i) for i in op.inputs])
                outs = tuple(outs) if op.multi else (outs,)
                for var, o in zip(op.out_vars, outs):
                    env[var.id] = o
            fetches = tuple(resolve(v) for v in fetch_vars)
            state_vals = tuple(
                resolve(var) for _, var in program.state_writes
            )
            return fetches, env, state_vals

        directives = program.optimize_directives
        if not directives:
            def run_fn(p_raws, leaf_raws, feed_raws, rng_raws):
                fetches, _, state_vals = replay(
                    p_raws, leaf_raws, feed_raws, rng_raws
                )
                return fetches, p_raws, (), state_vals

            return jax.jit(run_fn), leaves, params, None, rng_vars

        if len(directives) > 1:
            raise NotImplementedError(
                "multiple minimize() calls in one Program"
            )
        opt, loss_var = directives[0]

        from ..jit.train_step import process_grads

        def run_fn(p_raws, leaf_raws, feed_raws, rng_raws, opt_state, lr, t):
            def loss_of(p_tuple):
                fetches, env, state_vals = replay(
                    p_tuple, leaf_raws, feed_raws, rng_raws
                )
                return env[loss_var.id], (fetches, state_vals)

            (loss, (fetches, state_vals)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(tuple(p_raws))
            grads = process_grads(opt, params, list(p_raws), list(grads))
            new_p, new_state = opt._functional_update(
                params, list(p_raws), grads, opt_state, lr, t
            )
            return fetches, new_p, new_state, state_vals

        donate = (0, 4)
        return (jax.jit(run_fn, donate_argnums=donate), leaves, params, opt,
                rng_vars)

    # -- run -----------------------------------------------------------------
    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list=None, return_numpy=True):
        """executor.py:916. Returns fetched values in fetch_list order.
        A CompiledProgram.with_data_parallel shards feeds over the dp
        mesh axis (ParallelExecutor path, executor.py:1112)."""
        dp_mesh = None
        if isinstance(program, CompiledProgram):
            if program._data_parallel:
                from ..distributed import comm

                dp_mesh = comm._default_group().mesh
            program = program.program
        program = program if program is not None else default_main_program()
        feed = dict(feed or {})
        fetch_list = list(fetch_list or [])
        if not program.ops:
            return []  # startup program: params initialize eagerly

        fetch_vars = []
        for f in fetch_list:
            v = getattr(f, "_static_var", None)
            if v is None and isinstance(f, Variable):
                v = f
            if v is None and isinstance(f, Tensor):
                v = f  # concrete tensor fetch (e.g. a parameter)
            if v is None:
                raise TypeError(f"cannot fetch {type(f)}")
            fetch_vars.append(v)

        feed_names = tuple(sorted(feed))
        feed_raws = tuple(
            f._data if isinstance(f, Tensor) else jnp.asarray(feed[n])
            for n, f in ((n, feed[n]) for n in feed_names)
        )
        if dp_mesh is not None:
            from ..distributed import comm as _comm

            n_dev = dp_mesh.devices.size
            for name, r in zip(feed_names, feed_raws):
                if r.ndim > 0 and r.shape[0] % n_dev != 0:
                    raise ValueError(
                        f"CompiledProgram.with_data_parallel: feed "
                        f"'{name}' batch {r.shape[0]} is not divisible "
                        f"by the {n_dev} devices (ParallelExecutor "
                        "raises here too; pad or drop the tail batch)"
                    )
            feed_raws = tuple(
                _comm.shard_rank_axis(r) if r.ndim > 0 else r
                for r in feed_raws
            )
        sig = tuple(
            (n, tuple(r.shape), str(r.dtype))
            for n, r in zip(feed_names, feed_raws)
        )
        key = (
            id(program), program._version, sig,
            tuple(
                v.id if isinstance(v, Variable) else id(v)
                for v in fetch_vars
            ),
        )
        if key not in self._cache:
            self._cache[key] = self._build(program, feed_names, fetch_vars)
        run_fn, leaves, params, opt, rng_vars = self._cache[key]

        p_raws = tuple(p._data for p in params)
        leaf_raws = tuple(t._data for t in leaves)
        # fresh key data per run for every rng placeholder (dropout masks
        # vary across runs; see program.rng_feed)
        from ..core import random as rnd

        rng_raws = tuple(
            jax.random.key_data(rnd.next_key()) for _ in rng_vars
        )
        if opt is None:
            fetches, _, _, state_vals = run_fn(
                p_raws, leaf_raws, feed_raws, rng_raws
            )
        else:
            opt_state = opt._functional_state(params)
            opt._step_count += 1
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            t = jnp.asarray(opt._step_count, jnp.float32)
            fetches, new_p, new_state, state_vals = run_fn(
                p_raws, leaf_raws, feed_raws, rng_raws, opt_state, lr, t
            )
            for p, raw in zip(params, new_p):
                p._data = raw
                p._node = None
                p.grad = None
            opt._load_functional_state(params, new_state)
        # persistable-state write-back (batch-norm running stats):
        # updated values land in the LIVE buffer objects after each run
        for (obj, _), val in zip(program.state_writes, state_vals):
            obj._data = val
            obj._node = None
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [Tensor._wrap(f, stop_gradient=True) for f in fetches]
