"""Fused training step: forward + loss + backward + optimizer update
compiled as ONE XLA program.

Reference analog: the hot path the generated `core.ops.*` bindings +
run_program op give static-mode Paddle (pybind/op_function_generator.cc:488,
operators/run_program_op.cc) — one host call per step, all math fused by the
compiler. TPU-first: the optimizer update runs INSIDE the compiled program
(pure rules over an explicit opt-state pytree, optimizer.py _pure_one), so a
step is a single device program launch; parameter buffers are donated so XLA
updates them in place in HBM.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core import autograd as AG
from ..core import random as rnd
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..utils import fault_injection as _FI
from ..utils import train_guard as _TG
from .functional_call import _swapped, _trace_rng


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _commit_on_one_device(tree):
    """Commit every eager-built (uncommitted) array of the loop-carried
    step state to the device it already sits on — when, and only when,
    the whole state lives on ONE device.

    The step's outputs come back committed as soon as any input was
    (the optimizer's moments are), so fresh params and the guard carry
    flip uncommitted -> committed between call 1 and call 2: same
    shapes, another input signature, and the entire step silently
    compiles twice. State that spans several devices is left alone:
    pinning a stray single-device leaf next to operands a DataParallel
    wrap laid out on the default-group mesh is an "incompatible devices"
    error at dispatch, and the hybrid-mesh path normalizes placement in
    the constructor instead."""
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    if len({d for x in leaves for d in x.sharding.device_set}) != 1:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, x.sharding)
        if isinstance(x, jax.Array) and not x.committed else x, tree)


def process_grads(opt, p_objs, p_raws, g_raws, grad_post_hook=None):
    """Regularizer terms + grad clip + strategy hook, traced. Shared by
    TrainStep and LocalSGDStep so strategy/optimizer extras never silently
    drop in an alternate step."""
    reg = opt._regularization
    if reg is not None or any(p.regularizer is not None for p in p_objs):
        out = []
        for p, praw, g in zip(p_objs, p_raws, g_raws):
            r = p.regularizer or reg
            if g is None or r is None:
                out.append(g)
            else:
                out.append(g + r.grad_term(praw))
        g_raws = out
    if opt._grad_clip is not None:
        with AG.trace_mode(), _swapped(p_objs, p_raws):
            pgs = [(p, Tensor._wrap(g) if g is not None else None)
                   for p, g in zip(p_objs, g_raws)]
            pgs = opt._grad_clip(pgs)
            g_raws = [g._data if g is not None else None for _, g in pgs]
    if grad_post_hook is not None:
        g_raws = grad_post_hook(g_raws, p_objs)
    return g_raws


class TrainStep:
    """Compile model+loss+optimizer into one jitted step.

    Usage::

        step = paddle_tpu.jit.TrainStep(model, loss_fn, opt)
        loss = step(inputs, labels)      # Tensors or raw arrays

    loss_fn receives (model_outputs, *labels) as Tensors under trace and
    returns a scalar loss Tensor. Parameter and optimizer-state buffers are
    donated to XLA (in-place HBM update) on every backend.
    Gradient clipping, per-param regularizers, and LR schedules compose
    inside the compiled program; the LR rides as a traced scalar so schedule
    changes never retrigger compilation.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, *,
                 donate: bool = True, grad_post_hook: Optional[Callable] = None,
                 return_outputs: bool = False):
        self.model = model
        self.loss_fn = loss_fn
        self.opt = optimizer
        # return_outputs: step() also returns the forward outputs (metric
        # consumers avoid a second forward; DynamicGraphAdapter analog)
        self._ret_out = return_outputs
        # grad_post_hook(list[raw_grad], list[Parameter]) -> list[raw_grad]:
        # the seam where DataParallel/fleet strategies splice in comm or
        # accumulation (Reducer-hook analog, imperative/reducer.cc:563).
        self._grad_post_hook = grad_post_hook
        if optimizer._parameter_list is None:
            optimizer._parameter_list = list(model.parameters())
        # -- DistributedStrategy consumption (the strategy-compiler seam,
        # reference fleet_base.py:1150-1181 meta-optimizer chain): flags
        # change THIS compiled program, or route to a different step.
        self._amp_ctx = None          # amp.auto_cast kwargs for the trace
        self._loss_scale_cfg = None   # fp16 dynamic loss scaling config
        self._scaler_state = ()       # (scale, good, bad) traced state
        self._recompute = False
        self._async_dcn = False       # explicit per-grad dcn-hop pmean
        self._delegate = None         # localsgd routes to LocalSGDStep
        self._guard = None            # set below (delegate owns its own)
        self._guard_state = ()
        self._inject_enabled = False
        self._dcn_quant = None        # quantized dcn-hop exchange policy
        self._quant_info = None       # resolved width policy (telemetry)
        self._q_matmul = None         # quantized-matmul compute policy
        strategy = getattr(optimizer, "user_defined_strategy", None)
        if strategy is not None:
            if strategy.quantized_allreduce:
                from ..distributed import quantized_comm as _qc

                self._quant_info = _qc.resolve_policy(
                    strategy.quantized_allreduce,
                    strategy.quantized_allreduce_block,
                )
            if strategy.quantized_matmul:
                # QAT matmul route (ISSUE 19): armed around the traced
                # forward via matmul_scope so F.linear sees the policy
                # exactly where this strategy's program traces — eager
                # code outside the step stays governed by PADDLE_Q_MATMUL
                from ..distributed import quantized_compute as _qcp

                self._q_matmul = _qcp.resolve_matmul(
                    strategy.quantized_matmul)
            if strategy.localsgd:
                if strategy.amp or strategy.recompute:
                    raise NotImplementedError(
                        "localsgd does not compose with amp/recompute yet"
                    )
                if strategy.quantized_allreduce:
                    raise NotImplementedError(
                        "localsgd does not compose with "
                        "quantized_allreduce: LocalSGD replaces per-step "
                        "grad reduction with periodic parameter averaging"
                    )
                if strategy.async_dcn_allreduce:
                    # LocalSGDStep has its own comm schedule (periodic
                    # pmean) — silently dropping the flag would hand the
                    # user the tail collective they explicitly disabled
                    raise NotImplementedError(
                        "localsgd does not compose with "
                        "async_dcn_allreduce: LocalSGD replaces per-step "
                        "grad reduction with periodic parameter averaging"
                    )
                from ..distributed.fleet.localsgd import LocalSGDStep

                cfg = strategy.localsgd_configs
                self._delegate = LocalSGDStep(
                    model, loss_fn, optimizer,
                    k_steps=int(cfg["k_steps"]),
                    begin_step=int(cfg["begin_step"]),
                    grad_post_hook=grad_post_hook,
                )
                return
            if strategy.amp:
                ac = strategy.amp_configs
                dtype = "float16" if ac["use_pure_fp16"] or not ac["use_bf16"] \
                    else "bfloat16"
                self._amp_ctx = dict(
                    enable=True,
                    level="O2" if ac["use_pure_fp16"] else "O1",
                    dtype=dtype,
                    custom_white_list=ac["custom_white_list"],
                    custom_black_list=ac["custom_black_list"],
                )
                if dtype == "float16" and ac["use_dynamic_loss_scaling"]:
                    # fused check_finite_and_unscale + update_loss_scaling
                    # (operators/amp/*.cc) INSIDE the compiled step
                    self._loss_scale_cfg = dict(ac)
                    self._scaler_state = (
                        jnp.asarray(ac["init_loss_scaling"], jnp.float32),
                        jnp.asarray(0, jnp.int32),   # good steps
                        jnp.asarray(0, jnp.int32),   # bad steps
                        jnp.asarray(0, jnp.int32),   # APPLIED updates (t)
                    )
            if strategy.recompute:
                self._recompute = True
            if strategy.async_dcn_allreduce and \
                    not strategy.hierarchical_allreduce:
                raise ValueError(
                    "async_dcn_allreduce requires "
                    "hierarchical_allreduce: the explicit async hop "
                    "is the 'dcn' level of the dcn x ici mesh "
                    "factoring"
                )
            # the explicit manual-over-'dcn' grad reduction engages for
            # async_dcn_allreduce AND for quantized_allreduce composed
            # with hierarchical_allreduce (ISSUE 10): the quantized
            # exchange IS a per-grad dcn collective — ici stays
            # full-width under GSPMD, only the slow hop narrows
            if strategy.async_dcn_allreduce or (
                self._quant_info is not None
                and strategy.hierarchical_allreduce
            ):
                if self._loss_scale_cfg is not None:
                    raise NotImplementedError(
                        "the explicit dcn grad reduction (async_dcn_"
                        "allreduce / hierarchical quantized_allreduce) "
                        "does not compose with fp16 dynamic loss "
                        "scaling yet (bf16 amp composes)"
                    )
                self._async_dcn = True
                self._dcn_quant = self._quant_info
        self._p_objs = [p for p in optimizer._get_params() if p.trainable]
        b_named = dict(model.named_buffers())
        self._b_names = list(b_named)
        self._b_objs = list(b_named.values())
        # placement normalization: when a hybrid mesh is active, any
        # param/buffer still on its default single-device placement gets
        # a replicated NamedSharding on that mesh. Mixed placements make
        # the first step's input avals carry a different mesh context
        # ({} vs {Auto: axes}) than its outputs, which re-traces and
        # re-compiles the entire step once on the second call.
        from ..distributed import comm as _comm
        from jax.sharding import NamedSharding, PartitionSpec as _P

        mesh = _comm.hybrid_mesh()
        if mesh is not None and mesh.size <= 1:
            # a trivial (one-device) hybrid mesh is no mesh at all for
            # placement purposes — normalizing onto it would COMMIT the
            # step's state to device 0, which conflicts with params a
            # DataParallel wrap already laid out on the multi-device
            # default-group mesh ("incompatible devices" at dispatch;
            # root cause of the order-dependent dp_matches failure)
            mesh = None
        if mesh is not None:
            repl = NamedSharding(mesh, _P())
            for o in self._p_objs + self._b_objs:
                if not isinstance(
                    getattr(o._data, "sharding", None), NamedSharding
                ):
                    o._data = jax.device_put(o._data, repl)
        # ZeRO stage-3 pad-to-shard-multiple storage (ISSUE 11): params
        # with no dp-divisible axis go padded + dp-sharded NOW (uneven
        # sharding constraints are silently dropped by this XLA); the
        # forward unpads — "unpad on gather" — via _unpad_params below
        if hasattr(self.opt, "_apply_zero_padding"):
            self.opt._apply_zero_padding(self._p_objs)
        self._refresh_zero_pads()
        if self._async_dcn:
            if mesh is None or "dcn" not in mesh.axis_names \
                    or int(mesh.shape["dcn"]) <= 1:
                raise ValueError(
                    "the explicit dcn grad reduction (async_dcn_"
                    "allreduce / hierarchical quantized_allreduce) "
                    "needs a hybrid mesh with a dcn axis (> 1) — "
                    "fleet.init with hierarchical_allreduce and a "
                    "dp_degree that factors must run first"
                )
            if self._b_objs:
                # batch-statistic buffers (BN running stats) would be
                # updated per dcn group and diverge across groups
                raise NotImplementedError(
                    "the explicit dcn grad reduction does not support "
                    "models with buffers (running batch statistics) yet"
                )
            if self._ret_out:
                raise NotImplementedError(
                    "the explicit dcn grad reduction does not compose "
                    "with return_outputs"
                )
            self._dcn_mesh = mesh
            if self._dcn_quant is not None and hasattr(
                    optimizer, "_quant_explicit"):
                # the dcn exchange owns the narrowing — the optimizer's
                # boundary round trip stands down. Set only AFTER the
                # validation above: a ctor that raised must leave the
                # optimizer's eager boundary policy armed, not silently
                # full-width
                optimizer._quant_explicit = True
        self._donate = donate
        # -- numerical guardrails (utils/train_guard.py): the in-graph
        # sentinel + skip masking engage unless PADDLE_GUARD_MODE=off;
        # the guard-policy counters ride the program as a small f32
        # carry, observed by the host monitor every few steps through
        # an async prefetch (no per-step device sync).
        self._guard_mode = _TG.guard_mode()
        self._guard = (_TG.TrainGuard(mode=self._guard_mode, model=model)
                       if self._guard_mode != "off" else None)
        self._guard_state = ()
        if self._guard is not None:
            self._guard._on_rollback = self._after_rollback
            self._guard_state = self._place_guard_state(
                _TG.init_guard_state())
        # grad-comm byte accounting (ISSUE 10): the dtype and actual
        # bytes-on-wire (quantized payload + per-block scales) of one
        # grad reduction, from STATIC param shapes — zero device reads.
        # Rides every step_metrics row via the guard's sampler and lands
        # once on the bus as a `grad_comm` record below.
        from ..distributed import quantized_comm as _qc

        self._grad_comm_info = _qc.grad_comm_info(
            sum(int(p._data.size) for p in self._p_objs),
            self._quant_info,
            fp16_allreduce=bool(strategy is not None
                                and strategy.fp16_allreduce),
        )
        if self._guard is not None:
            self._guard._sampler.set_grad_comm(self._grad_comm_info)
        # grad-poison fault injection (PADDLE_FAULT_SPEC=grad:nan:N):
        # decided once at construction — a clean spec keeps the compiled
        # program byte-identical to the unguarded seed program
        self._inject_enabled = _FI.has_site("grad")
        # per-param "participates in the loss" mask, decided once by jaxpr
        # analysis at first call: unused params keep eager semantics (no
        # update at all) instead of receiving zero grads + decay.
        self._used_mask = None
        # jit is built lazily at the first call so the state outputs can be
        # PINNED to the input shardings (out_shardings): without pinning,
        # GSPMD normalizes output shardings (SingleDevice -> NamedSharding,
        # P(None,'mp') -> P() on trivial axes), the second call sees a new
        # input signature, and the whole step re-traces and re-compiles
        # once — tens of seconds on a large model.
        self._jitted = None
        # observability (ISSUE 8): monotonic step index for the bus, arg
        # avals kept so the step can be lowered again without its
        # arrays; the jitted program is wrapped by the recompile ledger
        self._n_steps = 0
        self._lower_avals = None
        from ..observability import bus as _bus

        # quantized-compute byte attribution (ISSUE 19): resident matmul-
        # weight bytes under the armed QAT policy and the Adam-moment
        # bytes under quantized_moments — static shapes like grad_comm,
        # zero device reads, one bus record each at construction
        from ..distributed import quantized_compute as _qcp

        self._q_matmul_info = _qcp.q_matmul_info(
            sum(int(p._data.size) for p in self._p_objs
                if p._data.ndim == 2),
            self._q_matmul,
        )
        self._moment_bytes_info = _qcp.moment_bytes_info(
            sum(int(p._data.size) for p in self._p_objs),
            getattr(self.opt, "_q_moments", None),
        )
        if self._guard is not None:
            self._guard._sampler.set_quant_bytes(
                self._q_matmul_info, self._moment_bytes_info)
        if _bus.enabled():
            _bus.emit("grad_comm", self._grad_comm_info, step=0)
            _bus.emit("q_matmul", self._q_matmul_info, step=0)
            _bus.emit("moment_bytes", self._moment_bytes_info, step=0)

    def _refresh_zero_pads(self):
        """Index the params whose storage is padded to the ZeRO shard
        multiple (param._zero_pad contract, fleet._DistributedOptimizer):
        the traced unpad below slices them back to logical shape before
        the model sees them."""
        self._zero_pads = [
            (i, p._zero_pad) for i, p in enumerate(self._p_objs)
            if getattr(p, "_zero_pad", None) is not None
        ]

    def _unpad_params(self, p_tuple):
        if not self._zero_pads:
            return p_tuple
        out = list(p_tuple)
        for i, (axis, logical) in self._zero_pads:
            v = out[i]
            out[i] = v[tuple(
                slice(0, logical) if a == axis else slice(None)
                for a in range(v.ndim))]
        return tuple(out)

    # -- the pure program ----------------------------------------------------
    def _amp_guard(self):
        if self._amp_ctx is None:
            return contextlib.nullcontext()
        from .. import amp

        return amp.auto_cast(**self._amp_ctx)

    def _q_guard(self):
        if self._q_matmul is None:
            return contextlib.nullcontext()
        from ..distributed import quantized_compute as _qcp

        return _qcp.matmul_scope(self._q_matmul)

    def _fwd_segment(self, p_tuple, b_raws, key, in_raws):
        """Model forward as a pure pytree function — the jax.checkpoint
        (remat) boundary when strategy.recompute is on (RecomputeOptimizer
        analog, fluid/optimizer.py:4549)."""
        from .. import profiler as _prof

        p_objs, b_objs = self._p_objs, self._b_objs
        with AG.trace_mode(), _trace_rng(key), self._amp_guard(), \
                self._q_guard(), \
                _prof.device_annotation("TrainStep::forward"), \
                _swapped(p_objs + b_objs, list(p_tuple) + list(b_raws)):
            outs = self.model(*[Tensor._wrap(r) for r in in_raws])
            out_raw = jax.tree_util.tree_map(
                lambda v: v._data if isinstance(v, Tensor) else v,
                outs, is_leaf=lambda v: isinstance(v, Tensor),
            )
            new_b = tuple(b._data for b in b_objs)
        return out_raw, new_b

    def _loss_of(self, p_tuple, b_raws, key, in_raws, label_raws):
        # padded ZeRO storage comes down to logical shapes here — the
        # "unpad on gather": grads w.r.t. the padded operands carry zeros
        # in the pad rows, so the update stays exact in padded space
        p_tuple = self._unpad_params(tuple(p_tuple))
        # disjoint RNG streams for the two trace regions (the fwd segment
        # may be recomputed in backward and must redraw identically)
        fwd_key = None if key is None else jax.random.fold_in(key, 0)
        loss_key = None if key is None else jax.random.fold_in(key, 1)
        fwd = jax.checkpoint(self._fwd_segment) if self._recompute \
            else self._fwd_segment
        out_raw, new_b = fwd(tuple(p_tuple), b_raws, fwd_key, in_raws)
        outs = jax.tree_util.tree_map(Tensor._wrap, out_raw)
        # loss_fn sees the TRACED params/post-forward buffers (it may read
        # model.parameters() for a penalty term) and its own RNG stream
        with AG.trace_mode(), _trace_rng(loss_key), self._amp_guard(), \
                self._q_guard(), \
                _swapped(self._p_objs + self._b_objs,
                         list(p_tuple) + list(new_b)):
            labels = [Tensor._wrap(r) for r in label_raws]
            loss = self.loss_fn(outs, *labels)
            loss_raw = loss._data if isinstance(loss, Tensor) else loss
        return loss_raw, (new_b, out_raw if self._ret_out else None)

    def _step_fn(self, p_raws, opt_state, b_raws, key, lr, t, scaler_state,
                 guard_state, inject, in_raws, label_raws):
        if self._async_dcn:
            # manual over 'dcn', GSPMD-auto over every other axis: each
            # grad's inter-node pmean sits at its definition point in
            # the backward dataflow (schedulable behind the remaining
            # backward compute) instead of a combined tail collective
            from ..distributed.overlap import dcn_value_and_grad

            loss, grads = dcn_value_and_grad(
                self._loss_of, self._dcn_mesh, p_raws, key, in_raws,
                label_raws, quant=self._dcn_quant,
            )
            new_b, outs = (), None
        elif self._loss_scale_cfg is None:
            (loss, (new_b, outs)), grads = jax.value_and_grad(
                lambda p: self._loss_of(p, b_raws, key, in_raws, label_raws),
                has_aux=True,
            )(tuple(p_raws))
        else:
            scale = scaler_state[0]

            def scaled(p):
                loss, aux = self._loss_of(
                    p, b_raws, key, in_raws, label_raws
                )
                return loss * scale.astype(loss.dtype), (loss, aux)

            (_, (loss, (new_b, outs))), grads = jax.value_and_grad(
                scaled, has_aux=True
            )(tuple(p_raws))
            grads = tuple(
                None if g is None else g / scale.astype(g.dtype)
                for g in grads
            )
        grads = list(grads)
        if self._used_mask is not None:
            grads = [g if used else None
                     for g, used in zip(grads, self._used_mask)]
        if self._inject_enabled:
            # PADDLE_FAULT_SPEC=grad:nan|inf|spike — the traced selector
            # poisons every grad in-graph (x1 on clean steps is exact,
            # so the armed program stays numerically identical when idle)
            factor = jnp.asarray(
                [1.0, jnp.nan, jnp.inf, 1e4], jnp.float32)[inject]
            grads = [None if g is None else g * factor.astype(g.dtype)
                     for g in grads]
        grads = self._process_grads(list(p_raws), grads)
        if self._loss_scale_cfg is not None:
            # bias-correction time must count APPLIED updates, not
            # attempted steps (the eager scaler skips optimizer.step()
            # entirely on overflow) — it rides in the scaler state
            t = (scaler_state[3] + 1).astype(t.dtype)
        from .. import profiler as _prof

        with _prof.device_annotation("TrainStep::opt_update"):
            new_p, new_state = self.opt._functional_update(
                self._p_objs, list(p_raws), grads, opt_state, lr, t
            )
        if self._guard is not None:
            # the sentinel: one fused grad reduction + scalar flags;
            # the policy update folds in spike detection and returns the
            # apply verdict (nonfinite OR exploded-gnorm steps mask)
            with _prof.device_annotation("TrainStep::guard"):
                ok, bits, gnorm = _TG.grad_health(loss, grads, new_p)
                guard_state, ok_apply = _TG.update_guard_state(
                    guard_state, ok, bits, gnorm, loss
                )
            if self._loss_scale_cfg is not None:
                # the scaler's skip masking doubles as the guard's, and
                # a guard trip counts as a bad step -> scale backoff
                new_p, new_state, scaler_state = self._apply_loss_scaling(
                    grads, p_raws, opt_state, new_p, new_state,
                    scaler_state, finite=ok_apply,
                )
            else:
                new_p = _TG.mask_step(ok_apply, tuple(new_p),
                                      tuple(p_raws))
                new_state = _TG.mask_step(ok_apply, new_state, opt_state)
            # forward-updated buffers (BN stats) are masked too: a
            # nonfinite activation pass must not poison running stats
            new_b = _TG.mask_step(ok_apply, new_b, b_raws)
        elif self._loss_scale_cfg is not None:
            new_p, new_state, scaler_state = self._apply_loss_scaling(
                grads, p_raws, opt_state, new_p, new_state, scaler_state
            )
        return (loss, new_p, new_state, new_b, outs, scaler_state,
                guard_state)

    def _apply_loss_scaling(self, grads, p_raws, opt_state, new_p, new_state,
                            scaler_state, finite=None):
        """Fused check_finite_and_unscale + update_loss_scaling
        (operators/amp/check_finite_and_unscale_op.cc,
        update_loss_scaling_op.cc): ONE all-grads finite reduction in the
        compiled program — no per-param host sync (r3 weak #3). Non-finite
        steps keep params/state and shrink the scale. The numerical guard
        passes its (wider: loss + grads + params) health word as `finite`
        so a guard trip also backs the scale off."""
        cfg = self._loss_scale_cfg
        if finite is None:
            finite = jnp.all(jnp.stack([
                jnp.isfinite(g).all() for g in grads if g is not None
            ]))
        sel = lambda new, old: jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o), new, old
        )
        new_p = sel(tuple(new_p), tuple(p_raws))
        new_state = sel(new_state, opt_state)
        scale, good, bad, t_applied = scaler_state
        t_applied = jnp.where(finite, t_applied + 1, t_applied)
        good = jnp.where(finite, good + 1, 0)
        bad = jnp.where(finite, 0, bad + 1)
        do_incr = finite & (good >= cfg["incr_every_n_steps"])
        do_decr = (~finite) & (bad >= cfg["decr_every_n_nan_or_inf"])
        scale = jnp.where(do_incr, scale * cfg["incr_ratio"], scale)
        scale = jnp.where(
            do_decr, jnp.maximum(scale * cfg["decr_ratio"], 1.0), scale
        )
        good = jnp.where(do_incr, 0, good)
        bad = jnp.where(do_decr, 0, bad)
        return new_p, new_state, (scale, good, bad, t_applied)

    def _analyze_usage(self, p_raws, b_raws, key, in_raws, label_raws):
        """Which params does the loss actually read? (one abstract trace).

        Eager `.backward()` leaves `.grad` as None for params off the tape
        and `step()` skips them; jax.grad instead returns zeros. Matching
        the eager/reference semantics (optimizer.py step: `p.grad is not
        None`) requires knowing reachability — read it off the jaxpr.
        """
        closed = jax.make_jaxpr(
            lambda p: self._loss_of(p, b_raws, key, in_raws, label_raws)[0]
        )(tuple(p_raws))
        used = set()
        for eqn in closed.jaxpr.eqns:
            for v in eqn.invars:
                used.add(id(v))
        for v in closed.jaxpr.outvars:
            used.add(id(v))
        n_p = len(self._p_objs)
        return tuple(id(v) in used for v in closed.jaxpr.invars[:n_p])

    def _process_grads(self, p_raws, g_raws):
        return process_grads(
            self.opt, self._p_objs, p_raws, g_raws, self._grad_post_hook
        )

    def _place_guard_state(self, gs):
        """Replicate the guard carry on the hybrid mesh (same reason the
        ctor normalizes param placement: a single-device operand among
        mesh-placed ones changes the input signature after GSPMD
        normalizes the outputs — one full retrace of the step)."""
        from ..distributed import comm as _comm

        mesh = _comm.hybrid_mesh()
        if mesh is not None and mesh.size > 1:  # trivial mesh = no mesh
            from jax.sharding import NamedSharding, PartitionSpec as _P

            gs = jax.device_put(gs, NamedSharding(mesh, _P()))
        return gs

    def _after_rollback(self):
        """Guard rollback hook: the checkpoint restore already rewrote
        p_objs/opt (and, when this step is registered as an extra, the
        scaler + guard counters through set_state_dict) — re-seed the
        device guard carry from the restored host counters."""
        if self._guard is not None:
            self._guard_state = self._place_guard_state(
                self._guard.restored_device_state())

    # -- elastic resharding (distributed/resharding.py, ISSUE 11) ----------
    def rebind_mesh(self, mesh):
        """Move every piece of step state onto `mesh` device-to-device
        and drop the compiled program — the reshard executor. Params,
        buffers, optimizer accumulators, the fp16 scaler and the guard
        carry are re-placed with jax.device_put (replicated, or the
        param's tensor-parallel spec); ZeRO pad-to-shard-multiple storage
        is stripped first and re-derived for the new dp. The next call
        re-jits: ONE bounded recompile, attributed by the recompile
        ledger under the same "TrainStep" label."""
        if self._delegate is not None:
            raise NotImplementedError(
                "elastic resharding does not compose with localsgd: "
                "LocalSGDStep carries per-replica state the reshard "
                "planner does not cover yet"
            )
        from jax.sharding import NamedSharding, PartitionSpec as _P

        if self._async_dcn:
            if "dcn" not in mesh.axis_names or int(mesh.shape["dcn"]) <= 1:
                raise ValueError(
                    "the explicit dcn grad reduction needs a dcn axis "
                    "(> 1) on the resharded mesh — the planner must keep "
                    "the hierarchical factoring"
                )
            self._dcn_mesh = mesh
        # pads are sized for the OLD dp — strip to logical shapes, move,
        # then re-pad for the new factoring
        if hasattr(self.opt, "_strip_zero_padding"):
            self.opt._strip_zero_padding(self._p_objs)
        repl = NamedSharding(mesh, _P())
        for p in self._p_objs:
            spec = getattr(p, "_tp_spec", None)
            sh = NamedSharding(mesh, spec) if spec is not None else repl
            p._data = jax.device_put(p._data, sh)
        for b in self._b_objs:
            b._data = jax.device_put(b._data, repl)
        spec_of = {id(p): getattr(p, "_tp_spec", None)
                   for p in self._p_objs}
        shape_of = {id(p): tuple(p._data.shape) for p in self._p_objs}

        def _axes_size(entry):
            size = 1
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if a not in mesh.axis_names:
                    return None
                size *= int(mesh.shape[a])
            return size

        def _carry_spec(v):
            """Keep a leaf's CURRENT partitioning on the new mesh when
            it still fits (ZeRO dp-sharded moments must not transit
            through full replication — that spike is the memory the
            sharding exists to avoid); replicate only when the old spec
            no longer divides, and let the next step's in-graph
            constraint re-shard."""
            sh = getattr(v, "sharding", None)
            if not isinstance(sh, NamedSharding) or sh.spec is None:
                return None
            for dim, entry in zip(v.shape, sh.spec):
                if entry is None:
                    continue
                size = _axes_size(entry)
                if size is None or dim % size:
                    return None
            return sh.spec

        inner = getattr(self.opt, "_inner", self.opt)
        for store in getattr(inner, "_accumulators", {}).values():
            if not isinstance(store, dict):
                continue
            for pid, v in store.items():
                spec = spec_of.get(pid)
                if spec is not None and hasattr(v, "shape") \
                        and tuple(v.shape) == shape_of.get(pid):
                    sh = NamedSharding(mesh, spec)
                else:
                    carried = _carry_spec(v) if hasattr(v, "shape") \
                        else None
                    sh = NamedSharding(mesh, carried) \
                        if carried is not None else repl
                store[pid] = jax.device_put(v, sh)
        if self._scaler_state:
            self._scaler_state = tuple(
                jax.device_put(v, repl) for v in self._scaler_state)
        if self._guard is not None and self._guard_state is not None \
                and len(self._guard_state):
            self._guard_state = jax.device_put(self._guard_state, repl)
        if hasattr(self.opt, "_apply_zero_padding"):
            self.opt._apply_zero_padding(self._p_objs)
        self._refresh_zero_pads()
        self._jitted = None
        self._lower_avals = None

    # -- persisted step state (the auto_checkpoint `extras` contract) -----
    def state_dict(self):
        """Dynamic loss-scaler state (scale, growth counter, skip count,
        applied-update clock) + guard counters — the step state that was
        silently lost on save/restore before this landed. Register the
        step with TrainEpochRange (``register(extras=step)``) to carry
        it through snapshot generations."""
        import numpy as np

        out = {}
        if self._loss_scale_cfg is not None:
            scale, good, bad, t_applied = self._scaler_state
            out["scaler"] = {
                "scale": float(np.asarray(scale)),
                "good_steps": int(np.asarray(good)),
                "bad_steps": int(np.asarray(bad)),
                "applied_steps": int(np.asarray(t_applied)),
            }
        if self._guard is not None:
            out["guard"] = self._guard.state_dict()
        return out

    def set_state_dict(self, state):
        state = dict(state or {})
        sc = state.get("scaler")
        if self._loss_scale_cfg is not None and sc:
            self._scaler_state = (
                jnp.asarray(sc["scale"], jnp.float32),
                jnp.asarray(sc["good_steps"], jnp.int32),
                jnp.asarray(sc["bad_steps"], jnp.int32),
                jnp.asarray(sc["applied_steps"], jnp.int32),
            )
        if self._guard is not None and state.get("guard"):
            self._guard.set_state_dict(state["guard"])
            self._guard_state = self._place_guard_state(
                self._guard.restored_device_state())

    # -- eager entry ---------------------------------------------------------
    def __call__(self, inputs, labels=None):
        from .. import profiler as _profiler

        with _profiler.RecordEvent("TrainStep"):
            return self._call_impl(inputs, labels)

    def _call_impl(self, inputs, labels=None):
        if self._delegate is not None:
            return self._delegate(inputs, labels)
        from .. import profiler as _prof
        from ..observability import bus as _bus

        # three flat phases on the profiler's clock (`profiler.phase`):
        # an idle gap of the device under a step reads as one of them
        with _prof.phase("TrainStep.prepare"):
            opt = self.opt
            in_raws = tuple(
                x._data if isinstance(x, Tensor) else jnp.asarray(x)
                for x in _as_list(inputs)
            )
            label_raws = tuple(
                y._data if isinstance(y, Tensor) else jnp.asarray(y)
                for y in _as_list(labels)
            )
            p_raws = tuple(p._data for p in self._p_objs)
            opt_state = opt._functional_state(self._p_objs)
            b_raws = tuple(b._data for b in self._b_objs)
            key = rnd.next_key()
            if self._used_mask is None:
                self._used_mask = self._analyze_usage(
                    p_raws, b_raws, key, in_raws, label_raws
                )
            if self._jitted is None:
                (p_raws, opt_state, b_raws, self._scaler_state,
                 self._guard_state) = _commit_on_one_device(
                    (p_raws, opt_state, b_raws, self._scaler_state,
                     self._guard_state))
                # pin state outputs to their input shardings — EXCEPT what
                # the ZeRO strategy intentionally reshards (stage>=1 shards
                # the optimizer state inside the update, stage 3 the
                # params): those converge to their sharded form after one
                # call instead
                from jax.sharding import NamedSharding as _NS

                def pin(tree):
                    # only NamedSharding leaves are pinned; single-device
                    # leaves (e.g. freshly made scalar counters) stay
                    # unconstrained — pinning them to device 0 conflicts
                    # with mesh-placed operands
                    return jax.tree_util.tree_map(
                        lambda r: r.sharding
                        if isinstance(getattr(r, "sharding", None), _NS)
                        else None,
                        tree,
                    )
                stage = int(getattr(self.opt, "_sharding_stage", 0) or 0)
                out_sh = (
                    None,                                    # loss
                    pin(p_raws) if stage < 3 else None,      # new_p
                    pin(opt_state) if stage < 1 else None,   # new_state
                    pin(b_raws),                             # new_b
                    None,                                    # outs
                    None,                                    # scaler_state
                    pin(self._guard_state),                  # guard_state
                )
                # params, opt state, buffers — and the loss-scaler state
                # when dynamic scaling is on (replaced every step, same
                # shape) — are donated so XLA updates them in place in HBM.
                # The guard carry is NOT donated: the host monitor still
                # holds the previous step's vector for its deferred read
                # (observe()'s async prefetch), and donating it would
                # invalidate that buffer the moment it is re-passed — a
                # 40-byte array buys nothing from donation anyway.
                donate = (0, 1, 2) if self._donate else ()
                if self._donate and self._loss_scale_cfg is not None:
                    donate = donate + (6,)
                from ..observability import ledger as _ledger

                # the ledger wrapper turns every jit cache miss into a
                # `recompile` bus record (arg fingerprint + compile seconds)
                # — one integer compare per call on the hit path — and
                # names the module `jit_TrainStep` in a device trace
                self._jitted = _ledger.jit(self._step_fn, "TrainStep",
                                           donate_argnums=donate,
                                           out_shardings=out_sh)
            opt._step_count += 1
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            t = jnp.asarray(opt._step_count, jnp.float32)
            inject = (_FI.consume_grad_action() if self._inject_enabled else 0)
            if self._guard is not None:
                self._guard.capture(key, in_raws, label_raws)
            # observability per-step hooks (one int assign + one None check
            # when nothing is armed): the bus step index events inherit, and
            # the capture-on-anomaly trace window opens BEFORE the dispatch
            # it is meant to cover
            self._n_steps += 1
            _bus.set_step(self._n_steps)
            _prof.step_boundary(self._n_steps)
            call_args = (
                p_raws, opt_state, b_raws, key, lr, t, self._scaler_state,
                self._guard_state, jnp.asarray(inject, jnp.int32),
                in_raws, label_raws,
            )
            if self._lower_avals is None:
                # shape/dtype skeleton of the call signature, kept so the
                # step can be lowered again without its arrays
                # (chip_smoke.py counts the Mosaic calls of the lowered
                # text): donated buffers are invalidated after dispatch,
                # avals hold no storage
                self._lower_avals = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if hasattr(x, "shape") and hasattr(x, "dtype") else x,
                    call_args,
                )
        with _prof.phase("TrainStep.dispatch"):
            (loss, new_p, new_state, new_b, outs, self._scaler_state,
             self._guard_state) = self._jitted(*call_args)
        with _prof.phase("TrainStep.rebind"):
            for p, raw in zip(self._p_objs, new_p):
                p._data = raw
                p._node = None
                p.grad = None
            opt._load_functional_state(self._p_objs, new_state)
            for b, raw in zip(self._b_objs, new_b):
                b._data = raw
                b._node = None
            if self._guard is not None:
                # lazy, interval-synced policy read; on rollback the guard's
                # _on_rollback hook (-> _after_rollback) has already
                # refreshed the device carries
                self._guard.observe(self._guard_state)
        loss_t = Tensor._wrap(loss, stop_gradient=True)
        if self._ret_out:
            outs_t = jax.tree_util.tree_map(
                lambda r: Tensor._wrap(r, stop_gradient=True), outs
            )
            return loss_t, outs_t
        return loss_t
