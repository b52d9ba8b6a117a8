"""Automatic mixed precision.

reference: python/paddle/amp/auto_cast.py:20 (auto_cast over
fluid/dygraph/amp/auto_cast.py:91 amp_guard), grad_scaler.py:20 (GradScaler
over loss_scaler.py:27 AmpScaler: scale :119, minimize :156), C++ white/
black op lists (paddle/fluid/imperative/amp_auto_cast.h:31), and the AMP
primitive ops check_finite_and_unscale / update_loss_scaling
(operators/amp/).

TPU-first: the default low-precision dtype is bfloat16 — same exponent
range as f32, so loss scaling is unnecessary for the default path (the
GradScaler degrades to a pass-through unless fp16 is requested, matching
how the reference's scaler behaves with use_dynamic_loss_scaling=False).
The white/black lists mirror the reference's: matmul/conv cast down (MXU
ops), reductions/softmax/norm stay f32.
"""
from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp
import numpy as np

from ..core.dtype import convert_dtype
from ..core.tensor import Tensor

__all__ = ["auto_cast", "amp_guard", "GradScaler", "AmpScaler", "decorate"]

# op categories (imperative/amp_auto_cast.cc AmpOperators)
WHITE_LIST = {"matmul", "linear", "conv2d", "conv1d", "conv3d", "einsum",
              "bmm", "mm", "mv", "attention_scores", "attention_context",
              "flash_attention"}
# fused_layer_norm / fused_residual_layer_norm are deliberately on NEITHER
# list: the Pallas kernels take bf16 activations as-is and do their
# statistics in f32 internally — black-listing them would reintroduce the
# f32 HBM round trip they exist to remove (the dense "layer_norm" stays
# black-listed). fused_linear_cross_entropy likewise: its vocab-chunk
# matmuls accumulate f32 via preferred_element_type while the [N, d]
# hidden input stays in the compute dtype.
BLACK_LIST = {"softmax", "log_softmax", "cross_entropy", "mean", "sum",
              "layer_norm", "exp", "log", "logsumexp",
              "softmax_with_cross_entropy"}
# batch_norm is deliberately NOT black-listed: the functional keeps its
# stat accumulation in f32 internally while applying in the input dtype,
# so casting bf16 activations up before it would only double HBM traffic


class _AmpState(threading.local):
    def __init__(self):
        self.enabled = False
        self.dtype = jnp.bfloat16
        self.level = "O1"
        self.custom_white = set()
        self.custom_black = set()


_state = _AmpState()


def is_enabled() -> bool:
    return _state.enabled


def amp_dtype():
    return _state.dtype


def should_cast_down(op_name: str) -> bool:
    if not _state.enabled:
        return False
    if op_name in _state.custom_black or op_name in BLACK_LIST:
        return False
    if _state.level == "O2":
        return True
    return op_name in WHITE_LIST or op_name in _state.custom_white


def _cast_floats(raws, d):
    return tuple(
        r.astype(d)
        if hasattr(r, "dtype")
        and jnp.issubdtype(r.dtype, jnp.floating)
        and r.dtype != d
        else r
        for r in raws
    )


def cast_if_amp(op_name: str, raws):
    """AutoCastInputs analog (tracer.cc:159): white-list ops cast float
    inputs down to the amp dtype; black-list ops cast up to f32; the rest
    pass through."""
    if not _state.enabled or op_name is None:
        return raws
    if op_name in _state.custom_black or op_name in BLACK_LIST:
        return _cast_floats(raws, jnp.float32)
    if should_cast_down(op_name):
        return _cast_floats(raws, _state.dtype)
    return raws


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast (auto_cast.py:20)."""
    prev = (_state.enabled, _state.dtype, _state.level,
            _state.custom_white, _state.custom_black)
    _state.enabled = bool(enable)
    _state.dtype = convert_dtype(dtype)
    _state.level = level
    _state.custom_white = set(custom_white_list or ())
    _state.custom_black = set(custom_black_list or ())
    try:
        yield
    finally:
        (_state.enabled, _state.dtype, _state.level,
         _state.custom_white, _state.custom_black) = prev


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O1", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate: O2 casts model params to the amp dtype (master
    weights stay f32 inside the optimizer accumulators)."""
    if level == "O2":
        for m in models if isinstance(models, (list, tuple)) else [models]:
            m.to(dtype=dtype)
    if optimizers is None:
        return models
    return models, optimizers


def _unscale_rule(gs, s):
    out = tuple(g / s.astype(g.dtype) for g in gs)
    finite = jnp.all(jnp.stack([jnp.isfinite(g).all() for g in out]))
    return out, ~finite


_unscale_jitted = None


def _unscale_fused(grads, scale):
    """One compiled program: g/scale for every grad + a single fused
    finiteness reduction (cached per grad-shape structure by jax.jit)."""
    global _unscale_jitted
    if _unscale_jitted is None:
        import jax

        _unscale_jitted = jax.jit(_unscale_rule)
    return _unscale_jitted(grads, jnp.asarray(scale, jnp.float32))


class GradScaler:
    """Dynamic loss scaling (grad_scaler.py:20 / AmpScaler loss_scaler.py:27).

    With bf16 (TPU default) scaling is unnecessary: enable=True still works
    but becomes a no-op multiply by 1 unless init_loss_scaling != 1.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0**15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling) if enable else 1.0
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling and enable
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False

    def scale(self, loss: Tensor) -> Tensor:
        """AmpScaler.scale (loss_scaler.py:119)."""
        if not self._enable or self._scale == 1.0:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        """check_finite_and_unscale analog (operators/amp/
        check_finite_and_unscale_op.cc): divide grads by scale, flag
        non-finite — ONE fused program over all grads and a single
        device->host sync, like the reference's single kernel over the
        whole grad list (not one launch + sync per parameter)."""
        if not self._enable:
            return
        grads = [p.grad._data for p in optimizer._get_params()
                 if p.grad is not None]
        if not grads:
            self._found_inf = False
            return
        new_grads, found = _unscale_fused(tuple(grads), self._scale)
        it = iter(new_grads)
        for p in optimizer._get_params():
            if p.grad is not None:
                p.grad._data = next(it)
        self._found_inf = bool(found)

    def step(self, optimizer):
        """Skip the update on inf/nan; update the scale (AmpScaler.minimize
        loss_scaler.py:156 + update_loss_scaling op)."""
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._dynamic and self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            optimizer.step()
            self._good_steps += 1
            self._bad_steps = 0
            if self._dynamic and self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def update(self):
        pass  # folded into step()

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        optimizer.clear_grad()

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_loss_scaling(self):
        return self._scale

    def set_init_loss_scaling(self, v):
        self._scale = float(v)

    def state_dict(self):
        return {
            "scale": self._scale,
            "incr_ratio": self._incr_ratio,
            "decr_ratio": self._decr_ratio,
            "incr_every_n_steps": self._incr_every,
            "decr_every_n_nan_or_inf": self._decr_every,
            "good_steps": self._good_steps,
            "bad_steps": self._bad_steps,
        }

    def load_state_dict(self, state):
        self._scale = state.get("scale", self._scale)
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)


AmpScaler = GradScaler
