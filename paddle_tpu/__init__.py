"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle(~2.0)
capabilities, built on JAX/XLA/Pallas/pjit.

Top-level namespace mirrors `paddle` (reference: python/paddle/__init__.py):
tensor creation/math ops, Tensor, no_grad, save/load, set_device, plus the
subpackages nn/optimizer/io/vision/metric/amp/jit/static/distributed.

Architecture is TPU-first, not a port (see SURVEY.md): eager ops dispatch to
XLA via jax with a tape recording per-op VJPs (imperative/ analog); the
static/jit path traces whole programs into single compiled executables
(framework/executor analog); distribution is jax.sharding meshes + XLA
collectives, not comm rings.
"""
from __future__ import annotations

import time as _time

_t_import = _time.perf_counter()

# core first (no heavy deps)
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Parameter,
    Place,
    TPUPlace,
    Tensor,
    enable_grad,
    get_default_dtype,
    get_device,
    grad,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_grad_enabled,
    no_grad,
    seed,
    set_default_dtype,
    set_device,
    set_grad_enabled,
)
from .core.flags import get_flags, set_flags  # noqa: F401
# the compile ledger's jax.monitoring listener: every compile from here on
from .observability import ledger as _ledger  # noqa: F401

# the full flat op namespace (paddle.add, paddle.matmul, ...)
from .ops import *  # noqa: F401,F403
from . import nn  # noqa: F401
from .nn.layer import ParamAttr  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import amp  # noqa: F401
from . import jit  # noqa: F401
from . import distributed  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .framework.io import load, save  # noqa: F401
from . import hapi  # noqa: F401
from . import profiler  # noqa: F401
from . import static  # noqa: F401
from .hapi import Model, flops, summary  # noqa: F401
from .ops import creation, linalg, logic, manipulation, math, search  # noqa: F401
from .ops.creation import to_tensor  # noqa: F401
from .ops.logic import is_tensor  # noqa: F401

__version__ = "0.1.0"


def disable_static(place=None):
    """2.0 default mode is dygraph."""
    from . import static as static_mod

    static_mod._disable()


def enable_static():
    """Switch to static-graph mode: supported via paddle_tpu.static."""
    from . import static as static_mod

    static_mod._enable()


def in_dynamic_mode() -> bool:
    from . import static as static_mod

    return not static_mod._static_mode_on()


# paddle.abs etc. come from ops import *; math.max/min shadow builtins only
# inside this namespace, matching paddle's own API.
from . import text  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from .batch import batch  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from . import tensor  # noqa: F401,E402

#: wall seconds this package's own import took, jax included when this
#: import was the first to pull it in: the part of a process's set-up
#: that is spent before any program is built
import_seconds = _time.perf_counter() - _t_import
