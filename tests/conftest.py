"""Test harness config.

Runs the whole suite on the CPU backend with 8 virtual devices so collective
and sharding tests exercise a real 8-way mesh without TPU hardware (the
analog of the reference's single-host multiprocess dist tests,
python/paddle/fluid/tests/unittests/test_dist_base.py:671 — here ranks are
in-process XLA devices, SURVEY.md §4 TPU equivalent).

`force_cpu_devices` runs before the first backend query: importing
paddle_tpu has imported jax (which captured JAX_PLATFORMS), so the platform
goes in through jax.config, and XLA_FLAGS is read lazily at backend init so
appending the device-count flag here works. Note the host may export
XLA_FLAGS="" (empty), so append rather than setdefault.
"""
from paddle_tpu.core.device import force_cpu_devices

force_cpu_devices(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Numeric-check tests compare against float64 numpy references; use full
# f32 matmul precision (the framework's default elsewhere is bf16-on-MXU).
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu

    paddle_tpu.seed(102)
    np.random.seed(102)
    yield
