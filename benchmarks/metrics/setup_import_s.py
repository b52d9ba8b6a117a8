"""Wall seconds `import paddle_tpu` took, jax included, `paddle_tpu.import_seconds` (program counter)."""
from phase_lib import import_seconds as read  # noqa: F401
