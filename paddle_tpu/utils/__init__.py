"""paddle_tpu.utils — developer tooling (custom ops,
deterministic fault injection for the elastic runtime, numerical
training guardrails)."""
from . import (  # noqa: F401
    custom_op, download, fault_injection, train_guard,
)
from .custom_op import register_op  # noqa: F401
