"""Model operations a token (forward + backward, no optimizer, no recomputation) x train_tokens_per_s over chips x peak."""
from metric_lib import mfu_train as read  # noqa: F401
