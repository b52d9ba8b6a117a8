"""All tokens of all steps of the window over the whole window, which ends when the last step's loss is on the host (host clock)."""
from metric_lib import train_tokens_per_s as read  # noqa: F401
