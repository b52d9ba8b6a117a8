"""All generated tokens read back in the window over the whole window (host clock)."""
from metric_lib import serve_tokens_per_s as read  # noqa: F401
