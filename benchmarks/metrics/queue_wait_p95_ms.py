"""95th percentile of due -> prefill starts: the pump's lateness plus the engine's own ttft_ms - prefill_ms of the request (program span)."""
from metric_lib import p95


def read(ctx):
    return p95(ctx["serve"]["queue_wait_ms"])
