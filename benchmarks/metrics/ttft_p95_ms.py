"""95th percentile over ALL requests due in the window of: due -> the turn that produced the first token has returned (host clock). An unfinished request counts as the worst."""
from metric_lib import p95


def read(ctx):
    return p95(ctx["serve"]["ttft_ms"])
