"""95th percentile over all requests of (last token - first token) / (tokens - 1), each token timed when the turn that produced it has returned (host clock)."""
from metric_lib import p95


def read(ctx):
    return p95(ctx["serve"]["tpot_ms"])
