"""How late the pump ran: 95th percentile of engine.submit - due (host clock)."""
from metric_lib import p95


def read(ctx):
    return p95(ctx["serve"]["lateness_ms"])
