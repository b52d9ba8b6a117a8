"""The busiest held expert's assignments over the mean held expert's, prefill and decode together, averaged over the routed layers: 1 is even routing (program counter: observability.metrics.expert_load(), the device counters of nn.RoutedExperts as the engine's last readback read them)."""
import numpy as np


def read(ctx):
    from paddle_tpu.observability import metrics

    loads = getattr(metrics, "expert_load", lambda: {})()
    ratios = []
    for load in loads.values():
        held = np.asarray(load)[:, :-1].sum(0)        # prefill + decode
        if held.sum() > 0:
            ratios.append(held.max() / held.mean())
    return float(np.mean(ratios)) if ratios else None
