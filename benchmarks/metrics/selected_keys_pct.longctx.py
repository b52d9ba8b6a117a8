"""(Query, key) pairs the indexer selected over the pairs visible, prefill and decode together, summed over the layers: 100 while every context is under topk (program counter: observability.metrics.selected_keys(), the device counters of nn.IndexedAttention as the engine's last readback read them)."""
import numpy as np


def read(ctx):
    from paddle_tpu.observability import metrics

    keys = getattr(metrics, "selected_keys", lambda: {})()
    if not keys:
        return None
    visible, selected = np.sum([np.asarray(k).sum(0)
                                for k in keys.values()], axis=0)
    return 100.0 * float(selected) / float(visible) if visible else None
