"""The busiest held expert's assignments over the mean held expert's, prefill and decode together, averaged over the routed layers: 1 is even routing (program counter: observability.metrics.expert_load(); the `.longdoc` cell's reader, found by its name)."""
import find

read = find.load("metrics", "expert_load_max_over_mean.longdoc").read
