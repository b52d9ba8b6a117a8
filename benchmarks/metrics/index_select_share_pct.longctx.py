"""Device time of the instructions that are wholly the indexer's scores and the selection (the kernels `dsa_index` and `dsa_select` of trace_names/pr34_names.json: instruction names with result signatures) over the device's busy time in the traced window (device trace)."""


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    spent = sum(c["dur_s"] for k in ("dsa_index", "dsa_select")
                for c in t["kernels"].get(k, ()))
    return 100.0 * spent / t["busy_s"] if spent else None
