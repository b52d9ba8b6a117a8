"""Device time of the instructions the rules of trace_names/ssm_names.json name `ssm_chunk_scan` (the state-space layers' chunked scan of a prefill) over the PrefillStep program's device time, both over the whole traced window (device trace)."""


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    pre = t["programs"].get("prefill_step")
    calls = t["kernels"].get("ssm_chunk_scan")
    if not pre or not calls or pre["all_device_s"] <= 0:
        return None
    return 100.0 * sum(c["dur_s"] for c in calls) / pre["all_device_s"]
