"""Device time of the instructions the rules of trace_names/ssm_names.json name `ssm_step` (the state-space layers' one-token state update) over the DecodeStep program's device time, both over the whole traced window (device trace)."""


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    dec = t["programs"].get("decode_step")
    calls = t["kernels"].get("ssm_step")
    if not dec or not calls or dec["all_device_s"] <= 0:
        return None
    return 100.0 * sum(c["dur_s"] for c in calls) / dec["all_device_s"]
