"""The bytes the decode steps' one-token state updates have to move (each slot's float32 state and convolution tail read and written, its x, B, C and step-size rows read, over the state-space layers: the family's ssm_step_bytes for the engine's slots) / peak bandwidth, over the device time of the instructions the rules of trace_names/ssm_names.json name `ssm_step`; the steps are the `state` part's calls over the state-space layers (device trace)."""
from metric_lib import peak


def read(ctx):
    calls = (ctx.get("trace") or {}).get("kernels", {}).get("ssm_step")
    if not calls:
        return None
    s = ctx["sizes"]
    state = sum(1 for c in calls if c["rule"].get("part") == "state")
    spent = sum(c["dur_s"] for c in calls)
    if not state or spent <= 0:
        return None
    steps = state / s["mamba_layers"]
    nbytes = steps * ctx["family"].ssm_step_bytes(s, ctx["slots"])
    return 100.0 * (nbytes / peak(ctx)["hbm_bytes_per_s"]) / spent
