"""Drive one run of a state-space hybrid cell with one part of its
mathematics left out of the timed path:
`python broken_longgen.py <fault> <workload> <seed> [chip]`, as
`broken_run.py` beside this file does for the faults it knows. The run has
to print `correct: false`.

| fault | what the program does instead |
|---|---|
| `padding_advances_state` | a prefill's padded positions advance the state (their step size and input are kept) |
| `no_d_skip` | the skip D * x is left out of every mixer's output |
| `bf16_state` | the state is rounded to bfloat16 each time a scan or a step hands it on |
| `conv_tail_from_padding` | the carried convolution tail is the last inputs of the padded chunk, not of its valid positions |
"""
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]


def plant(fault: str, set_attr=setattr) -> None:
    """Put `fault` into `nn.functional.ssm` through `set_attr` (a test
    passes one that undoes itself)."""
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import ssm as S

    if fault == "padding_advances_state":
        set_attr(S, "_valid",
                 lambda length, T: jnp.ones((length.shape[0], T), bool))
    elif fault == "no_d_skip":
        sound_mixer = S.mixer

        def no_skip(u, params, *a, **kw):
            from paddle_tpu.core.tensor import Tensor

            params = list(params)
            params[5] = Tensor._wrap(jnp.zeros_like(params[5]._data))
            return sound_mixer(u, tuple(params), *a, **kw)

        set_attr(S, "mixer", no_skip)
    elif fault == "bf16_state":
        def rounded(fn):
            def f(*a, **kw):
                y, state = fn(*a, **kw)
                return y, state.astype(jnp.bfloat16).astype(jnp.float32)
            return f

        set_attr(S, "ssd_scan", rounded(S.ssd_scan))
        set_attr(S, "ssd_step", rounded(S.ssd_step))
    elif fault == "conv_tail_from_padding":
        sound_conv = S.causal_conv
        set_attr(S, "causal_conv", lambda xbc, tail, w, b, length: sound_conv(
            xbc, tail, w, b, jnp.full_like(length, xbc.shape[1])))
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, seed = sys.argv[1:4]
    on_chip = sys.argv[4:] == ["chip"]
    import run

    plant(fault)
    sys.exit(run.main(["--workload", workload, "--seed", seed, "--trace", "0"]
                      + (["--seconds", "15"] if on_chip
                         else ["--seconds", "3", "--rehearse"])))
