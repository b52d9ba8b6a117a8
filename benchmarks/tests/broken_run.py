"""Drive one run of a cell with the timed path broken underneath:
`python broken_run.py <fault> <workload> <seed> [chip]`. It skips the
harness's look for a chip (`--rehearse`: the CPU, a toy size, the limits
file's `_rehearse` group) and runs everything else of a run; the line it
prints last carries `correct`. `none` plants nothing. With `chip` as the
last word it is a real run of 15 s at the cell's own size under the
cell's own limits, for the builder to read a fault on the chip."""
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault == "unchanged_state":
        # a step that returns its state unchanged
        from paddle_tpu.optimizer.optimizer import AdamW

        AdamW._pure_one = lambda self, p, p_raw, g_raw, accs, lr, t: (
            p_raw, accs)
    elif fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        from paddle_tpu.jit import TrainStep

        whole = TrainStep.__call__

        def half(self, inputs, labels=None):
            n = inputs.shape[0] // 2
            return whole(self, inputs[:n], labels[:n])

        TrainStep.__call__ = half
    elif fault == "altered_token":
        # a token altered where it is produced, chosen by the low bits of
        # its row's best logit (greedy tokens repeat, so not by their id):
        # a third of the toy's tokens, one in 251 of the cells'
        import jax
        import jax.numpy as jnp

        from paddle_tpu.serving import sampling

        sound = sampling.sample

        def altered(logits, *a, **kw):
            tok = sound(logits, *a, **kw)
            v = logits.shape[-1]
            bits = jax.lax.bitcast_convert_type(
                logits.max(-1).astype(jnp.float32), jnp.int32)
            return jnp.where(bits % max(v // 200, 3) == 0,
                             ((tok + v // 2) % v).astype(tok.dtype), tok)

        sampling.sample = altered
    elif fault == "bf16_kv":
        # milder than the control: only the cached keys and values are
        # kept in bfloat16, everything else as the configuration states
        from paddle_tpu.serving import TransformerLM

        sound_cache = TransformerLM.gen_cache

        def narrow(self, batch_size, max_length, dtype=None, **kw):
            return sound_cache(self, batch_size, max_length, "bfloat16",
                               **kw)

        TransformerLM.gen_cache = narrow
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
