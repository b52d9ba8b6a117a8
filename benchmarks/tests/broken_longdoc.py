"""Drive one run of a latent-attention, routed-expert cell with one part
of its mathematics left out of the timed path:
`python broken_longdoc.py <fault> <workload> <seed> [chip]`, as
`broken_run.py` beside this file does for the faults it knows. The run has
to print `correct: false`.

| fault | what the program leaves out |
|---|---|
| `no_select_bias` | the router chooses by score alone |
| `no_shared_expert` | an expert layer returns its routed part only |
| `no_k_rope_rotation` | the shared rotary key is cached unrotated (the queries still turn) |
"""
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]


def plant(fault: str) -> None:
    from paddle_tpu.nn.functional import latent as L
    from paddle_tpu.nn.layers import latent as layers

    if fault == "no_select_bias":
        sound_route = L.route_top_k
        L.route_top_k = lambda x, gate_w, bias, top_k, scaling: sound_route(
            x, gate_w, None, top_k, scaling)
    elif fault == "no_shared_expert":
        sound_forward = layers.RoutedExperts.forward

        def routed_only(self, x, count=False):
            shared, self.shared = self.shared, None
            try:
                return sound_forward(self, x, count)
            finally:
                self.shared = shared

        layers.RoutedExperts.forward = routed_only
    elif fault == "no_k_rope_rotation":
        # the shared key is [B, T, rope], a head's query part [B, T, H, rope]
        sound_rope = L._rope
        L._rope = lambda x, pos, inv_freq, scale: x if x.ndim == 3 \
            else sound_rope(x, pos, inv_freq, scale)
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
