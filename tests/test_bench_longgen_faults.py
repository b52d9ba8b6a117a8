"""The planted faults of the state-space hybrid cell at its toy size on the
CPU: each leaves one part of the mathematics out of the timed path and has
to come out as `correct: false` under the limits file's `_rehearse` group
(`token_gap_pow4`: the toy runs float32 and the sound program reads 0 on
every seed tried, each served token being the reference's best; the
faults read, on seed 7, 2.7e-4 with the skip left out, 1.5e-5 with the
tail taken from padding, 1.1e-9 with padding advancing the state and
4.6e-24 with the state rounded to bfloat16, which at this size flips one
near-tied token of a hundred on seeds 7 and 1 and none on seeds 2-6;
limit 1e-26; the sound run is `tests/test_bench_seam.py`'s rehearsal).
That fault is held on every seed by the logits themselves:
`tests/test_hybrid_ssm_lm.py::test_a_bfloat16_state_leaves_the_float32_band`."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TESTS = os.path.join(_ROOT, "benchmarks", "tests")


@pytest.mark.parametrize("fault", ["padding_advances_state", "no_d_skip",
                                   "bf16_state", "conv_tail_from_padding"])
def test_a_planted_fault_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(_TESTS, "broken_longgen.py"), fault,
         "granite-4.0-h-micro.longgen", "7"],
        env=env, capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert p.returncode == 3, p.stderr[-3000:]
    assert "correct: false" in p.stderr.splitlines(), p.stderr[-1500:]
