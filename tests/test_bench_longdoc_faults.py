"""The planted faults of the latent-attention, routed-expert cell at its
toy size on the CPU: each leaves one part of the mathematics out of the
timed path and has to come out as `correct: false` under the limits
file's `_rehearse` group (`token_gap_pow4`: the program's largest of 16 runs
3.3e-14; the mildest fault, the rotary left off `k_rope`, 7.9e-12; limit
3e-13; the sound run is `tests/test_bench_seam.py`'s rehearsal)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TESTS = os.path.join(_ROOT, "benchmarks", "tests")


@pytest.mark.parametrize("script,fault", [
    # `broken_longdoc.py`'s own stand-in for this fault takes five
    # arguments and `routed_experts` names the scoring rule: the fault is
    # planted by the file beside it until a benchmark issue repairs it
    ("broken_longctx.py", "no_select_bias"),
    ("broken_longdoc.py", "no_shared_expert"),
    ("broken_longdoc.py", "no_k_rope_rotation"),
    ("broken_run.py", "altered_token"),
])
def test_a_planted_fault_is_not_correct(script, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(_TESTS, script), fault,
         "sarvam-105b.longdoc", "7"],
        env=env, capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert p.returncode == 3, p.stderr[-3000:]
    assert "correct: false" in p.stderr.splitlines(), p.stderr[-1500:]
