"""The planted faults of the learned-sparse-attention, routed-expert cell
at its toy size on the CPU: each leaves one part of the mathematics out of
the timed path and has to come out as `correct: false` under the limits
file's `_rehearse` group (`token_gap_pow4`: the toy runs float32 and the
sound program reads 0 on every seed tried, each served token being the
reference's best; the mildest fault, the chosen experts' weights not
renormalised, 6.9e-9; the attention's five 4.5e-5 to 3.4e-4; limit 3e-13;
the sound run is `tests/test_bench_seam.py`'s rehearsal)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TESTS = os.path.join(_ROOT, "benchmarks", "tests")


@pytest.mark.parametrize("script,fault", [
    ("broken_longctx.py", "no_selection"),
    ("broken_longctx.py", "half_topk"),
    ("broken_longctx.py", "index_keys_unrotated"),
    ("broken_longctx.py", "no_head_weights"),
    ("broken_longctx.py", "kv_heads_misgrouped"),
    ("broken_longctx.py", "no_renorm"),
    ("broken_run.py", "altered_token"),
])
def test_a_planted_fault_is_not_correct(script, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(_TESTS, script), fault,
         "keye-vl-2.0-30b-a3b.longctx", "7"],
        env=env, capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert p.returncode == 3, p.stderr[-3000:]
    assert "correct: false" in p.stderr.splitlines(), p.stderr[-1500:]
