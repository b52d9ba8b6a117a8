"""`correct` has to come out false when the timed path is broken, and true
when it is not: once for each fault a cell can have (the exchange between
chips exists in no one-chip cell). Each case is a process of its own: a
run sets process-wide state (fleet, the hybrid mesh)."""
import json
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
CASES = [
    ("none", "gpt2-medium.train", True),
    ("unchanged_state", "gpt2-medium.train", False),
    ("half_batch", "gpt2-medium.train", False),
    ("none", "gpt2-medium.chat", True),
    ("altered_token", "gpt2-medium.chat", False),
    ("altered_token", "gpt2-large.batch", False),
]


@pytest.mark.parametrize("fault,workload,want", CASES)
def test_correct_follows_the_fault(fault, workload, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(_HERE, "broken_run.py"), fault,
         workload, "31"], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 3, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal only, no result: ")
    line = json.loads(last.split(": ", 1)[1])
    assert line["correct"] is want, (line["compared"], p.stderr[-1500:])
