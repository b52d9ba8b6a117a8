"""Tier-1's view of the benchmark's seam (PERF.md section 7 asked for it):
the cases of `benchmarks/tests/test_seam.py`, `test_drain.py` and the
prefill-then-decode case of `test_reference.py`, collected here where the
driver counts them, and each configuration's cell rehearsed on the CPU to
its "rehearsal only" line with `correct: true` — so tier-1 compares every
configuration with its family's plain reference.

One case of `test_seam.py` is taken over in a repaired form and not as it
stands: `test_only_the_family_names_gpt2s_keys_classes_and_leaves` looks for
GPT-2's keys as substrings, and `n_layer` / `n_head` are substrings of the
keys `num_hidden_layers` / `num_attention_heads` that a published
configuration of another family has to carry. Here the same words are
looked for as whole words."""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
for _p in (_BENCH, _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_tests_{name}", os.path.join(_BENCH, "tests", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_seam, _drain, _reference = (_load(n) for n in
                             ("test_seam", "test_drain", "test_reference"))
_SUBSTRINGS = "test_only_the_family_names_gpt2s_keys_classes_and_leaves"
globals().update({k: v for m in (_seam, _drain) for k, v in vars(m).items()
                  if k.startswith("test_") and k != _SUBSTRINGS})
test_prefill_then_decode_through_the_cache_matches_the_reference = \
    _reference.test_prefill_then_decode_through_the_cache_matches_the_reference


def test_only_the_family_names_gpt2s_keys_classes_and_leaves_whole_words():
    G = _seam.G
    words = ["n_embd", "n_layer", "n_head", "n_inner", "n_positions",
             "TransformerLM", "pos_embed.weight", "embed.weight",
             "ln_f.weight", "ln_f.bias", "head.weight", "head.bias"] \
        + list(G.weights.BLOCK_NAMES)
    found = []
    for d, _, files in os.walk(_BENCH):
        rel = os.path.relpath(d, _BENCH)
        if "__pycache__" in rel or rel.split(os.sep)[0] == "tests" \
                or rel.startswith(os.path.join("families", "gpt2")):
            continue
        for f in files:
            if not f.endswith((".py", ".json", ".md")):
                continue
            with open(os.path.join(d, f)) as fh:
                text = fh.read()
            if rel == "configs" and json.loads(text)["family"] == "gpt2":
                continue
            found += [(os.path.join(rel, f), w) for w in words
                      if re.search(rf"(?<![\w.]){re.escape(w)}(?![\w])", text)]
    assert found == []


def _cells():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # one cell a configuration and kind of run
    seen, out = set(), []
    for w in bench["workloads"]:
        kind = "train" if w["traffic"] == "train" else "serve"
        if (w["config"], kind) not in seen:
            seen.add((w["config"], kind))
            out.append(w["name"])
    return out


@pytest.mark.parametrize("cell", _cells())
def test_a_cells_rehearsal_compares_the_program_with_its_reference(cell):
    """`run.py --workload <cell> --rehearse`: the cell's own control flow
    at its family's toy size on the CPU, the timed path's output against
    the family's plain reference under the limits file's `_rehearse`
    group."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(_BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000030", "--seconds", "3", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert p.returncode == 3, p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal only, no result: ")
    line = json.loads(last.split(": ", 1)[1])
    assert line["correct"] is True, (line["compared"], p.stderr[-1500:])
    assert line["failed"] == 0 and line["attempted"] > 0
