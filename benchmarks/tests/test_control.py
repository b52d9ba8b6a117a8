"""The control, at a size a test run can hold: `run.py --control` puts the
reference, computed in the nearest lower precision (and, for training,
with half the batch left out), in the program's place and judges it by
`checks.compared` and `checks.verdict` under the cell's own limits file
(the `_rehearse` group, set from toy readings as the cell's are from the
chip's). It has to come out as not correct, and the program as correct.
(The readings at the cells' own sizes, on the chip, are in PERF.md.)"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import checks
import find
import traffic

G = find.load("families", "gpt2")
R, W = G.reference, G.weights

CFG = {"vocab_size": 503, "n_positions": 128, "n_embd": 128, "n_layer": 4,
       "n_head": 4, "n_inner": 512, "layer_norm_epsilon": 1e-5,
       "initializer_range": 0.02}
OPT = {"learning_rate": 1e-4, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "weight_decay": 0.01}


_RUN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "run.py")


@pytest.mark.parametrize("workload,seed,verdicts", [
    ("gpt2-medium.train", 31, {"fp8": False, "half_batch": False}),
    ("gpt2-medium.chat", 34, {"program": True, "bf16": False}),
    ("gpt2-large.batch", 31, {"program": True, "bf16": False}),
])
def test_control_is_not_correct_under_the_cells_limits(workload, seed,
                                                       verdicts):
    p = subprocess.run(
        [sys.executable, _RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", "6", "--rehearse", "--control"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] == verdicts, line
    # the verdict is the harness's own, printed as a run prints it
    assert p.stderr.count("correct: false") == \
        sum(not v for v in verdicts.values())


def _three(**kw):
    pool = np.asarray(traffic.train_batches({"batch": 8, "seq": 128}, 3, 3,
                                            503))
    ids = [(pool[i, :, :-1], pool[i, :, 1:]) for i in range(3)]
    l, g, d = R.train_steps(CFG, 3, ids, OPT, **kw)
    return {"losses": l, "grad": g, "delta": d}


def test_the_reference_agrees_with_itself_and_the_dead_leaf_rule():
    ref = _three()
    same = checks.train_numbers(_three(), ref)["values"]
    assert max(same.values()) == 0.0
    # the key's bias has no gradient under softmax: the rule finds it
    assert all(n.endswith("attn.qkv.bias.k")
               for n in checks.dead_leaves(ref["grad"]))
    assert len(checks.dead_leaves(ref["grad"])) == CFG["n_layer"]


def test_served_gaps_are_nought_for_the_references_own_tokens():
    params = W.make(CFG, 5, form="stacked")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 503, size=40)
    s = W.sizes(CFG)
    # greedy tokens of the reference itself: gap 0 at every position
    seq = list(prompt)
    for _ in range(24):
        ids = np.zeros((1, 128), np.int32)
        ids[0, :len(seq)] = seq
        lg = np.asarray(R.logits(params, ids, heads=s["heads"],
                                 eps=s["eps"]))[0, len(seq) - 1]
        seq.append(int(lg.argmax()))
    toks = seq[40:]
    gap, _, spread = R.served_gaps(params, prompt, toks, cfg=CFG,
                                   pad_to=128)
    assert gap.max() == 0.0 and (spread > 0).all()
    # an altered token lies far below the best
    toks[3] = (toks[3] + 251) % 503
    gap2, _, _ = R.served_gaps(params, prompt, toks, cfg=CFG, pad_to=128)
    assert gap2[3] > 0.0
