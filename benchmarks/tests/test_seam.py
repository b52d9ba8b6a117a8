"""The seam by which a second architecture comes as new files: the harness
finds a configuration's family, a mix's driver and its generators by name
(`find.py`) and reads no key, leaf or class of GPT-2 itself; and the move
that made GPT-2 the first family changed no weight, schedule or count."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import find
import metrics
import traffic as T
import weights as W

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROOT = os.path.dirname(_BENCH)
G = find.load("families", "gpt2")

# -- (a) a second family, a configuration, a mix with a new arrival process,
# a cell and its limits, all as new files in a copy ---------------------------

_NEW_FILES = {
    "families/llamaish/__init__.py": '''
"""A family that spells its sizes with other keys. It has the program's
one decoder too, so it hands every question on to `gpt2` under GPT-2's
keys; the harness never sees those."""
import find

_G = find.load("families", "gpt2")
_KEYS = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
         "num_attention_heads": "n_head", "intermediate_size": "n_inner",
         "max_position_embeddings": "n_positions",
         "norm_eps": "layer_norm_epsilon", "init_std": "initializer_range"}
TOY_CFG = {"vocab_size": 211, "max_position_embeddings": 128,
           "hidden_size": 64, "num_hidden_layers": 2,
           "num_attention_heads": 4, "intermediate_size": 128}


def _own(cfg):
    return {_KEYS.get(k, k): v for k, v in cfg.items()}


def sizes(cfg):
    return _G.sizes(_own(cfg))


def serving_model(cfg, mix, seed):
    return _G.serving_model(_own(cfg), mix, seed)


def make(cfg, seed, form="by_name"):
    return _G.make(_own(cfg), seed, form)


def served_gaps(params, prompt, tokens, *, cfg, pad_to, control=None):
    return _G.served_gaps(params, prompt, tokens, cfg=_own(cfg),
                          pad_to=pad_to, control=control)


def __getattr__(name):          # the counts take `sizes`, not the file
    return getattr(_G, name)
''',
    "configs/llamaish-tiny.json": json.dumps({
        "source": "none: a test's stand-in", "family": "llamaish",
        "vocab_size": 32000, "max_position_embeddings": 2048,
        "hidden_size": 2048, "num_hidden_layers": 16,
        "num_attention_heads": 16, "intermediate_size": 8192,
        "norm_eps": 1e-5, "init_std": 0.02, "reduced": []}),
    "generators/arrivals/bursts.py": '''
"""`burst` requests at a time, the bursts evenly spaced at `rate_per_s`
requests a second overall."""
import numpy as np


def due(spec, seconds, rng):
    n = max(int(round(spec["rate_per_s"] * seconds)), 1)
    k = int(spec["burst"])
    return (np.arange(n) // k) * (k / spec["rate_per_s"])
''',
    "traffic/bursts.json": json.dumps({
        "kind": "serve",
        "arrivals": {"process": "bursts", "rate_per_s": 4.0, "burst": 2},
        "order": "fixed", "order_seed": 0,
        "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.7,
                       "min": 16, "max": 768},
        "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                       "min": 16, "max": 256},
        "max_total": 1024, "engine": {"slots": 8, "max_length": 1024},
        "weights": "float32", "control": "bf16", "check_requests": 8,
        "why": "a test's stand-in"}),
    "limits/llamaish-tiny.bursts.json": json.dumps({
        "token_gap_pow4": 1e-10, "wrong_answers": 0, "compiles_in_window": 0,
        "_rehearse": {"token_gap_pow4": 1e-18, "wrong_answers": 0,
                      "compiles_in_window": 0}}),
}
_CELL = "llamaish-tiny.bursts"


def _tree(top):
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_second_family_comes_as_new_files_only(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(_BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = _tree(bench)
    for rel, text in _NEW_FILES.items():
        path = bench / rel
        assert not path.exists(), rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    # entries, as a later PR adds them: a configuration, a cell, and the
    # cell's name under the metrics it reports
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "llamaish-tiny", "source": "none", "reduced": [],
        "file": "benchmarks/configs/llamaish-tiny.json", "why": "test"})
    spec["workloads"].append({
        "name": _CELL, "config": "llamaish-tiny", "traffic": "bursts",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "gpt2-medium.chat" in m.get("workloads", ()):
            m["workloads"].append(_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", _CELL,
         "--seed", "3000000019", "--seconds", "3", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600, cwd=tmp_path)
    assert p.returncode == 3, p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal only, no result: ")
    line = json.loads(last.split(": ", 1)[1])
    assert line["correct"] is True, (line["compared"], p.stderr[-1500:])
    # 4 a second in bursts of 2 for 3 s, each answered in full
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    # no file that was there changed
    after = _tree(bench)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == sorted(_NEW_FILES)


# -- (c) the move changed no weight, schedule or count: checksums and counts
# taken on the parent (commit aa5c3c0, this machine's CPU backend) ------------


def _sha(arrays):
    h = hashlib.sha256()
    for name in sorted(arrays):
        h.update(name.encode())
        h.update(np.asarray(arrays[name]).tobytes())
    return h.hexdigest()


def test_toy_weights_are_the_parents():
    cfg = dict(W.load_config("gpt2-medium"), **G.TOY_CFG)
    assert _sha(G.make(cfg, 31)) == \
        "9a253a2bdaa9f4661d3bd00d700feeb3ced0a44d6f17afdddcde3de12ce49716"
    assert _sha(G.make(cfg, 31, form="stacked")) == \
        "215154c06b38d9f156a4c7e0ed6ff6f3c83f17455301df2cb6008b85b4fc9a6c"


@pytest.mark.parametrize("mix,n,want", [
    ("chat", 72,
     "e9cf0ea45a713fb1febf8d2caaa15682aba70b31109b72fa6cb5f61bfb493b43"),
    ("batch", 400,
     "c4914e856178b124a3e2e5571f4c681186486657b4e7ce90489a22dac60e83de"),
])
def test_schedules_are_the_parents(mix, n, want):
    sched = T.schedule(T.load_mix(mix), 3_000_000_019, 30, 50257)
    h = hashlib.sha256()
    for r in sched:
        h.update(np.float64(r["due"]).tobytes())
        h.update(np.int64(r["max_new"]).tobytes())
        h.update(r["prompt"].tobytes())
    assert (len(sched), h.hexdigest()) == (n, want)


def test_train_batches_are_the_parents():
    mix = dict(T.load_mix("train"), batch=4, seq=32)
    got = np.asarray(T.train_batches(mix, 3_000_000_019, 3, 503))
    assert hashlib.sha256(got.tobytes()).hexdigest() == \
        "242d72bc45d487266c878690728c3efb563a54f6c274700d5e37790817a1b0f9"


@pytest.mark.parametrize("config,want", [
    ("gpt2-medium", {
        "matmul_params": 353453056, "total_params": 406336593,
        "train_flops_per_token": 2271860736.0, "serve_flops": 3536005120,
        "kv_bytes_per_token": 196608, "decode_step_bytes": 1611907396}),
    ("gpt2-large", {
        "matmul_params": 772117760, "total_params": 838409297,
        "train_flops_per_token": 4916098560.0, "serve_flops": 7723942400,
        "kv_bytes_per_token": 368640, "decode_step_bytes": 3459718468}),
])
def test_counts_are_the_parents(config, want):
    cfg = W.load_config(config)
    fam = find.family(cfg)
    s = fam.sizes(cfg)
    assert {
        "matmul_params": fam.matmul_params(s),
        "total_params": fam.total_params(s),
        "train_flops_per_token": fam.train_flops_per_token(s, 1024),
        "serve_flops": fam.serve_flops(
            s, prefill_pairs=6, prefill_tokens=3, decode_pairs=9,
            decode_tokens=2),
        "kv_bytes_per_token": fam.kv_bytes_per_token(s),
        "decode_step_bytes": fam.decode_step_bytes(s, 1000)} == want


# -- (d) a name with no file says which file was expected ---------------------


@pytest.mark.parametrize("find_it,expected,has", [
    (lambda: find.load("drivers", "evaluate"),
     "benchmarks/drivers/evaluate.py", "'serve', 'train'"),
    (lambda: find.family({"name": "x", "family": "mamba"}),
     "benchmarks/families/mamba/__init__.py", "'gpt2'"),
    (lambda: metrics.reader("goodput_pct"),
     "benchmarks/metrics/goodput_pct.py", "'ttft_p95_ms'"),
    (lambda: T.arrivals({"process": "bursty"}, 30, None),
     "benchmarks/generators/arrivals/bursty.py", "'all_at_zero', 'poisson'"),
])
def test_a_name_with_no_file_names_the_file(find_it, expected, has):
    with pytest.raises(FileNotFoundError) as e:
        find_it()
    assert expected in str(e.value) and has in str(e.value)


def test_a_configuration_without_a_family_is_refused():
    with pytest.raises(KeyError, match="names no `family`"):
        find.family({"name": "x", "n_embd": 8})


# -- the harness names nothing of GPT-2 ----------------------------------------


def test_only_the_family_names_gpt2s_keys_classes_and_leaves():
    leaves = [f"{n}" for n in G.weights.BLOCK_NAMES] + [
        "pos_embed.weight", "embed.weight", "ln_f.weight", "ln_f.bias",
        "head.weight", "head.bias"]
    words = ["n_embd", "n_layer", "n_head", "n_inner", "n_positions",
             "TransformerLM"] + leaves
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
                continue            # GPT-2's own configuration files
            found += [(os.path.join(rel, f), w) for w in words if w in text]
    assert found == []
    with open(os.path.join(_BENCH, "run.py")) as f:
        run_src = f.read()
    # no branch on a mix's kind: the driver is found by it
    assert not re.search(r"\[.kind.\]\s*[!=]=|import (train|serve)\b",
                         run_src)
    assert 'find.load("drivers", mix["kind"])' in run_src
    with open(os.path.join(_BENCH, "drivers", "serve.py")) as f:
        serve_src = f.read()
    # no branch on a precision: the family says what it has proven
    assert "float32" not in serve_src
    assert not re.search(r"\[.weights.\]\s*[!=]=", serve_src)
