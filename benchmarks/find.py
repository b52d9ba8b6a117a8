"""The harness's one way of finding code by a name that data gives it.

`load(group, name)` loads `benchmarks/<group>/<name>.py`, or the package
`benchmarks/<group>/<name>/__init__.py`, once a process. What is found so:

| group | named by | what the module defines |
|---|---|---|
| `families` | a configuration file's `family` | everything that depends on the architecture (`families/gpt2/__init__.py` lists it) |
| `drivers` | a mix's `kind` | `run`, `control`, `sweep`, and `TOY`, the mix's sizes under `--rehearse` |
| `generators/arrivals` | a mix's `arrivals.process` | `due(spec, seconds, rng)` |
| `generators/lengths` | a length's `dist` | `lengths(spec, n)` |
| `generators/schedules` | a mix's `schedule` (default `standard`) | `schedule(mix, seed, seconds, vocab)` |
| `metrics` | a metric's `name` in BENCHMARK.json | `read(ctx)` |

A name with no file is an error that says which file was expected and
which exist, so a later PR brings a new one as a new file and edits none.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

_loaded: dict = {}


def names(group: str) -> list:
    """The names `load(group, ...)` would find."""
    base = os.path.join(HERE, group)
    found = set()
    for entry in os.listdir(base) if os.path.isdir(base) else ():
        path = os.path.join(base, entry)
        if entry.endswith(".py") and entry != "__init__.py":
            found.add(entry[:-3])
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            found.add(entry)
    return sorted(found)


def load(group: str, name: str):
    """The module `benchmarks/<group>/<name>`, loaded once a process."""
    key = (group, str(name))
    if key in _loaded:
        return _loaded[key]
    base = os.path.join(HERE, group, str(name))
    if os.path.isfile(base + ".py"):
        path, package = base + ".py", None
    elif os.path.isfile(os.path.join(base, "__init__.py")):
        path, package = os.path.join(base, "__init__.py"), [base]
    else:
        raise FileNotFoundError(
            f"{name!r} is named as one of benchmarks/{group} but neither "
            f"benchmarks/{group}/{name}.py nor benchmarks/{group}/{name}/"
            f"__init__.py exists; benchmarks/{group} has {names(group)}")
    modname = "bench_" + re.sub(r"\W", "_", f"{group}_{name}")
    spec = importlib.util.spec_from_file_location(
        modname, path, submodule_search_locations=package)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod        # a package's relative imports need it
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    _loaded[key] = mod
    return mod


def family(cfg: dict):
    """The family of a configuration, by the file's own `family` key."""
    if "family" not in cfg:
        raise KeyError(f"configs/{cfg.get('name')}.json names no `family`; "
                       f"benchmarks/families has {names('families')}")
    return load("families", cfg["family"])
