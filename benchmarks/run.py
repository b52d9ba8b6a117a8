"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It fails at once without a TPU
(no CPU fallback), makes the weights on the device from `--seed`, warms
the cell's own shapes, measures for `--seconds`, checks what the timed
path produced against the plain reference, and prints the contract's one
JSON line last on standard output.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file found by its name in BENCHMARK.json,
and so is the code behind them (`find.py`): the family of a configuration,
the driver of a mix's kind, the mix's generators; see README.md beside
this file.

Builder-only switches (the driver never passes them; none prints a result
line): `--rehearse` runs the same control flow on the CPU at a toy size
with the Pallas interpreter; `--control` reads the control's and the
planted faults' numbers for the cell's limits; `--sweep` offers a list
of arrival rates to a serving cell to find its knee.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="")
    return ap.parse_args(argv)


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"run.py: BENCHMARK.json has no workload {name!r}; "
                 f"it has {sorted(cells)}")
    return bench, cells[name]


def main(argv) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ.setdefault("PADDLE_FLASH_DEFAULT", "interpret")
        os.environ.setdefault("PADDLE_FUSED_LN", "interpret")
    bench, cell = load_cell(args.workload)
    import device as device_mod
    import find
    import traffic
    import weights

    cfg = weights.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    device = device_mod.require(int(cell["chips"]), args.rehearse)

    import checks
    import metrics as metric_readers

    family = find.family(cfg)
    driver = find.load("drivers", mix["kind"])
    if args.rehearse:
        cfg.update(family.TOY_CFG)
        mix.update(driver.TOY)
    if args.control:
        driver.control(cell, cfg, mix, args)
        return 3
    if args.sweep:
        driver.sweep(cell, cfg, mix, args, device)
        return 3
    run = driver.run(cell, cfg, mix, args, device, T_START)
    # run: {"ctx": what the readers read, "attempted", "failed",
    #       "compared": [...], "device": extra keys of `device`}
    ctx = run["ctx"]
    ctx.update(cell=cell, cfg=cfg, mix=mix, device=device, bench=bench,
               family=family)
    which = "per_layer" if args.trace else "end_to_end"
    values = metric_readers.read_all(bench[which], cell["name"], ctx)
    compared = run["compared"]
    correct = checks.verdict(compared) and run["failed"] == 0
    line = {
        "correct": bool(correct),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": values,
        "device": {**device, **run["device"]},
    }
    if args.trace and ctx.get("breakdown"):
        line["breakdown"] = ctx["breakdown"]
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in compared}
    checks.report(compared, correct, sys.stderr)
    if args.rehearse:
        print("rehearsal only, no result:", json.dumps(line), flush=True)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
