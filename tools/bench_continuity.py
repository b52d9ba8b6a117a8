#!/usr/bin/env python
"""Bench continuity gate: compare the two latest `BENCH_r*.json` records
and FAIL on any >10% per-metric median regression that the newer round
did not annotate (VERDICT r5 weak #2 — the "explain every regression"
methodology, made enforceable).

Rules
-----
* Metrics: the headline `metric`/`value` pair plus every numeric
  `extra` key. `*_compile_s` (warm-cache compile times), `vs_*` ratios
  and `*_spread` records are excluded. Direction is inferred from the
  name: `*per_sec*` is higher-is-better, `*_ms`/`*_s` lower-is-better;
  anything else is skipped.
* A regression is WAIVED when
    - the newer round's `extra.incomparable_to_prev` is non-empty (a
      declared methodology break applies to the whole record), or
    - the metric's name appears in the newer round's `extra.note` /
      `extra.incomparable_to_prev` text (per-metric annotation).
* Rounds up to r05 were single-shot; enforcement only makes sense on the
  median-of-N methodology, detected by the presence of `*_spread` keys.
  A newer file without spreads downgrades failures to warnings.

Round 14 (ROADMAP item-2 carry-over): the per-phase MULTICHIP
`compile_s` drift table is GATED at >25% (see
:func:`multichip_compile_check`) with the same note/waiver mechanism.

Usage: `python tools/bench_continuity.py [repo_root]` — exit 1 on an
unwaived regression. `tests/test_hygiene.py::TestBenchContinuity` runs
this over the repo's records in CI and unit-tests the gate on synthetic
pairs.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

THRESHOLD = 0.10
#: max % the numerical-guard sentinel may cost the GPT step
#: (bench.py records `guard_overhead_pct` from the on/off pair)
GUARD_OVERHEAD_PCT = 2.0
#: compile-time drift gate between the two latest MULTICHIP dryruns
#: (ISSUE 14 satellite / ROADMAP item-2 carry-over): GSPMD partition
#: cliffs surface as per-phase compile blowups long before a chip run.
#: Looser than the 10% perf gate — compile time on a shared host is
#: noisy — but a >25% unannotated jump now FAILS instead of reporting.
COMPILE_THRESHOLD = 0.25


def _parsed(path: str) -> dict:
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)  # harness wrapper or the bare bench line


def load_latest_pair(root: str):
    """The two most recent BENCH_r*.json by round number, or None."""
    paths = glob.glob(os.path.join(root, "BENCH_r*.json"))
    rounds = []
    for p in paths:
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m:
            rounds.append((int(m.group(1)), p))
    rounds.sort()
    if len(rounds) < 2:
        return None
    (_, prev_p), (_, cur_p) = rounds[-2], rounds[-1]
    return (prev_p, _parsed(prev_p)), (cur_p, _parsed(cur_p))


def metric_direction(name: str):
    """+1 higher-is-better, -1 lower-is-better, None = not comparable."""
    if name.startswith("vs_") or name.endswith("_spread"):
        return None
    if name.endswith("_compile_s"):
        return None  # warm-cache artifact, not a perf metric
    if name.endswith("_mfu_pct") or name == "compile_count":
        return None  # observability trend lines (mfu_report), never gated
    if "per_sec" in name:
        return 1
    if name == "serve_failover_recovery_ms_migrate":
        return -1  # round-17 migrate twin of the gated _ms key
    if name == "ctl_live_reclaim_ms":
        # round-20 live lend: the reclaim ladder's wall time scales
        # with whatever queue depth drain happens to find — a load
        # artifact, not a regression signal. The lend-side twin
        # (ctl_live_lend_ms) IS gated by the _ms rule below.
        return None
    if name.endswith("_ms") or name.endswith("_s"):
        return -1
    # round-19 quantization byte accounting: static shape arithmetic,
    # not a timed sample — zero noise, so a >10% move is a structural
    # change (a layer silently falling off the narrow path) and IS
    # gated. The round-11 comm_mb key predates this and stays
    # report-only as documented.
    if name in ("q_ckpt_payload_mb", "gpt_medium_bf16_q8m_moment_mb"):
        return -1
    if name in ("q_ckpt_reduction_x",
                "gpt_medium_bf16_q8m_moment_reduction_x"):
        return 1
    return None


def metrics_of(parsed: dict) -> dict:
    out = {}
    if isinstance(parsed.get("value"), (int, float)) and parsed.get("metric"):
        out[parsed["metric"]] = float(parsed["value"])
    for k, v in (parsed.get("extra") or {}).items():
        if isinstance(v, (int, float)) and metric_direction(k) is not None:
            out[k] = float(v)
    # a *_step_ms key is the same measurement as its sibling *per_sec
    # throughput, un-normalized — it double-counts the comparison and
    # flips spuriously when the batch size changes; keep the throughput
    for k in [k for k in out if k.endswith("_step_ms")]:
        prefix = k[: -len("step_ms")]
        if any(o.startswith(prefix) and "per_sec" in o for o in out):
            del out[k]
    return out


def compare(prev: dict, cur: dict):
    """-> (regressions, waived, improvements): lists of
    (name, prev, cur, change_fraction[, reason])."""
    note = str((cur.get("extra") or {}).get("note", ""))
    incomparable = str(
        (cur.get("extra") or {}).get("incomparable_to_prev", "")
    )
    ann_text = note + " " + incomparable
    pm, cm = metrics_of(prev), metrics_of(cur)
    regressions, waived, improvements = [], [], []
    for name in sorted(set(pm) & set(cm)):
        sign = metric_direction(name)
        if sign is None or pm[name] == 0:
            continue
        change = sign * (cm[name] - pm[name]) / abs(pm[name])
        if change >= 0:
            improvements.append((name, pm[name], cm[name], change))
            continue
        if -change <= THRESHOLD:
            continue
        if incomparable.strip():
            waived.append((name, pm[name], cm[name], change,
                           "incomparable_to_prev declared"))
        elif re.search(  # whole-name match: annotating x_per_sec_dense
            #  must not waive its prefix sibling x_per_sec
            r"(?<![A-Za-z0-9_])" + re.escape(name) + r"(?![A-Za-z0-9_])",
            ann_text,
        ):
            waived.append((name, pm[name], cm[name], change,
                           "annotated in note"))
        else:
            regressions.append((name, pm[name], cm[name], change))
    return regressions, waived, improvements


def _multichip_doc(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _compile_times_of(doc: dict) -> dict:
    out = {}
    for m in re.finditer(
        r"dryrun_multichip\(\d+\): (.+?) loss=\S+ compile_s=([0-9.]+)",
        doc.get("tail", ""),
    ):
        out[m.group(1).strip()] = float(m.group(2))
    return out


def multichip_compile_times(path: str) -> dict:
    """Per-phase `compile_s=` values from a MULTICHIP_r*.json dryrun
    tail, keyed by the phase label (the text between the prefix and the
    loss). Older rounds without compile stamps return {}."""
    return _compile_times_of(_multichip_doc(path))


def _phase_annotated(name: str, note: str, all_names) -> bool:
    """Does ``note`` name this phase? Phase labels are multi-word
    ('dp GPT'), so the perf gate's token-boundary regex is not enough:
    an occurrence only counts when it is not merely part of a LONGER
    sibling label's occurrence — annotating 'dp GPT flash' must not
    waive 'dp GPT'."""
    longer = [o for o in all_names
              if o != name and name in o]
    pat = (r"(?<![A-Za-z0-9_])" + re.escape(name)
           + r"(?![A-Za-z0-9_])")
    covers = []
    for o in longer:
        covers.extend((mo.start(), mo.end())
                      for mo in re.finditer(re.escape(o), note))
    for m in re.finditer(pat, note):
        if not any(s <= m.start() and m.end() <= e for s, e in covers):
            return True
    return False


def multichip_compile_check(root: str):
    """GATED compile-time drift between the two latest
    MULTICHIP_r*.json dryruns (ISSUE 6 introduced the report-only
    table; ISSUE 14 / ROADMAP item-2 promotes it): GSPMD partition
    cliffs on the pod-scale CPU mesh show up as compile-time blowups
    long before a chip run. A phase whose `compile_s` grew more than
    COMPILE_THRESHOLD fails — unless the newer record waives it via
    the SAME mechanism the perf gate uses: a top-level
    ``incomparable_to_prev`` declaration (whole record) or the phase
    label (or the literal token ``compile_s``) appearing in a
    top-level ``note``. New phases and shrinks stay report-only.
    Returns ``(rc, lines)``."""
    paths = glob.glob(os.path.join(root, "MULTICHIP_r*.json"))
    rounds = []
    for p in paths:
        m = re.search(r"MULTICHIP_r(\d+)\.json$", p)
        if m:
            rounds.append((int(m.group(1)), p))
    rounds.sort()
    if len(rounds) < 2:
        return 0, []
    (_, prev_p), (_, cur_p) = rounds[-2], rounds[-1]
    cur_doc = _multichip_doc(cur_p)
    prev, cur = multichip_compile_times(prev_p), _compile_times_of(
        cur_doc)
    note = str(cur_doc.get("note", ""))
    incomparable = str(cur_doc.get("incomparable_to_prev", ""))
    lines = []
    rc = 0
    for name in sorted(set(prev) | set(cur)):
        a, b = prev.get(name), cur.get(name)
        if a is not None and b is not None and a > 0:
            change = (b - a) / a
            if change <= COMPILE_THRESHOLD:
                lines.append(
                    f"  ok      compile_s[{name}]: {a:g} -> {b:g} "
                    f"({change:+.1%}, gate {COMPILE_THRESHOLD:.0%})"
                )
            elif incomparable.strip():
                lines.append(
                    f"  waived  compile_s[{name}]: {a:g} -> {b:g} "
                    f"({change:+.1%}) [incomparable_to_prev declared]"
                )
            elif _phase_annotated(name, note, set(prev) | set(cur)) \
                    or re.search(
                        r"(?<![A-Za-z0-9_])compile_s(?![A-Za-z0-9_])",
                        note):
                lines.append(
                    f"  waived  compile_s[{name}]: {a:g} -> {b:g} "
                    f"({change:+.1%}) [annotated in note]"
                )
            else:
                lines.append(
                    f"  REGRESS compile_s[{name}]: {a:g} -> {b:g} "
                    f"({change:+.1%} > {COMPILE_THRESHOLD:.0%} compile "
                    f"budget)"
                )
                rc = 1
        elif b is not None:
            lines.append(f"  report  compile_s[{name}]: {b:g} (new)")
    if lines:
        lines.insert(0, (
            f"multichip compile-time gate ({COMPILE_THRESHOLD:.0%}): "
            f"{os.path.basename(prev_p)} -> {os.path.basename(cur_p)}"
        ))
    return rc, lines




def mfu_report(prev: dict, cur: dict):
    """REPORT-ONLY drift of the ISSUE-8 observability keys between two
    bench rounds: per-model ``*_mfu_pct`` (achieved-FLOPs utilization —
    moves with every legitimate model change, so a trend line, not a
    gate) and ``compile_count`` (recompile-ledger total: a jump means a
    new recompile source landed in the benched path)."""
    pe, ce = (prev.get("extra") or {}), (cur.get("extra") or {})
    keys = sorted(
        k for k in set(pe) | set(ce)
        if k.endswith("_mfu_pct") or k == "compile_count"
    )
    lines = []
    for k in keys:
        a, b = pe.get(k), ce.get(k)
        if not isinstance(b, (int, float)):
            continue
        if isinstance(a, (int, float)):
            lines.append(f"  report  {k}: {a:g} -> {b:g} (not gated)")
        else:
            lines.append(f"  report  {k}: {b:g} (new)")
    return lines


def check(root: str):
    """-> (exit_code, report_lines)."""
    pair = load_latest_pair(root)
    lines = []
    if pair is None:
        crc, clines = multichip_compile_check(root)
        out = (["bench_continuity: fewer than two BENCH_r*.json — skip"]
               + clines)
        if crc:
            out.append(
                "FAIL: unannotated >25% compile_s regression; either "
                "fix it or name the phase (or 'compile_s') in the "
                "MULTICHIP record's note / declare incomparable_to_prev"
            )
        return crc, out
    (prev_p, prev), (cur_p, cur) = pair
    lines.append(
        f"bench_continuity: {os.path.basename(prev_p)} -> "
        f"{os.path.basename(cur_p)} (threshold {THRESHOLD:.0%})"
    )
    regressions, waived, improvements = compare(prev, cur)
    enforce = any(
        k.endswith("_spread") for k in (cur.get("extra") or {})
    )
    for name, a, b, c in improvements:
        lines.append(f"  ok      {name}: {a:g} -> {b:g} ({c:+.1%})")
    for name, a, b, c, why in waived:
        lines.append(f"  waived  {name}: {a:g} -> {b:g} ({c:+.1%}) [{why}]")
    for name, a, b, c in regressions:
        tag = "REGRESS" if enforce else "warn   "
        lines.append(f"  {tag} {name}: {a:g} -> {b:g} ({c:+.1%})")
    if regressions and not enforce:
        lines.append(
            "  (single-shot round — no *_spread keys — regressions "
            "reported, not enforced)"
        )
    rc = 1 if (regressions and enforce) else 0
    # absolute gate: the in-graph numerical sentinel's cost on the GPT
    # step (guard on vs off, recorded by bench.py) must stay under
    # GUARD_OVERHEAD_PCT — waivable by naming guard_overhead_pct in the
    # round's note, like any other regression
    gp = (cur.get("extra") or {}).get("guard_overhead_pct")
    if isinstance(gp, (int, float)):
        note_txt = str((cur.get("extra") or {}).get("note", "")) + " " + \
            str((cur.get("extra") or {}).get("incomparable_to_prev", ""))
        if gp <= GUARD_OVERHEAD_PCT:
            lines.append(f"  ok      guard_overhead_pct: {gp:g}% "
                         f"(gate {GUARD_OVERHEAD_PCT:g}%)")
        elif "guard_overhead_pct" in note_txt:
            lines.append(f"  waived  guard_overhead_pct: {gp:g}% "
                         f"[annotated in note]")
        elif enforce:
            lines.append(f"  REGRESS guard_overhead_pct: {gp:g}% > "
                         f"{GUARD_OVERHEAD_PCT:g}% sentinel budget")
            rc = 1
        else:
            lines.append(f"  warn    guard_overhead_pct: {gp:g}% > "
                         f"{GUARD_OVERHEAD_PCT:g}% (single-shot round)")
    lines.extend(mfu_report(prev, cur))
    crc, clines = multichip_compile_check(root)
    lines.extend(clines)
    rc = rc or crc
    if rc:
        lines.append(
            "FAIL: unannotated >10% regression(s), guard-overhead "
            "budget breach, or >25% compile_s drift; either fix it or "
            "explain it in extra.note / the MULTICHIP note / declare "
            "incomparable_to_prev"
        )
    return rc, lines


def main(argv):
    root = argv[1] if len(argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    rc, lines = check(root)
    print("\n".join(lines))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
