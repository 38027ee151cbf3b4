"""Benchmark regression gate (``make bench-gate``).

Runs ``benchmarks.run --json`` fresh (or takes ``--report PATH``) and
diffs it against the committed baseline (``BENCH_fcnn.json``).  Exits 1
when:

  * a reproduction check that PASSed in the baseline now FAILs or has
    disappeared from the report (deleting a check is a regression too), or
  * a gated timing ratio degrades by more than ``--slowdown`` (default
    20%) — the microbench speedups (fused vs reference implementation)
    and the executor's program-execution wall-time ratio
    (``exec_residency_bench``'s replicated-over-sharded step time, see
    ``_ratio_fields``).

Raw wall-clock fields are never compared — only timing *ratios*, which
are stable across machines since both sides of the ratio run on the same
box.  Even ratios flake on loaded CPU runners, so when the gate runs the
benchmarks itself it re-runs each ratio-gated benchmark ``--repeats``
times (default 3) and gates on the **median** ratio per case — a single
noisy run can no longer fail (or pass) the gate.  After an intentional change (new
checks, a real kernel win), refresh the baseline with ``make bench-json``
and commit the new snapshot.

Intentional baseline refreshes go through ``--refresh`` (``make
bench-refresh``): instead of hand-editing or wholesale overwriting
``BENCH_fcnn.json``, the gate runs the sweep (ratio fields snapshotted at
the per-case **minimum** across repeats — a conservative floor, so a
lucky fast run cannot tighten the gate), writes it as the new baseline,
and appends a summary of the *old* baseline to a ``"history"`` list
inside the file — the refresh trail rides along in the committed JSON.
``compare`` never reads ``"history"``.

  PYTHONPATH=src python -m benchmarks.gate [--baseline BENCH_fcnn.json]
      [--report PATH] [--slowdown 0.20] [--repeats 3] [--refresh]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import tempfile
import time

def _ratio_fields(name: str) -> tuple[str, ...]:
    """Gated ratio fields per benchmark.  Only ratios are compared across
    reports (both sides of a ratio run on the same box); benchmarks not
    listed here contribute checks but no timing gate."""
    if name.endswith("microbench"):
        return ("fwd_speedup", "fwdbwd_speedup")
    if name == "exec_residency_bench":
        return ("replicated_over_sharded_step",)
    if name == "serving_bench":
        return ("tok_s_ratio", "p99_ttft_ratio")
    return ()


def _check_key(line: str) -> str:
    """Stable identity of a check line: everything before the measured
    numbers ("check,table7,plateau-APE<=2.3% (paper claim)")."""
    head = line.split(" -> ")[0]
    return head.split(":")[0] if ":" in head else head


def _verdict(line: str) -> str | None:
    return line.rsplit("-> ", 1)[1].strip() if "-> " in line else None


def compare(base: dict, cur: dict, slowdown: float) -> list[str]:
    failures: list[str] = []

    cur_checks = {}
    for line in cur.get("checks", []):
        if _verdict(line) in ("PASS", "FAIL"):
            cur_checks[_check_key(line)] = line
    for line in base.get("checks", []):
        if _verdict(line) != "PASS":
            continue  # informational or already-failing: not gated
        key = _check_key(line)
        now = cur_checks.get(key)
        if now is None:
            failures.append(f"check disappeared (was PASS): {key}")
        elif _verdict(now) == "FAIL":
            failures.append(f"paper-claim regression: {now}")

    for name, bench in base.get("benchmarks", {}).items():
        fields = _ratio_fields(name)
        if not fields:
            continue
        cur_bench = cur.get("benchmarks", {}).get(name)
        if cur_bench is None:
            failures.append(f"gated benchmark disappeared: {name}")
            continue
        cur_rows = {r.get("case"): r for r in cur_bench["rows"]}
        for row in bench["rows"]:
            case = row.get("case")
            now = cur_rows.get(case)
            if now is None:
                failures.append(f"{name}: case {case!r} disappeared")
                continue
            for f in fields:
                if f in row and f in now and now[f] < (1 - slowdown) * row[f]:
                    failures.append(
                        f"{name}/{case}: {f} {row[f]:.3f} -> {now[f]:.3f} "
                        f"(>{slowdown:.0%} slowdown)")
    return failures


def merge_ratio_stats(reports: list[dict], reduce) -> dict:
    """Flake dampening: replace each ratio-gated row's timing ratios with
    ``reduce(samples)`` across ``reports`` (median when gating, min when
    refreshing the baseline).  The first report supplies everything else
    (checks, ungated rows)."""
    merged = reports[0]
    if len(reports) < 2:
        return merged
    for name, bench in merged.get("benchmarks", {}).items():
        fields = _ratio_fields(name)
        if not fields:
            continue
        samples: dict[tuple, list[float]] = {}
        for rep in reports:
            b = rep.get("benchmarks", {}).get(name)
            if b is None:
                continue
            for row in b["rows"]:
                for f in fields:
                    if f in row:
                        samples.setdefault((row.get("case"), f),
                                           []).append(row[f])
        for row in bench["rows"]:
            for f in fields:
                vals = samples.get((row.get("case"), f))
                if vals:
                    row[f] = reduce(vals)
    return merged


def merge_median_speedups(reports: list[dict]) -> dict:
    return merge_ratio_stats(reports, statistics.median)


def baseline_snapshot(base: dict) -> dict:
    """A compact summary of a baseline for the ``"history"`` trail: check
    pass/fail counts and every gated ratio value."""
    verdicts = [_verdict(c) for c in base.get("checks", [])]
    ratios = {}
    for name, bench in base.get("benchmarks", {}).items():
        for row in bench.get("rows", []):
            for f in _ratio_fields(name):
                if f in row:
                    ratios[f"{name}/{row.get('case')}/{f}"] = row[f]
    return {
        "checks_pass": sum(1 for v in verdicts if v == "PASS"),
        "checks_fail": sum(1 for v in verdicts if v == "FAIL"),
        "n_benchmarks": len(base.get("benchmarks", {})),
        "ratios": ratios,
    }


def refresh_baseline(base: dict, cur: dict, stamp: str | None = None) -> dict:
    """The new baseline on an intentional refresh: ``cur`` plus the old
    baseline's history trail extended with a snapshot of the old
    baseline itself.  ``compare`` ignores ``"history"`` entirely."""
    entry = {"refreshed": stamp or time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime()),
             "previous": baseline_snapshot(base)}
    out = dict(cur)
    out["history"] = list(base.get("history", [])) + [entry]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="BENCH_fcnn.json")
    ap.add_argument("--report", default=None,
                    help="pre-computed benchmarks.run --json report "
                         "(default: run the benchmarks now)")
    ap.add_argument("--slowdown", type=float, default=0.20,
                    help="max tolerated microbench speedup-ratio drop")
    ap.add_argument("--repeats", type=int, default=3,
                    help="microbench re-runs; the gate compares the median "
                         "speedup per case (only when running fresh)")
    ap.add_argument("--refresh", action="store_true",
                    help="intentional baseline refresh: write the fresh "
                         "report (ratio fields at the per-case minimum "
                         "across repeats) as the new baseline, appending "
                         "a snapshot of the old baseline to its "
                         "\"history\" trail")
    args = ap.parse_args()

    with open(args.baseline) as f:
        base = json.load(f)

    if args.report:
        with open(args.report) as f:
            cur = json.load(f)
    else:
        report_path = tempfile.mktemp(suffix=".json", prefix="bench_gate_")
        # The sweeps run in child processes, each of which may need the
        # chip; this parent must never import JAX, or it holds the chip.
        print(f"# bench-gate: running benchmarks -> {report_path}")
        subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--json", report_path],
            check=True)
        with open(report_path) as f:
            reports = [json.load(f)]
        gated = [n for n in reports[0].get("benchmarks", {})
                 if _ratio_fields(n)]
        for rep in range(1, max(args.repeats, 1)):
            for name in gated:
                p = tempfile.mktemp(suffix=".json", prefix="bench_gate_")
                print(f"# bench-gate: timing-gated repeat {rep + 1}/"
                      f"{args.repeats}: {name}")
                subprocess.run(
                    [sys.executable, "-m", "benchmarks.run",
                     "--only", name, "--json", p], check=True)
                with open(p) as f:
                    reports.append(json.load(f))
        cur = merge_ratio_stats(
            reports, min if args.refresh else statistics.median)

    if args.refresh:
        refreshed = refresh_baseline(base, cur)
        accepted = compare(base, cur, args.slowdown)
        with open(args.baseline, "w") as f:
            json.dump(refreshed, f, indent=1)
        print(f"\n# bench-gate: refreshed {args.baseline} "
              f"({len(refreshed['history'])} history snapshot(s))")
        for msg in accepted:
            print(f"  accepted vs old baseline: {msg}")
        return

    failures = compare(base, cur, args.slowdown)
    if failures:
        print(f"\n# bench-gate: FAIL ({len(failures)} regressions "
              f"vs {args.baseline})")
        for msg in failures:
            print(f"  {msg}")
        sys.exit(1)
    n_checks = sum(1 for c in base.get("checks", []) if _verdict(c) == "PASS")
    print(f"\n# bench-gate: OK ({n_checks} gated checks held, "
          f"microbench within {args.slowdown:.0%} of {args.baseline})")


if __name__ == "__main__":
    main()
