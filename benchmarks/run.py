"""Benchmark suite entry point: one benchmark per paper figure/table.

  PYTHONPATH=src python -m benchmarks.run [--only fig7_mttf[,sim_bench]]
      [--json out.json] [--quick] [--profile]
      [--compare BENCH_sim.json]

``--json`` writes a machine-readable trajectory point: per-benchmark rows,
checks, wall-clock, and scale labels plus the git SHA and timestamp of the
run (see BENCH_sim.json for the committed sim_bench + ensemble_bench
baseline).  ``--profile`` runs the ``--only`` selection under cProfile
and prints the top cumulative hotspots: natively profile-aware
benchmarks (sim_bench) swap to a representative single workload, the
rest get a generic whole-benchmark cProfile wrap.  ``--profile``
without ``--only`` is an error (it lists the registered benchmarks).

``--compare BASELINE.json`` is the perf-regression gate: after the run it
diffs every numeric metric shared with the baseline file (printing
per-metric deltas) and exits non-zero if any throughput metric — a row
key ending in ``jobs_per_sec`` or ``cells_per_sec`` — dropped by more
than 20%.  Metrics (and whole benchmarks) present in the current run
but absent from the baseline are noted and skipped, never gated —
regenerate the baseline to start gating them.  Unless ``--only``
narrows further, the run is restricted to the benchmarks present in
the baseline.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

# importing registers each benchmark
from benchmarks import (cache_bench, ensemble_bench, fig3_job_status,  # noqa: F401
                        fig4_attribution, fig5_timeline, fig6_job_mix,
                        fig7_mttf,
                        fig8_goodput_loss, fig9_ettr, fig10_contours,
                        fig11_scale_projection, fig12_adaptive_routing,
                        fig13_mitigations, fork_bench, obs_bench,
                        runtime_ettr, sim_bench, stat_bench, table2_lemon,
                        trace_bench)
from benchmarks import common
from benchmarks.common import all_benchmarks
from repro.launch.compile_cache import configure_compile_cache


_THROUGHPUT_SUFFIXES = ("jobs_per_sec", "cells_per_sec")
_MAX_THROUGHPUT_DROP = 0.20

_REGEN_HINT = (
    "regenerate it from a clean tree with:\n"
    "  PYTHONPATH=src python -m benchmarks.run "
    "--only sim_bench,ensemble_bench,stat_bench,fork_bench,cache_bench "
    "--json BENCH_sim.json")


def _load_baseline(path: str) -> dict:
    """Read a ``--compare`` baseline, failing fast with a regeneration
    recipe when the file is missing or not a benchmark-run json."""
    try:
        with open(path) as f:
            base = json.load(f)
    except FileNotFoundError:
        sys.exit(f"error: --compare baseline {path!r} does not exist; "
                 f"{_REGEN_HINT}")
    except (OSError, json.JSONDecodeError) as e:
        sys.exit(f"error: --compare baseline {path!r} is unreadable "
                 f"({e}); {_REGEN_HINT}")
    if not isinstance(base, dict) or "benchmarks" not in base:
        sys.exit(f"error: --compare baseline {path!r} has no "
                 f"'benchmarks' section (not a benchmarks.run --json "
                 f"file?); {_REGEN_HINT}")
    return base


def _numeric(v):
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f


def compare_results(baseline_path: str, results: dict) -> int:
    """Print per-metric deltas vs a ``--json`` baseline file; return the
    number of >20% throughput regressions (jobs/sec, cells/sec)."""
    base = _load_baseline(baseline_path)
    sha = base.get("meta", {}).get("git_sha", "?")
    print(f"\n=== regression diff vs {baseline_path} (baseline git {sha}) "
          f"===")
    regressions = 0
    compared = 0
    new_metrics = 0
    base_benchmarks = base.get("benchmarks", {})
    for name, bres in base_benchmarks.items():
        cur = results.get(name)
        if cur is None:
            print(f"  {name}: not run (skipped in diff)")
            continue
        cur_rows = {k: v for k, v, _ in cur["rows"]}
        base_keys = {key for key, _, _ in bres.get("rows", [])}
        for key, bval, _ in bres.get("rows", []):
            bnum = _numeric(bval)
            cnum = _numeric(cur_rows.get(key))
            if bnum is None or cnum is None or bnum == 0:
                continue
            delta = (cnum - bnum) / abs(bnum)
            flag = ""
            if (key.endswith(_THROUGHPUT_SUFFIXES)
                    and delta < -_MAX_THROUGHPUT_DROP):
                regressions += 1
                flag = f"  << REGRESSION (>{_MAX_THROUGHPUT_DROP:.0%} drop)"
            print(f"  {name}.{key:52s} {bnum:>12.6g} -> {cnum:>12.6g} "
                  f"{delta:+8.1%}{flag}")
            compared += 1
        # metrics the current run has that the baseline predates: noted
        # and skipped, never gated — a new metric needs a regenerated
        # baseline, not a green-by-accident diff
        for key in (k for k, _, _ in cur["rows"] if k not in base_keys):
            if _numeric(cur_rows.get(key)) is None:
                continue
            new_metrics += 1
            print(f"  {name}.{key:52s} (new metric — not in baseline; "
                  f"skipped, regenerate the baseline to gate it)")
    for name in sorted(set(results) - set(base_benchmarks)):
        print(f"  {name}: new benchmark — not in baseline; skipped "
              f"(regenerate the baseline to gate it)")
    print(f"  {compared} shared metrics compared, "
          f"{new_metrics} new metrics skipped, "
          f"{regressions} throughput regressions")
    if not compared:
        print("  (no comparable numeric metrics — quick runs only compare "
              "against quick baselines)")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="small-scale defaults (CI smoke mode)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile the --only selection: top-20 "
                         "cumulative hotspots per benchmark (requires "
                         "--only; any registered benchmark works)")
    ap.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                    help="regression-diff mode: print per-metric deltas "
                         "vs a benchmarks.run --json file and exit "
                         "non-zero on a >20%% jobs/sec or cells/sec drop")
    args = ap.parse_args()
    configure_compile_cache()
    common.QUICK = args.quick
    common.PROFILE = args.profile
    only = set(args.only.split(",")) if args.only else None
    if args.profile and only is None:
        names = "\n  ".join(sorted(all_benchmarks()))
        ap.error("--profile needs --only to pick what to profile; "
                 f"registered benchmarks:\n  {names}")
    if args.compare and only is None:
        # default the run to the baseline's benchmark set (fails fast on
        # a missing/unreadable baseline, before any benchmark runs)
        only = set(_load_baseline(args.compare)["benchmarks"])
    if only:
        unknown = only - set(all_benchmarks())
        if unknown:
            names = "\n  ".join(sorted(all_benchmarks()))
            ap.error(f"unknown benchmark(s) {sorted(unknown)}; registered "
                     f"benchmarks:\n  {names}")

    t0 = time.time()
    results = {}
    n_warn = 0
    failures = []
    for name, fn in all_benchmarks().items():
        if only and name not in only:
            continue
        try:
            if args.profile and not getattr(fn, "native_profile", False):
                rep = common.profile_call(name, fn)
            else:
                rep = fn()
            rep.print()
            results[name] = {
                "rows": [[k, str(v), n] for k, v, n in rep.rows],
                "checks": [[d, ok, det] for d, ok, det in rep.checks],
                "wall_s": rep.wall_s,
                "labels": rep.meta,
            }
            n_warn += sum(1 for _, ok, _ in rep.checks if not ok)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"\n=== {name} === ERROR: {type(e).__name__}: {e}")
            traceback.print_exc()
    total_checks = sum(len(r["checks"]) for r in results.values())
    passed = total_checks - n_warn
    wall = time.time() - t0
    print(f"\n{'='*70}")
    print(f"benchmarks: {len(results)} ran, {len(failures)} errored "
          f"({failures if failures else ''})")
    print(f"paper-claim checks: {passed}/{total_checks} passed, "
          f"{n_warn} warnings; total {wall:.0f}s")
    if args.json:
        out = {
            "meta": {
                "git_sha": common.git_sha(),
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "python": platform.python_version(),
                "machine": platform.machine(),
                "quick": args.quick,
                "wall_s": round(wall, 2),
            },
            "benchmarks": results,
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    if failures:
        sys.exit(1)
    if args.compare:
        if compare_results(args.compare, results):
            sys.exit(2)


if __name__ == "__main__":
    main()
