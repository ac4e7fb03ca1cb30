"""Benchmark harness: one module per paper table/figure (+ the TPU-side
planner, kernels, roofline, and paper-claim validation).

Prints ``name,us_per_call,derived`` CSV rows.  ``--json PATH`` additionally
writes every row as a machine-readable artifact (CI uploads
``BENCH_capsule.json`` from the ``capsule`` module so the perf trajectory
is tracked across commits).  ``--baseline PATH`` compares this run's
``us_per_call`` against a prior artifact and FAILS on regressions beyond
``--regression-factor`` (default 1.5x) -- CI runs the capsule module
against the committed ``benchmarks/BENCH_baseline.json`` so the perf
trajectory actually gates.

``--trend PATH [PATH ...]`` watches the drift the gate cannot see: it
compares the last N ``BENCH_capsule.json`` artifacts (chronological; a
single directory argument globs ``BENCH*.json`` by mtime), appends the
CURRENT run's rows as the newest point, and FAILS on rows whose
speed-normalized time creeps up monotonically across the window even
though every single step stayed below the gate's factor.

Usage: PYTHONPATH=src python -m benchmarks.run [module ...] [--json PATH]
       [--baseline PATH] [--regression-factor X]
       [--trend PATH ...] [--trend-window N]
"""

import argparse
import json
import pathlib
import platform
import traceback

from benchmarks import (bench_capsule, bench_dataflow, bench_fig4,
                        bench_fig5, bench_fig10, bench_fig11, bench_kernels,
                        bench_paper_validation, bench_planner, bench_roofline,
                        bench_table2, common)
from repro.core.compile_cache import enable_compile_cache

MODULES = {
    "capsule": bench_capsule,
    "fig4": bench_fig4,
    "fig5": bench_fig5,
    "table2": bench_table2,
    "fig10": bench_fig10,
    "fig11": bench_fig11,
    "dataflow": bench_dataflow,
    "planner": bench_planner,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "validation": bench_paper_validation,
}

class BaselineSchemaError(RuntimeError):
    """The committed --baseline artifact cannot gate this run: malformed
    rows, duplicates, or STALE rows naming benchmarks the run no longer
    produces (a rename silently drops the row from the gate -- the
    regression it guarded would never flag again)."""


def check_baseline_schema(baseline: dict, rows: list[dict],
                          modules: list[str]) -> None:
    """Validate the --baseline artifact BEFORE gating against it.

    Structural checks always run: ``rows`` must be a list of dicts with a
    unique string ``name`` and a non-negative numeric ``us_per_call``.
    The staleness check runs only when this run covered every module the
    baseline recorded (a subset run legitimately misses rows): a timed
    baseline row absent from the current output names a benchmark that
    was renamed or removed, so the committed artifact needs a refresh.
    """
    if not isinstance(baseline, dict) \
            or not isinstance(baseline.get("rows"), list):
        raise BaselineSchemaError(
            "baseline artifact has no 'rows' list -- not a --json artifact "
            "of this harness")
    seen: set = set()
    for i, row in enumerate(baseline["rows"]):
        if not isinstance(row, dict) or not isinstance(row.get("name"), str):
            raise BaselineSchemaError(
                f"baseline row {i} has no string 'name': {row!r}")
        us = row.get("us_per_call", 0.0)
        if not isinstance(us, (int, float)) or isinstance(us, bool) \
                or us < 0.0:
            raise BaselineSchemaError(
                f"baseline row {row['name']!r}: us_per_call must be a "
                f"non-negative number, got {us!r}")
        if row["name"] in seen:
            raise BaselineSchemaError(
                f"baseline row {row['name']!r} appears twice -- ambiguous "
                f"gate")
        seen.add(row["name"])
    if set(baseline.get("modules", [])) <= set(modules):
        current = {r["name"] for r in rows}
        stale = sorted(
            row["name"] for row in baseline["rows"]
            if row.get("us_per_call", 0.0) > 0.0
            and row.get("gate", True) and row["name"] not in current)
        if stale:
            raise BaselineSchemaError(
                f"stale baseline row(s) {stale}: this run produced no such "
                f"benchmark -- refresh {BASELINE_NAME}")


def compare_baseline(rows: list[dict], baseline: dict,
                     factor: float) -> list[dict]:
    """Rows regressing beyond ``factor`` vs the baseline artifact.

    Only rows timed in BOTH runs participate (``us_per_call > 0``; the
    0.0-timed derived/plan rows carry no perf signal, and rows emitted
    with ``gate=False`` are wall-clock observations).  Machine speed is
    normalized out by the MEDIAN current/baseline ratio across the shared
    rows: a uniformly slower CI runner shifts every ratio (and the
    median with it) so nothing is flagged, while a single genuinely
    regressed row stands out against the unmoved median.

    Two accepted limitations of self-normalization: a regression hitting
    HALF or more of the gated rows moves the median with it and escapes
    (there is no absolute clock to compare against across machines), and
    machines whose per-row speed RATIOS differ from the baseline
    author's (BLAS/threading/cache differences) shift individual rows --
    CI therefore gates with a looser factor than the local default.
    """
    base = {r["name"]: r.get("us_per_call", 0.0)
            for r in baseline.get("rows", [])}
    cur = {r["name"]: r.get("us_per_call", 0.0) for r in rows
           if r.get("gate", True)}
    shared = {name: us / base[name] for name, us in cur.items()
              if us > 0.0 and base.get(name, 0.0) > 0.0}
    if not shared:
        return []
    ratios = sorted(shared.values())
    scale = ratios[len(ratios) // 2]              # median speed delta
    regressions = []
    for name, ratio in sorted(shared.items()):
        if ratio / scale > factor:
            regressions.append(dict(name=name, ratio=round(ratio / scale, 2),
                                    us_per_call=cur[name],
                                    baseline_us=base[name],
                                    scale=round(scale, 3)))
    return regressions


def detect_trend(histories: list[dict], *, min_points: int = 3,
                 tolerance: float = 0.03, min_total: float = 1.2
                 ) -> list[dict]:
    """Rows whose ``us_per_call`` creeps up monotonically across artifacts.

    The ``--baseline`` gate catches a single-step regression beyond its
    factor (1.5x locally); a drift of +10% per commit stays below that
    threshold forever.  Given the last N artifacts in chronological
    order, each artifact is speed-normalized by the MEDIAN ratio of its
    shared timed rows vs the first artifact (the gate's machine-speed
    cancellation), and a row is flagged when its normalized time never
    drops by more than ``tolerance`` at any step AND the total drift
    across the window exceeds ``min_total`` -- a monotonic slowdown the
    per-commit gate never fired on.

    Returns ``[{name, ratio, us_per_call, first_us, points}, ...]``;
    empty when fewer than ``min_points`` artifacts are given.
    """
    if len(histories) < min_points:
        return []
    runs = [{r["name"]: r.get("us_per_call", 0.0)
             for r in h.get("rows", []) if r.get("gate", True)}
            for h in histories]
    shared = [n for n, us in runs[0].items()
              if us > 0.0 and all(run.get(n, 0.0) > 0.0 for run in runs)]
    if not shared:
        return []
    norm = []
    for run in runs:
        ratios = sorted(run[n] / runs[0][n] for n in shared)
        scale = ratios[len(ratios) // 2]          # median speed delta
        norm.append({n: run[n] / scale for n in shared})
    flagged = []
    for name in sorted(shared):
        seq = [run[name] for run in norm]
        monotone = all(b >= a * (1.0 - tolerance)
                       for a, b in zip(seq, seq[1:]))
        total = seq[-1] / seq[0]
        if monotone and total > min_total:
            flagged.append(dict(name=name, ratio=round(total, 2),
                                us_per_call=runs[-1][name],
                                first_us=runs[0][name],
                                points=len(seq)))
    return flagged


BASELINE_NAME = "BENCH_baseline.json"


def _trend_paths(args_trend: list[str], window: int) -> list[pathlib.Path]:
    """Artifact paths, chronological: explicit files keep their order; a
    single directory argument globs BENCH*.json sorted by mtime.  The
    committed gate baseline (``BENCH_baseline.json``) is NOT a trend
    point: a freshly refreshed baseline has the newest mtime and would
    land as the "newest" run, corrupting the chronology (it still
    participates when named explicitly).  Only the last ``window``
    participate."""
    if len(args_trend) == 1 and pathlib.Path(args_trend[0]).is_dir():
        paths = sorted((p for p in
                        pathlib.Path(args_trend[0]).glob("BENCH*.json")
                        if p.name != BASELINE_NAME),
                       key=lambda p: p.stat().st_mtime)
    else:
        paths = [pathlib.Path(p) for p in args_trend]
    return paths[-window:]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("modules", nargs="*", default=[], metavar="module",
                    help=f"subset of: {' '.join(MODULES)} (default: all)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a JSON artifact")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="prior --json artifact to gate us_per_call against")
    ap.add_argument("--regression-factor", type=float, default=1.5,
                    metavar="X", help="fail when a row exceeds X * baseline "
                    "(speed-normalized; default 1.5)")
    ap.add_argument("--trend", nargs="+", default=None, metavar="PATH",
                    help="prior --json artifacts (chronological), or ONE "
                    "directory of them: fail on monotonic slowdowns the "
                    "per-commit gate stayed below")
    ap.add_argument("--trend-window", type=int, default=5, metavar="N",
                    help="how many of the newest artifacts to compare "
                    "(default 5)")
    args = ap.parse_args()
    unknown = [n for n in args.modules if n not in MODULES]
    if unknown:
        ap.error(f"unknown module(s) {unknown}; choose from {list(MODULES)}")
    names = args.modules or list(MODULES)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        try:
            MODULES[name].main()
        except Exception:
            failures.append(name)
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if args.baseline:                 # gate BEFORE the artifact dump so a
        with open(args.baseline) as fh:   # baseline failure is recorded in it
            baseline = json.load(fh)
        try:
            check_baseline_schema(baseline, common.RECORDS, names)
        except BaselineSchemaError as err:
            print(f"BASELINE SCHEMA ERROR for {args.baseline}: {err}")
            failures.append("baseline-schema")
        regressions = compare_baseline(common.RECORDS, baseline,
                                       args.regression_factor)
        if regressions:
            print(f"PERF REGRESSIONS vs {args.baseline} "
                  f"(>{args.regression_factor}x, speed-normalized):")
            for r in regressions:
                print(f"  {r['name']}: {r['us_per_call']:.1f} us vs "
                      f"{r['baseline_us']:.1f} us baseline "
                      f"({r['ratio']}x at scale {r['scale']})")
            failures.append("baseline")
        else:
            print(f"no perf regressions vs {args.baseline} "
                  f"(factor {args.regression_factor}x)")
    if args.trend:
        paths = _trend_paths(args.trend, args.trend_window)
        histories = []
        for p in paths:
            with open(p) as fh:
                histories.append(json.load(fh))
        if common.RECORDS:
            # THIS run is the newest history point: a drift completed by
            # the current commit must flag now, not one artifact later.
            histories.append(dict(rows=common.RECORDS))
            histories = histories[-args.trend_window:]
        trends = detect_trend(histories)
        if trends:
            print(f"PERF TRENDS over {len(histories)} artifacts "
                  f"(monotonic, speed-normalized):")
            for t in trends:
                print(f"  {t['name']}: {t['first_us']:.1f} us -> "
                      f"{t['us_per_call']:.1f} us ({t['ratio']}x over "
                      f"{t['points']} runs)")
            failures.append("trend")
        elif len(histories) < 3:
            print(f"trend: only {len(histories)} artifact(s), need >= 3")
        else:
            print(f"no perf trends over {len(histories)} artifacts")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(dict(modules=names, failures=failures,
                           python=platform.python_version(),
                           rows=common.RECORDS), fh, indent=1)
        print(f"wrote {len(common.RECORDS)} rows to {args.json}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
