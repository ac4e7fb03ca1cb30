"""Readings that a cell's limits are set from, in one process on the chip.

    python3 benchmarks/chip/limits.py --workload mnist-serve \\
        --seeds 1-12 --control-seeds 1-3 --seconds 4

For each seed of ``--seeds`` it runs the cell as ``bench.py`` does (a
short window at the cell's own load) and records every compared number:
the lower readings.  For each seed of ``--control-seeds`` it puts the
reference at the next precision down in the program's place and records
the same numbers: the upper readings.  The cell's driver may plant
faults of its own (``PLANTED``: training leaves half of each batch out,
in the reference put in the program's place); they are read on the same
seeds.  The control's and each fault's readings go through the cell's
own limits (``checks.judge``), which have to find them not correct.
Prints every reading and, as its last line, all of them as one JSON
object; it sets no limit itself (PERF.md gives each limit with the
readings behind it).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402

import bench  # noqa: E402
import checks  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402


def seed_list(text: str) -> list[int]:
    """"1-3,9" -> [1, 2, 3, 9]."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, default=[])
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench.tpu_devices(cell.chips)
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ref = spec.reference(cell.sizes)
    out = {"workload": cell.name, "seconds": args.seconds, "program": {}}
    for seed in args.seeds:
        res = bench.run_cell(cell, seed, args.seconds, False,
                             t_start=time.perf_counter(), devices=devices)
        out["program"][seed] = {**{k: c["value"]
                                   for k, c in res["checks"].items()},
                                **res["diag"]["not_compared"]}
        print("program", seed, json.dumps(out["program"][seed]),
              res["metrics"], flush=True)
    drv = spec.driver(cell)
    limits = cell.params["limits"]
    planted = {"control": drv.control, **getattr(drv, "PLANTED", {})}
    for seed in args.control_seeds:
        for kind, fn in planted.items():
            got = fn(cell, seed, args.seconds, ref=ref)
            if got is None:
                continue
            correct, _ = checks.judge(got, limits)
            out.setdefault(kind, {})[seed] = dict(got, correct=correct)
            print(kind, seed, "correct", correct, json.dumps(got),
                  flush=True)
    names = {k for r in [*out["program"].values(),
                         *out.get("control", {}).values()] for k in r}
    for name in sorted(names - {"correct"}):
        lows = [r[name] for r in out["program"].values()]
        highs = [r[name] for r in out.get("control", {}).values()]
        print(f"{name}: program max {max(lows, default=None)!r} "
              f"control min {min(highs, default=None)!r}", flush=True)
    for kind in planted:
        seen = [r["correct"] for r in out.get(kind, {}).values()]
        if seen:
            print(f"{kind}: correct on {sum(seen)} of {len(seen)} seeds",
                  flush=True)
    print("total_s", time.perf_counter() - _T0, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
