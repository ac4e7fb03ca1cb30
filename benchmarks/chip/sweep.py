"""Serving runs over a grid of rates and seeds, in one process on the
chip: the sweep that finds a serving cell's knee.

    python3 benchmarks/chip/sweep.py --workload mnist-serve \\
        --rates 800,1200,1600 --seeds 1-3 --seconds 10

Each run is a ``bench.py`` run of the cell with its rate replaced, and
prints one ``sweep`` line of JSON with its diagnostics.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402
from limits import seed_list  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda t: [float(r) for r in t.split(",")])
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench.tpu_devices(cell.chips)
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in args.rates:
        rated = dataclasses.replace(
            cell, params=dict(cell.params, rate_per_s=rate))
        for seed in args.seeds:
            res = bench.run_cell(rated, seed, args.seconds, False,
                                 t_start=time.perf_counter(), devices=devices)
            print("sweep", json.dumps(
                {"rate": rate, "seed": seed,
                 "correct": res["correct"], **res["diag"]}), flush=True)
    print("total_s", time.perf_counter() - _T0, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
