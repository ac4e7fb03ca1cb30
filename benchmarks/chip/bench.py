"""The chip benchmark: one cell, one seed, one measured window.

    python3 benchmarks/chip/bench.py --workload mnist-serve --seed 7 \\
        --seconds 10 --trace 0

Runs only where JAX's devices are TPUs, at least as many as the cell
asks for; otherwise it exits nonzero and prints no result.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Every run checks what the timed path produced against the
plain reference: the compared numbers and their limits are the last
lines on standard error and the last key of the result, the JSON object
on the last line of standard output.

The pieces of a cell are found by name (see ``spec.py``).  JAX's
persistent compilation cache lives in the checkout (``.jax_cache``), so
only a cell's first run there compiles.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402

import checks  # noqa: E402
import openloop  # noqa: E402
import peaks  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402


# Host events of a traced window: the benchmark's own spans (host tracer)
# and the runtime's, without a Python frame for every call.
PROFILE_OPTIONS = {"host_tracer_level": 2, "python_tracer_level": 0}


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devices[0].platform!r}; "
                     f"this benchmark measures only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")
    return devices


def memory_peak() -> int | None:
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks_ = [s["peak_bytes_in_use"] for s in stats
              if s and "peak_bytes_in_use" in s]
    return max(peaks_) if peaks_ else None


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader sees."""
    cell: spec.Cell
    ref: object            # the configuration's reference module (shapes)
    run: dict              # the driver's counts (ticks, steps, window, ...)
    trace: trace_reduce.Trace | None
    peaks: dict | None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, devices: list) -> dict:
    """Run one cell and return its result object."""
    ref = spec.reference(cell.sizes)
    driver = spec.driver(cell)
    counter, gc_pauses = openloop.CompileCounter(), openloop.GcPauses()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    times = {}

    def begin_window():
        times["setup_s"] = time.perf_counter() - t_start
        if traced:
            opts = jax.profiler.ProfileOptions()
            for k, v in PROFILE_OPTIONS.items():
                setattr(opts, k, v)
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def end_window():
        if traced:
            jax.profiler.stop_trace()
        return memory_peak()

    span = ((lambda name: jax.profiler.TraceAnnotation(name)) if traced
            else (lambda _name: contextlib.nullcontext()))
    try:
        # The configuration states its matmul precision; the program's
        # jnp contractions (the decoder) follow JAX's default for it.
        with jax.default_matmul_precision(cell.sizes["matmul_precision"]):
            out = driver.run(cell, seed, seconds, ref=ref, prog=program,
                             span=span, counter=counter,
                             gc_pauses=gc_pauses,
                             mark_setup_done=begin_window,
                             memory_peak=end_window)
        trace = trace_reduce.load_xplane(trace_dir) if traced else None
    finally:
        counter.close()
        gc_pauses.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = out["run"]
    limits = cell.params["limits"]
    correct, compared = checks.judge(out["readings"], limits)
    diag = {"compiles_in_window": counter.compiles,
            "traces_in_window": counter.traces,
            "gc_pause_s": gc_pauses.seconds,
            "gc_collections": gc_pauses.collections,
            **{k: v for k, v in run.items() if k != "memory_peak_bytes"},
            "not_compared": {k: v for k, v in out["readings"].items()
                             if k not in limits}}
    print("diag " + json.dumps(diag), file=sys.stderr, flush=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    if traced:
        ctx = Ctx(cell=cell, ref=ref, run=run, trace=trace,
                  peaks=peaks.peaks_for(dev.device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=trace_reduce.busy_s(trace),
                      window_s=trace_reduce.window_s(trace))
    else:
        values = dict(out["end_to_end"], setup_s=times["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {
            "device_ops": trace_reduce.top(trace_reduce.op_seconds(trace)),
            "idle_gaps": trace_reduce.top(trace_reduce.idle_gaps(trace))}
    result["diag"] = diag
    result["checks"] = compared
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        program.capsnet_config(cell.sizes)   # refuses a key it cannot take
        devices = tpu_devices(cell.chips)
    except (spec.SpecError, NoChip) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=_T0, devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
