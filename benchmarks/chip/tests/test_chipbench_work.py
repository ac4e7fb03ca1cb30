"""Roofline work counts come from a configuration's shapes alone: two
plans of one configuration (different routing tiles or schedules) give
the same count and the same share.  A serving count is the work of the
requests served, so ticks with empty slots count less.  The peak table
refuses a device it does not know."""

import inspect
import json
import os
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import bench  # noqa: E402
import peaks  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core import execplan  # noqa: E402

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
ROOFLINES = [m for m in BENCH["per_layer"] if m["name"].split(".")[0]
             .endswith("_roofline")]
V5E = peaks.peaks_for("TPU v5 lite")


def _two_plans(cell):
    cfg = program.capsnet_config(cell.sizes)
    train = cell.driver == "training"
    batch = cell.params.get("batch", cell.params.get("slots"))
    a = execplan.compile_plan(cfg, batch=batch, train=train, pipeline=True)
    for factor in (2.0, 4.0, 0.9, 0.8, 0.7, 0.6):
        try:
            b = execplan.compile_plan(
                cfg, batch=batch, train=train, pipeline=True,
                vmem_budget=int(a.vmem_budget * factor))
        except execplan.PlanError:
            continue
        if [(o.mode, o.block_i) for o in b.ops] != \
                [(o.mode, o.block_i) for o in a.ops]:
            return a, b
    pytest.skip("no second plan with other routing tiles or modes")


def _trace(patterns, seconds):
    """One matching kernel event lasting ``seconds`` in a 1 s window."""
    return trace_reduce.Trace(
        ops=[(0, f"{patterns[0]}.1", 0, int(seconds * 1e9), True)],
        spans=[(trace_reduce.WINDOW, 0, int(1e9))])


def _ctx(cell, ref, trace, *, calls, served, b, plan=None):
    run = {"ticks": calls, "ok": served, "steps": calls, "slots": b,
           "batch": b, "plan": plan}
    return bench.Ctx(cell=cell, ref=ref, run=run, trace=trace, peaks=V5E)


def _window_work(mod, cell, ref, calls, served, b):
    """The work a reader counts for ``calls`` calls: a serving reader
    that of the ``served`` requests and a weight read per call, a
    training reader ``calls`` steps of batch ``b``."""
    if cell.driver == "serving":
        return mod.layer_work(ref, cell.sizes, served, calls)
    flops, moved = mod.layer_work(ref, cell.sizes, b)
    return calls * flops, calls * moved


@pytest.mark.parametrize("metric", [m["name"] for m in ROOFLINES])
def test_work_count_depends_on_shapes_only(metric):
    mod = spec.metric_reader(metric)
    params = list(inspect.signature(mod.layer_work).parameters)
    assert params[:3] == ["ref", "s", "b"] and "plan" not in params
    row = next(m for m in ROOFLINES if m["name"] == metric)
    for cell_name in row["workloads"]:
        cell = spec.load_cell(cell_name)
        ref = spec.reference(cell.sizes)
        plan_a, plan_b = _two_plans(cell)
        b = cell.params.get("batch", cell.params.get("slots"))
        flops, moved = _window_work(mod, cell, ref, 10, 10 * b, b)
        assert flops > 0 and moved > 0
        # Ten full calls whose kernel time is exactly the roofline's
        # bound read 100%; the plan in the run's record changes nothing.
        bound = max(flops / V5E["bf16_flops"],
                    moved / V5E["hbm_bytes_per_s"])
        trace = _trace(mod.PATTERNS, bound)
        shares = [mod.read(_ctx(cell, ref, trace, calls=10, served=10 * b,
                                b=b, plan=plan))
                  for plan in (plan_a, plan_b)]
        assert shares[0] == shares[1] == pytest.approx(100.0, rel=1e-5)


SERVING_WORK = [m["name"] for m in BENCH["per_layer"]
                if m["name"].endswith(".serve")
                and (m["name"].startswith("mfu")
                     or m["name"].split(".")[0].endswith("_roofline"))]


@pytest.mark.parametrize("metric", SERVING_WORK)
def test_serving_work_counts_only_the_requests_served(metric):
    """Ticks with half their slots empty count half the FLOPs of full
    ones, so computing empty rows is never counted as work."""
    mod = spec.metric_reader(metric)
    cell = spec.load_cell("mnist-serve")
    ref = spec.reference(cell.sizes)
    slots = cell.params["slots"]
    trace = _trace(getattr(mod, "PATTERNS", ("primary_caps_routing",)),
                   0.5)
    full, half = (mod.read(_ctx(cell, ref, trace, calls=10, served=n,
                                b=slots))
                  for n in (10 * slots, 5 * slots))
    assert 0 < half < full
    if hasattr(mod, "layer_work"):
        f_full, m_full = mod.layer_work(ref, cell.sizes, 10 * slots, 10)
        f_half, m_half = mod.layer_work(ref, cell.sizes, 5 * slots, 10)
        _, weights = mod.layer_work(ref, cell.sizes, 0, 10)
        assert f_half == pytest.approx(f_full / 2)
        assert m_half - weights == pytest.approx((m_full - weights) / 2)
    else:
        assert half == pytest.approx(full / 2)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9


def test_roofline_share_names_its_bound():
    assert peaks.roofline_share(197e12, 1.0, 2.0, V5E) == \
        pytest.approx((50.0, "compute"))
    share, bound = peaks.roofline_share(1.0, 819e9, 4.0, V5E)
    assert bound == "memory" and share == pytest.approx(25.0)
