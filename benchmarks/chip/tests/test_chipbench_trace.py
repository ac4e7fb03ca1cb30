"""The reduction from trace events to device numbers, on two fixtures:
three engine ticks of a traced mnist-serve run on a v5e, and a small
one whose answers are worked out by hand:

- window 1000..11000 ns; device operations cover 1000..1200 (a fusion
  clipped at the window's start), 1500..4000 (a kernel overlapping a
  concatenate), 6000..7000 and 9000..11000 (a kernel clipped at the
  end): 5700 ns busy, 43% idle;
- idle gaps 1200..1500 and 7000..9000 fall in engine ticks, 4000..6000
  mostly in the generator's wait.
"""

import os
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import trace_reduce as tr  # noqa: E402


@pytest.fixture
def trace():
    return tr.Trace.from_json(
        (CHIP / "tests" / "fixtures" / "trace_small.json").read_text())


def test_busy_union_and_idle_share(trace):
    assert tr.window_s(trace) == pytest.approx(10000e-9)
    assert tr.busy_s(trace) == pytest.approx(5700e-9)
    assert tr.idle_share(trace) == pytest.approx(0.43)


def test_busy_is_averaged_over_devices(trace):
    two = tr.Trace(ops=trace.ops + [(1, "copy.9", 2000, 3000, False)],
                   spans=trace.spans)
    assert tr.busy_s(two) == pytest.approx((5700e-9 + 1000e-9) / 2)


def test_kernel_time_by_pattern(trace):
    assert tr.kernel_seconds(trace, ("primary_caps_routing",)) == \
        pytest.approx(4000e-9)
    assert tr.kernel_seconds(trace, ("transpose_jvp",)) is None
    # a name that matches only XLA operations is no kernel
    assert tr.kernel_seconds(trace, ("concatenate",)) is None
    assert tr.xla_seconds(trace) == pytest.approx(2200e-9)


def test_idle_gaps_by_host_span(trace):
    gaps = tr.idle_gaps(trace)
    assert gaps == pytest.approx({"engine.step": 2300e-9,
                                  "generator.wait": 2000e-9})
    assert sum(gaps.values()) == pytest.approx(
        tr.window_s(trace) - tr.busy_s(trace))


def test_gap_without_span_and_top(trace):
    bare = tr.Trace(ops=trace.ops, spans=[("bench.window", 1000, 11000)])
    assert tr.idle_gaps(bare) == pytest.approx({tr.NO_SPAN: 4300e-9})
    top = tr.top(tr.op_seconds(trace), 2)
    assert top[0][0] == "primary_caps_routing.1" and len(top) == 2


def test_one_window_required(trace):
    with pytest.raises(ValueError, match="bench.window"):
        tr.Trace(ops=trace.ops, spans=trace.spans[1:]).window


def test_json_round_trip(trace):
    assert tr.Trace.from_json(trace.to_json()) == trace


def test_load_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.load_xplane(tmp_path)
    names = [n for n, _, _ in got.spans]
    assert names.count(tr.WINDOW) == 1 and "engine.step" in names
    lo, hi = got.window
    assert hi > lo
    assert got.ops == []     # the CPU has no TPU plane


def test_op_name_of_an_hlo_event():
    kernel = ('%primary_caps_routing.1 = f32[16,10,16,1]{3,2,1,0} custom-'
              'call(f32[16,36,20736]{2,1,0} %reshape.770), custom_call_'
              'target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_name(kernel) == ("primary_caps_routing.1", True)
    assert tr.op_name("%concatenate.249 = f32[6400,81]{1,0} concatenate("
                      "f32[6400,1]{1,0} %reshape.689)") == \
        ("concatenate.249", False)
    assert tr.op_name("copy.3") == ("copy.3", False)


def test_recorded_ticks():
    """Three ticks of a real run: the reduction's parts add up, and the
    kernel and the patch extraction are where the run put them."""
    real = tr.Trace.from_json(
        (CHIP / "tests" / "fixtures" / "trace_mnist_serve_3ticks.json")
        .read_text())
    window, busy = tr.window_s(real), tr.busy_s(real)
    assert 0 < busy < window
    assert sum(tr.idle_gaps(real).values()) == pytest.approx(window - busy)
    assert set(tr.idle_gaps(real)) <= {"engine.step", "generator.wait",
                                       tr.NO_SPAN}
    kernel = tr.kernel_seconds(real, ("primary_caps_routing",))
    ops = tr.op_seconds(real)
    assert kernel == pytest.approx(sum(
        v for n, v in ops.items() if n.startswith("primary_caps_routing")))
    assert tr.xla_seconds(real) + sum(
        tr.op_seconds(real, pallas=True).values()) == \
        pytest.approx(sum(ops.values()))
    assert tr.xla_seconds(real) > ops["concatenate.249"] > 0
