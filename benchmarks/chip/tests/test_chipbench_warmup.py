"""Warm-up and the window's compile counter, on the CPU at smoke widths:
the warm-up reaches every padded scatter size of a serving cell's slot
count, and a compile forced inside a window is counted."""

import os
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import openloop  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402

SMOKE = dict(image_hw=14, conv1_channels=32, conv1_kernel=5, pc_kernel=3,
             num_primary_groups=4, primary_dim=4, class_dim=8,
             decoder_hidden=[32, 64])


SERVING = [w["name"] for w in spec.load_bench()["workloads"]
           if spec.load_cell(w["name"]).driver == "serving"]


@pytest.mark.parametrize("cell_name", SERVING)
def test_warm_up_reaches_every_padded_scatter_size(cell_name):
    cell = spec.load_cell(cell_name)
    sizes = dict(cell.sizes, **SMOKE)
    slots = cell.params["slots"]
    ref = spec.reference(sizes)
    engine = program.make_engine(ref.init_params(3, sizes), sizes, slots)
    seen = []
    scatter = engine._scatter

    def recording(b, i, x):
        seen.append(int(i.shape[0]))
        return scatter(b, i, x)

    engine._scatter = recording
    images = ref.request_images(3, sizes, 4)
    openloop.warm_up(engine, lambda rid, img, d=None: program.CapsRequest(
        rid=rid, image=img, deadline_s=d), images, slots)
    assert sorted(set(seen)) == openloop.padded_sizes(slots)
    assert engine._forward_traces == 1
    assert engine.stats()["ok"] == slots * (slots + 1) // 2


def test_compile_counter_counts_only_inside_the_window():
    counter = openloop.CompileCounter()
    try:
        jax.jit(lambda x: x - 3.0)(jnp.ones(5)).block_until_ready()
        assert counter.compiles == 0          # not yet in a window
        counter.active = True
        jax.jit(lambda x: x * 7.0 + 1.0)(jnp.ones(7)).block_until_ready()
        counter.active = False
        assert counter.compiles >= 1 and counter.traces >= 1
    finally:
        counter.close()


def test_gc_pauses_are_timed_inside_the_window():
    import gc
    pauses = openloop.GcPauses()
    try:
        gc.collect()
        assert pauses.collections == 0
        pauses.active = True
        gc.collect()
        pauses.active = False
        assert pauses.collections == 1 and pauses.seconds > 0
    finally:
        pauses.close()
