"""The control: the plain reference at the next precision down (three
bfloat16 passes) in the program's place.  On the chip it runs at the
cells' sizes through ``limits.py``, with the TPU's own ``HIGH``; here
the exact three-pass emulation runs at widths a CPU test run can hold.
Serving: it comes out as not correct under the cell's own limits.
Training: the emulation reads about a seventh of what the TPU's ``HIGH``
does, which at these widths falls under the training limits, so the
test holds it to what the limits rest on: on each seed it reads at least
three times what the program reads at the same size, on some number."""

import dataclasses
import os
import pathlib
import sys
import time

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import checks  # noqa: E402
import serving  # noqa: E402
import spec  # noqa: E402
import training  # noqa: E402

# Half of capsnet-mnist's channels and capsule groups.
HALF = dict(conv1_channels=128, num_primary_groups=16)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_is_not_correct(seed):
    cell = spec.load_cell("mnist-serve")
    cell = dataclasses.replace(
        cell, sizes=dict(cell.sizes, **HALF),
        traffic=dict(cell.traffic, check_sample=64, image_pool=64))
    got = serving.control(cell, seed, 64 / cell.params["rate_per_s"],
                          ref=spec.reference(cell.sizes))
    correct, compared = checks.judge(got, cell.params["limits"])
    assert not correct, compared


SMOKE = dict(image_hw=14, conv1_channels=32, conv1_kernel=5, pc_kernel=3,
             num_primary_groups=4, primary_dim=4, class_dim=8,
             decoder_hidden=[32, 64])


@pytest.mark.parametrize("seed", [1, 2])
def test_training_control_separates_from_the_program(seed):
    cell = spec.load_cell("mnist-train")
    cell = dataclasses.replace(
        cell, sizes=dict(cell.sizes, **SMOKE),
        params=dict(cell.params, batch=4),
        traffic=dict(cell.traffic, batch_pool=4))
    program = bench.run_cell(cell, seed, 1.0, False,
                             t_start=time.perf_counter(),
                             devices=jax.devices())
    got = training.control(cell, seed, ref=spec.reference(cell.sizes))
    ratios = {k: got[k] / max(c["value"], 1e-12)
              for k, c in program["checks"].items()}
    assert max(ratios.values()) >= 3, (ratios, got, program["checks"])
