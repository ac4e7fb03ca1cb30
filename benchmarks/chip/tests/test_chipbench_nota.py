"""The CIFAR-10 configuration's none-of-the-above category, end to end on
the CPU.

The plain reference of the ``capsnet_nota`` family routes with the
category and imports nothing of the program.  The program, given the
reference's parameters, agrees with it at smoke widths (lengths, loss and
gradients), and a run of each new cell at smoke widths reads ``correct``
true with the cell's own limits.  A program that drops the category
reads ``correct`` false there, and at the published widths its lengths
differ from the reference's by far more than ``cifar10-serve``'s
``len_diff`` limit.
"""

import dataclasses
import json
import os
import pathlib
import sys
import time

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402
from repro.core import capsnet  # noqa: E402

SIZES = json.loads((CHIP / "configs" / "capsnet-cifar10-sabour.json")
                   .read_text())
NOTA = spec.reference(SIZES)
PLAIN = spec.reference(dict(SIZES, family="capsnet"))
SMOKE = dict(SIZES, image_hw=12, conv1_channels=32, conv1_kernel=5,
             pc_kernel=3, num_primary_groups=4, primary_dim=4, class_dim=8,
             decoder_hidden=[32, 64])
SEED = 2 ** 31 + 4242


def test_reference_routes_with_the_category_and_stands_alone():
    assert NOTA.routing is not PLAIN.routing
    b = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 10)) - 1.0
    zero = jnp.zeros((2, 5, 1))
    want = jax.nn.softmax(jnp.concatenate([zero, b], axis=2), axis=2)[..., 1:]
    np.testing.assert_allclose(np.asarray(NOTA.leaky_softmax(b, axis=2)),
                               np.asarray(want), rtol=1e-6, atol=1e-7)
    src = (CHIP / "configs" / "capsnet_nota.py").read_text()
    assert "repro" not in src.replace("research/capsules", "")
    assert NOTA.param_shapes(SIZES) == PLAIN.param_shapes(SIZES)


def test_program_agrees_with_the_reference_at_smoke_widths():
    """``backend="pallas"`` (interpreted) on the reference's parameters:
    lengths, loss and every gradient."""
    cfg = program.capsnet_config(SMOKE)
    assert cfg.none_of_the_above
    params = NOTA.init_params(SEED, SMOKE)
    images, labels = NOTA.train_batch(SEED, SMOKE, 3, 0)
    got = capsnet.forward(params, images, cfg, backend="pallas")["lengths"]
    want = NOTA.lengths(params, images, SMOKE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(
        PLAIN.lengths(params, images, SMOKE) - want))) > 1e-3

    def prog_loss(p):
        return capsnet.total_loss(p, images, labels, cfg,
                                  backend="pallas")[0]

    (l_got, g_got), (l_want, g_want) = (
        jax.value_and_grad(prog_loss)(params),
        jax.value_and_grad(lambda p: NOTA.loss(p, images, labels,
                                               SMOKE))(params))
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=1e-5)
    for k in g_want:
        np.testing.assert_allclose(np.asarray(g_got[k]),
                                   np.asarray(g_want[k]), rtol=1e-4,
                                   atol=1e-6, err_msg=k)


def _smoke_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    params, traffic = dict(cell.params), dict(cell.traffic)
    if cell.driver == "serving":
        params.update(slots=2, rate_per_s=4.0)
        traffic.update(check_sample=8, image_pool=16)
    else:
        params.update(batch=4)
        traffic.update(batch_pool=4)
    return dataclasses.replace(cell, sizes=SMOKE, params=params,
                               traffic=traffic)


def _run(name: str) -> dict:
    return bench.run_cell(_smoke_cell(name), SEED, 2.0, False,
                          t_start=time.perf_counter(),
                          devices=jax.devices())


@pytest.mark.parametrize("name", ["cifar10-serve", "cifar10-train"])
def test_smoke_run_is_correct(name):
    assert _run(name)["correct"] is True


@pytest.mark.parametrize("name", ["cifar10-serve", "cifar10-train"])
def test_a_program_without_the_category_is_caught(monkeypatch, name):
    config = program.capsnet_config

    def without(sizes):
        return dataclasses.replace(config(sizes), none_of_the_above=False)
    monkeypatch.setattr(program, "capsnet_config", without)
    assert _run(name)["correct"] is False


def test_dropping_the_category_at_published_widths_exceeds_the_limit():
    """Three images at the published widths, the same parameters: the
    lengths with and without the category differ by far more than the
    serving cell's ``len_diff`` limit."""
    limit = spec.load_cell("cifar10-serve").params["limits"]["len_diff"]
    params = NOTA.init_params(SEED, SIZES)
    images = jnp.asarray(NOTA.request_images(SEED, SIZES, 3))
    gap = float(jnp.max(jnp.abs(NOTA.lengths(params, images, SIZES)
                                - PLAIN.lengths(params, images, SIZES))))
    assert gap > 1000 * limit
