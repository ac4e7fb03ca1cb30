"""A configuration file reaches the program whole.

``program.capsnet_config`` takes every key of a configuration file that
names a field of ``CapsNetConfig``, decodes capsule-layer stacks from
objects with a ``kind``, leaves the rest at the program's defaults and
refuses any key it cannot place.  The reference and the program then
agree on every parameter's shape, since the harness hands the
reference's parameters to the program.
"""

import dataclasses
import json
import math
import os
import pathlib
import re
import shutil
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import spec  # noqa: E402
from repro.configs import capsnet_cifar10, capsnet_svhn  # noqa: E402
from repro.core import capsnet  # noqa: E402
from repro.core.capsnet import (  # noqa: E402
    CapsLayerSpec, CapsNetConfig, ResCapsBlock)

MNIST_FILE = CHIP / "configs" / "capsnet-mnist.json"
MNIST = json.loads(MNIST_FILE.read_text())
FIELDS = [f.name for f in dataclasses.fields(CapsNetConfig)]
KINDS = {CapsLayerSpec: "plain", ResCapsBlock: "residual"}
# A stack with one entry of each kind, none at its defaults.
STACK = (CapsLayerSpec(num_caps=64, caps_dim=8, routing_iters=2),
         ResCapsBlock(routing_iters=4))


def _to_json(value):
    """``value`` as a configuration file writes it."""
    if isinstance(value, tuple):
        return [_to_json(v) for v in value]
    if dataclasses.is_dataclass(value):
        return {"kind": KINDS[type(value)],
                **{f.name: _to_json(getattr(value, f.name))
                   for f in dataclasses.fields(value)}}
    return value


def _sizes(cfg: CapsNetConfig) -> dict:
    """A configuration file for ``cfg``, with capsnet-mnist's own keys."""
    own = {k: v for k, v in MNIST.items() if k in program.BENCH_KEYS}
    fields = {f: _to_json(getattr(cfg, f)) for f in FIELDS}
    return json.loads(json.dumps({**own, **fields}))


def _other(value):
    """A value of the kind of ``value`` that differs from it."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    if isinstance(value, str):
        return value + "x"
    if isinstance(value, tuple):
        return tuple(_other(v) for v in value) if value else STACK
    pytest.fail(f"no other value known for {value!r}")


def test_mnist_file_builds_the_twelve_key_config():
    assert program.capsnet_config(MNIST) == CapsNetConfig(
        image_hw=28, in_channels=1, conv1_channels=256, conv1_kernel=9,
        pc_kernel=9, pc_stride=2, num_primary_groups=32, primary_dim=8,
        num_classes=10, class_dim=16, routing_iters=3,
        decoder_hidden=(512, 1024), caps_layers=())


@pytest.mark.parametrize("make", [capsnet_svhn.config, capsnet_cifar10.config],
                         ids=["svhn", "cifar10"])
def test_stacked_configs_pass_whole(make):
    cfg = make()
    assert cfg.caps_layers
    assert program.capsnet_config(_sizes(cfg)) == cfg


@pytest.mark.parametrize("name", FIELDS)
def test_every_field_reaches_the_program(name):
    base = program.capsnet_config(MNIST)
    value = _other(getattr(CapsNetConfig(), name))
    assert value != getattr(base, name)
    got = program.capsnet_config(dict(MNIST, **{name: _to_json(value)}))
    assert got == dataclasses.replace(base, **{name: value})
    # The harness passes the field on without naming it.
    src = pathlib.Path(program.__file__).read_text()
    assert not re.search(rf"\b{name}\b", src)


@pytest.mark.parametrize("sizes, named", [
    (dict(MNIST, conv1_chanels=256), "conv1_chanels"),
    (dict(MNIST, caps_layers=[{"kind": "dense", "routing_iters": 3}]),
     "dense"),
    (dict(MNIST, caps_layers=[{"kind": "plain", "num_caps": 64,
                               "caps_dims": 8}]), "caps_dims"),
    (dict(MNIST, caps_layers=[{"routing_iters": 3}]), "kind"),
], ids=["key", "kind", "entry-key", "no-kind"])
def test_unknown_keys_are_refused_by_name(sizes, named):
    with pytest.raises(spec.SpecError, match=named):
        program.capsnet_config(sizes)


def test_bench_exits_2_on_an_unknown_key_before_the_chip(
        tmp_path, monkeypatch, capsys):
    """A cell whose configuration holds a misspelt key: ``main`` returns
    2 and names it, without looking for a chip."""
    real = spec.load_bench()
    cell = dict(next(w for w in real["workloads"]
                     if w["name"] == "mnist-serve"),
                name="typo-serve", config="capsnet-typo")
    (tmp_path / "BENCHMARK.json").write_text(
        json.dumps(dict(real, workloads=[cell])))
    here = tmp_path / "chip"
    for sub in ("configs", "traffic", "workloads"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "capsnet-typo.json").write_text(
        json.dumps(dict(MNIST, num_primary_group=32)))
    shutil.copy(CHIP / "traffic" / f"{cell['traffic']}.json", here / "traffic")
    shutil.copy(CHIP / "workloads" / "mnist-serve.json",
                here / "workloads" / "typo-serve.json")
    shutil.copy(CHIP / "serving.py", here)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "HERE", here)

    def no_chip(chips):
        raise AssertionError("looked for a chip")

    monkeypatch.setattr(bench, "tpu_devices", no_chip)
    rc = bench.main(["--workload", "typo-serve", "--seed", "1",
                     "--seconds", "1"])
    assert rc == 2
    assert "num_primary_group" in capsys.readouterr().err


@pytest.mark.parametrize("path", sorted((CHIP / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_reference_and_program_agree_on_parameters(path):
    sizes = json.loads(path.read_text())
    want = {k: tuple(v)
            for k, v in spec.reference(sizes).param_shapes(sizes).items()}
    cfg = program.capsnet_config(sizes)
    got = jax.eval_shape(lambda key: capsnet.init_params(key, cfg),
                         jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in got.items()} == want
    assert sum(math.prod(s) for s in want.values()) == sizes["parameters"]
