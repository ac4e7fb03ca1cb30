"""``limits.py`` puts the control, and the faults a driver plants, through
the cell's own limits: each reading carries ``correct``, and they come
out as not correct.  Run on the CPU with the chip look skipped: serving
at half of capsnet-mnist's channels and groups, where the exact
three-pass emulation already fails the limit; training at smoke widths,
where half of each batch left out fails it."""

import dataclasses
import json
import os
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import limits  # noqa: E402
import spec  # noqa: E402

HALF = dict(conv1_channels=128, num_primary_groups=16)
SMOKE = dict(image_hw=14, conv1_channels=32, conv1_kernel=5, pc_kernel=3,
             num_primary_groups=4, primary_dim=4, class_dim=8,
             decoder_hidden=[32, 64])


def _small(cell):
    if cell.driver == "serving":
        return dataclasses.replace(
            cell, sizes=dict(cell.sizes, **HALF),
            traffic=dict(cell.traffic, check_sample=64, image_pool=64))
    return dataclasses.replace(
        cell, sizes=dict(cell.sizes, **SMOKE),
        params=dict(cell.params, batch=4),
        traffic=dict(cell.traffic, batch_pool=4))


def _limits(monkeypatch, capsys, name, seconds):
    load = spec.load_cell
    monkeypatch.setattr(spec, "load_cell", lambda n: _small(load(n)))
    monkeypatch.setattr(bench, "tpu_devices", lambda chips: jax.devices())
    assert limits.main(["--workload", name, "--control-seeds", "1",
                        "--seconds", str(seconds)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_serving_control_is_judged_not_correct(monkeypatch, capsys):
    rate = spec.load_cell("mnist-serve").params["rate_per_s"]
    out = _limits(monkeypatch, capsys, "mnist-serve", 64 / rate)
    got = out["control"]["1"]
    assert got["correct"] is False
    assert got["len_diff"] > spec.load_cell(
        "mnist-serve").params["limits"]["len_diff"]


def test_training_planted_fault_is_judged_not_correct(monkeypatch, capsys):
    out = _limits(monkeypatch, capsys, "mnist-train", 1.0)
    assert out["half_batch"]["1"]["correct"] is False
    assert set(out["control"]["1"]) >= {"correct", "loss_rel", "grad_gap",
                                        "step_gap_med"}


def test_a_traffic_file_names_a_driver_module(monkeypatch):
    for w in spec.load_bench()["workloads"]:
        mod = spec.driver(spec.load_cell(w["name"]))
        assert callable(mod.run) and callable(mod.control)
    read = spec._read_json
    monkeypatch.setattr(spec, "_read_json", lambda path: (
        dict(read(path), driver="no_such_driver")
        if path.parent.name == "traffic" else read(path)))
    with pytest.raises(spec.SpecError, match="no_such_driver"):
        spec.load_cell("mnist-serve")
