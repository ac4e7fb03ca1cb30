"""A run with its timed path broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU at smoke widths, with the cell's own limits, through the
program with one fault planted:

- serving: an answer altered where it is produced; half the requests'
  answers never produced;
- training: a step that returns its state unchanged; half of the batch
  left out, the loss the mean over the rest.

No cell spans chips, so no fault drops an exchange between them.  The
unbroken runs read ``correct`` true, so the faults are what fails.
"""

import copy
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
import program  # noqa: E402
import spec  # noqa: E402

SMOKE = dict(image_hw=14, conv1_channels=32, conv1_kernel=5, pc_kernel=3,
             num_primary_groups=4, primary_dim=4, class_dim=8,
             decoder_hidden=[32, 64])
SEED = 2 ** 31 + 12345


def _smoke_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    params, traffic = dict(cell.params), dict(cell.traffic)
    if cell.driver == "serving":
        params.update(slots=2, rate_per_s=4.0)
        traffic.update(check_sample=8, image_pool=16)
    else:
        params.update(batch=4)
        traffic.update(batch_pool=4)
    return dataclasses.replace(cell, sizes=dict(cell.sizes, **SMOKE),
                               params=params, traffic=traffic)


def _run(name: str) -> dict:
    return bench.run_cell(_smoke_cell(name), SEED, 2.0, False,
                          t_start=time.perf_counter(),
                          devices=jax.devices())


def _wrap_engine(monkeypatch, fault):
    make = program.make_engine

    def broken(*args, **kwargs):
        engine = make(*args, **kwargs)
        fault(engine)
        return engine
    monkeypatch.setattr(program, "make_engine", broken)


def _wrap_step(monkeypatch, fault):
    make = program.make_train_loop

    def broken(*args, **kwargs):
        loop = make(*args, **kwargs)
        loop._run_step = fault(loop._run_step)
        return loop
    monkeypatch.setattr(program, "make_train_loop", broken)


@pytest.mark.parametrize("name", ["mnist-serve", "mnist-train"])
def test_unbroken_run_is_correct(name):
    assert _run(name)["correct"] is True


def test_altered_answer_is_caught(monkeypatch):
    def fault(engine):
        forward = engine._forward

        def altered(p, x, idx):
            lengths, preds = forward(p, x, idx)
            return lengths.at[0, 0].add(1e-4), preds
        engine._forward = altered
    _wrap_engine(monkeypatch, fault)
    res = _run("mnist-serve")
    assert res["correct"] is False
    assert res["checks"]["len_diff"]["value"] > \
        res["checks"]["len_diff"]["limit"]


def test_dropped_answers_are_caught(monkeypatch):
    def fault(engine):
        submit, seen = engine.submit, []

        def half(req):
            seen.append(req)
            if len(seen) % 2 == 0 or req.rid < 0:   # warm-up goes through
                submit(req)
        engine.submit = half
    _wrap_engine(monkeypatch, fault)
    res = _run("mnist-serve")
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] > 0


def test_unchanged_state_is_caught(monkeypatch):
    def fault(step):
        def frozen(state, batch):
            _, metrics = step(copy.deepcopy(state), batch)
            return state, metrics
        return frozen
    _wrap_step(monkeypatch, fault)
    res = _run("mnist-train")
    assert res["correct"] is False
    assert res["checks"]["grad_gap"]["value"] >= 0.99


def test_half_batch_is_caught(monkeypatch):
    def fault(step):
        def half(state, batch):
            n = batch["labels"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _wrap_step(monkeypatch, fault)
    res = _run("mnist-train")
    assert res["correct"] is False
    assert res["checks"]["loss_rel"]["value"] > \
        res["checks"]["loss_rel"]["limit"]


def test_command_fails_without_a_tpu(capsys):
    assert bench.main(["--workload", "mnist-serve", "--seed", "1",
                       "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
