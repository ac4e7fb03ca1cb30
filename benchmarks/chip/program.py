"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program
(``src/repro``): the serving engine and the training loop through their
normal entry points, built from a configuration's sizes.  Parameters
and inputs are the benchmark's own, made from the seed.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys
import tempfile

SRC = pathlib.Path(__file__).resolve().parents[2] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import spec  # noqa: E402
from repro.core.capsnet import (  # noqa: E402
    CapsLayerSpec, CapsNetConfig, ResCapsBlock)
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.capsule import CapsRequest, CapsuleEngine  # noqa: E402
from repro.train.capsnet_loop import CapsLoopConfig, CapsTrainLoop  # noqa: E402
from repro.train.optimizer import init_opt_state  # noqa: E402

__all__ = ["CapsRequest", "enable_compile_cache", "capsnet_config",
           "make_engine", "make_train_loop", "init_opt_state"]


# The configuration file's own keys, which the program does not take:
# the reference's family, the source, the stated precision (``dtype`` is
# stated and not read; ``bench.py`` runs the program under
# ``matmul_precision``), the parameter count, and the cuts from the
# source and the sizes assumed (``reduced``, ``assumed``).
BENCH_KEYS = ("family", "source", "dtype", "matmul_precision", "parameters",
              "reduced", "assumed")

# An entry of a capsule-layer stack is a JSON object whose ``kind`` names
# its class; its other keys are that class's fields.
LAYER_KINDS = {"plain": CapsLayerSpec, "residual": ResCapsBlock}


def _refuse_unknown(keys, cls, also: tuple, where: str) -> None:
    known = [f.name for f in dataclasses.fields(cls)] + list(also)
    unknown = sorted(set(keys) - set(known))
    if unknown:
        raise spec.SpecError(f"{where}: unknown key(s) {', '.join(unknown)}"
                             f" (known: {', '.join(known)})")


def _decode(value, where: str):
    """A JSON value as the program takes it: lists as tuples, objects as
    the capsule-layer entries their ``kind`` names."""
    if isinstance(value, list):
        return tuple(_decode(v, f"{where}[{i}]") for i, v in enumerate(value))
    if not isinstance(value, dict):
        return value
    fields = dict(value)
    kind = fields.pop("kind", None)
    if kind not in LAYER_KINDS:
        raise spec.SpecError(f"{where}: unknown kind {kind!r} (known: "
                             f"{', '.join(LAYER_KINDS)})")
    _refuse_unknown(fields, LAYER_KINDS[kind], (), f"{where} ({kind})")
    return LAYER_KINDS[kind](
        **{k: _decode(v, f"{where}.{k}") for k, v in fields.items()})


def capsnet_config(s: dict) -> CapsNetConfig:
    """The program's configuration from a configuration file's sizes:
    every key that names a field of ``CapsNetConfig``, the rest at the
    program's defaults.  A key that is neither a field nor one of
    ``BENCH_KEYS`` is refused, so that a misspelt size never runs as a
    different model."""
    _refuse_unknown(s, CapsNetConfig, BENCH_KEYS, "configuration")
    return CapsNetConfig(**{k: _decode(v, k) for k, v in s.items()
                            if k not in BENCH_KEYS})


def make_engine(params, sizes: dict, slots: int) -> CapsuleEngine:
    """The serving engine on the Pallas backend with its default
    (pipelined where it fits) plan."""
    return CapsuleEngine(params, capsnet_config(sizes), slots=slots,
                         backend="pallas")


def make_train_loop(sizes: dict, batch: int, opt: dict) -> CapsTrainLoop:
    """AdamW through ``CapsTrainLoop`` on the Pallas backend.  The
    benchmark calls its step (``_run_step``) itself, so no checkpoint is
    written; the checkpoint directory is never created."""
    if opt["b1"] != 0.9 or opt["b2"] != 0.95 or opt["eps"] != 1e-8 \
            or opt["clip_norm"] != 1.0 or opt["min_lr_ratio"] != 0.1:
        raise ValueError("the training loop's AdamW fixes b1=0.9, b2=0.95, "
                         "eps=1e-8, clip_norm=1.0, min_lr_ratio=0.1")
    return CapsTrainLoop(capsnet_config(sizes), CapsLoopConfig(
        total_steps=opt["decay_steps"], batch=batch, lr=opt["lr"],
        optimizer="adam", warmup_steps=opt["warmup_steps"],
        weight_decay=opt["weight_decay"], backend="pallas",
        ckpt_dir=str(pathlib.Path(tempfile.gettempdir()) / "chipbench_ckpt")))
