"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program
(``src/repro``): the serving engine and the training loop through their
normal entry points, built from a configuration's sizes.  Parameters
and inputs are the benchmark's own, made from the seed.
"""

from __future__ import annotations

import pathlib
import sys
import tempfile

SRC = pathlib.Path(__file__).resolve().parents[2] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.core.capsnet import CapsNetConfig  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.capsule import CapsRequest, CapsuleEngine  # noqa: E402
from repro.train.capsnet_loop import CapsLoopConfig, CapsTrainLoop  # noqa: E402
from repro.train.optimizer import init_opt_state  # noqa: E402

__all__ = ["CapsRequest", "enable_compile_cache", "capsnet_config",
           "make_engine", "make_train_loop", "init_opt_state"]


def capsnet_config(s: dict) -> CapsNetConfig:
    """The program's configuration of a CapsNet with no residual capsule
    blocks (the reference has none)."""
    if s["caps_layers"]:
        raise ValueError("residual capsule blocks are not benchmarked")
    return CapsNetConfig(
        image_hw=s["image_hw"], in_channels=s["in_channels"],
        conv1_channels=s["conv1_channels"], conv1_kernel=s["conv1_kernel"],
        pc_kernel=s["pc_kernel"], pc_stride=s["pc_stride"],
        num_primary_groups=s["num_primary_groups"],
        primary_dim=s["primary_dim"], num_classes=s["num_classes"],
        class_dim=s["class_dim"], routing_iters=s["routing_iters"],
        decoder_hidden=tuple(s["decoder_hidden"]), caps_layers=())


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
