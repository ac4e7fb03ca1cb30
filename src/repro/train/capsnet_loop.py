"""Fault-tolerant CapsuleNet training through the Pallas backend.

The custom VJPs (``kernels/conv_im2col``, ``kernels/votes_routing``,
``kernels/primary_routing``, ``kernels/squash``) make
``backend="pallas"`` differentiable end to end, so the margin-loss +
masked-reconstruction objective trains through the SAME plan-driven
kernels that serve inference -- with the backward schedule pinned by
``compile_plan(train=True)`` (backward OpPlans: per-mode VMEM
footprints, ``u_hat``/``d u_hat`` never in HBM).  The forward side of
the training plan is PIPELINED: PrimaryCaps epilogue streams into the
routing megakernel when the combined footprint fits, per-op fallback
otherwise.

Two optimizers:

  * ``sgd`` (default): plain SGD at a fixed ``lr`` -- the original CI
    smoke configuration, checkpoint state is params-only;
  * ``adam``: AdamW + warmup/cosine decay from ``train.optimizer``
    (``decay_steps`` pinned to the run horizon), checkpoint state gains
    the m/v/step optimizer tree.

The checkpoint/NaN-guard/heartbeat skeleton is
``train.harness.FaultTolerantLoop`` -- shared with the LM loop.

CLI:  python -m repro.train.capsnet_loop --steps 20 --backend pallas \
          --optimizer adam
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.core import capsnet, spans
from repro.core.capsnet import CapsNetConfig
from repro.core.compile_cache import enable_compile_cache
from repro.core.execplan import compile_plan
from repro.train.data import DataConfig, mnist_batch
from repro.train.harness import FaultTolerantLoop
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state

SMOKE = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                      pc_kernel=3, num_primary_groups=4, primary_dim=4,
                      class_dim=8, decoder_hidden=(32, 64))
CONFIGS = {"smoke": SMOKE, "mnist": CapsNetConfig()}


@dataclasses.dataclass
class CapsLoopConfig:
    total_steps: int = 20
    batch: int = 16
    lr: float = 3e-2
    optimizer: str = "sgd"            # "sgd" | "adam"
    warmup_steps: int = 2             # adam only
    weight_decay: float = 0.0         # adam only
    ckpt_every: int = 10
    ckpt_dir: str = "caps_checkpoints"
    keep: int = 3
    log_every: int = 5
    backend: str = "pallas"
    interpret: bool | None = None     # None: compile on a TPU, else interpret
    max_nan_skips: int = 5            # bounds CONSECUTIVE non-finite steps
    straggler_factor: float | None = None   # step-time multiple that flags
    heartbeat_path: str | None = None
    seed: int = 0


class CapsTrainLoop(FaultTolerantLoop):
    """SGD/AdamW over ``capsnet.total_loss`` with checkpoint + NaN-guard."""

    def __init__(self, cfg: CapsNetConfig = SMOKE,
                 loop_cfg: CapsLoopConfig = CapsLoopConfig(),
                 on_straggler=None):
        if loop_cfg.optimizer not in ("sgd", "adam"):
            raise ValueError(f"unknown optimizer {loop_cfg.optimizer!r}")
        super().__init__(loop_cfg, on_straggler=on_straggler)
        self.cfg = cfg
        self.data_cfg = DataConfig(kind="mnist",
                                   global_batch=loop_cfg.batch,
                                   seed=loop_cfg.seed)
        # ONE training plan: pins both the forward schedule and the
        # backward OpPlans the custom VJPs execute.  Pipelined: the
        # forward runs the PrimaryCaps->ClassCaps pair as one kernel
        # when it fits; the backward OpPlans are per-op either way.
        self.plan = (compile_plan(cfg, batch=loop_cfg.batch, train=True,
                                  pipeline=True)
                     if loop_cfg.backend == "pallas" else None)
        self.opt_cfg = (OptConfig(peak_lr=loop_cfg.lr,
                                  warmup_steps=loop_cfg.warmup_steps,
                                  decay_steps=loop_cfg.total_steps,
                                  weight_decay=loop_cfg.weight_decay)
                        if loop_cfg.optimizer == "adam" else None)

        def loss_and_grads(params, images, labels):
            return jax.value_and_grad(capsnet.total_loss, has_aux=True)(
                params, images, labels, cfg,
                backend=loop_cfg.backend, plan=self.plan,
                interpret=loop_cfg.interpret)

        if loop_cfg.optimizer == "adam":
            def step_fn(params, opt, images, labels):
                (_, metrics), grads = loss_and_grads(params, images, labels)
                params, opt, opt_m = adamw_update(params, grads, opt,
                                                  self.opt_cfg)
                return params, opt, {**metrics, **opt_m}

            self._step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        else:
            def step_fn(params, images, labels):
                (_, metrics), grads = loss_and_grads(params, images, labels)
                params = jax.tree_util.tree_map(
                    lambda p, g: p - loop_cfg.lr * g, params, grads)
                return params, metrics

            self._step_fn = jax.jit(step_fn, donate_argnums=(0,))

    # -- state ----------------------------------------------------------------
    def init_params(self):
        return capsnet.init_params(jax.random.PRNGKey(self.loop_cfg.seed),
                                   self.cfg)

    def try_restore(self, params):
        state = {"params": params}
        if self.opt_cfg is not None:
            state["opt"] = init_opt_state(params)
        state, start = self._try_restore(state)
        return state["params"], start

    # -- harness hooks ---------------------------------------------------------
    def _init_state(self) -> dict:
        params = self.init_params()
        if self.opt_cfg is not None:
            return {"params": params, "opt": init_opt_state(params)}
        return {"params": params}

    def _ckpt_extra(self) -> dict:
        return {"backend": self.loop_cfg.backend,
                "optimizer": self.loop_cfg.optimizer}

    def _next_batch(self, step: int) -> dict:
        return self._batch(step)

    def _batch(self, step: int) -> dict:
        return mnist_batch(self.data_cfg, step,
                           image_hw=self.cfg.image_hw,
                           channels=self.cfg.in_channels)

    def _run_step(self, state: dict, batch) -> tuple[dict, dict]:
        with spans.span("caps.train.dispatch"):
            if "opt" in state:
                params, opt, metrics = self._step_fn(
                    state["params"], state["opt"],
                    batch["images"], batch["labels"])
                return {"params": params, "opt": opt}, metrics
            params, metrics = self._step_fn(state["params"], batch["images"],
                                            batch["labels"])
            return {"params": params}, metrics

    def _extra_record(self, metrics: dict) -> dict:
        rec = {"accuracy": float(jax.device_get(metrics["accuracy"]))}
        if "lr" in metrics:
            rec["lr"] = float(jax.device_get(metrics["lr"]))
        return rec

    def _log_line(self, rec: dict) -> str:
        return (f"step {rec['step']:6d} loss {rec['loss']:9.4f} "
                f"acc {rec['accuracy']:5.2f} {rec['time_s'] * 1e3:7.1f} ms")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--optimizer", choices=("sgd", "adam"), default="sgd",
                    help="sgd: fixed-lr SGD (default); adam: AdamW + "
                         "warmup/cosine from train.optimizer")
    ap.add_argument("--backend", choices=("jnp", "pallas"),
                    default="pallas")
    ap.add_argument("--config", choices=sorted(CONFIGS), default="smoke")
    ap.add_argument("--arch", default=None,
                    help="registry architecture id (e.g. capsnet_mnist, "
                         "capsnet_cifar10, capsnet_svhn); overrides "
                         "--config.  Deep-stack archs train through the "
                         "per-layer graph plan + reversible backward.")
    ap.add_argument("--smoke", action="store_true",
                    help="with --arch: use the arch's smoke_config() "
                         "(toy widths, same topology)")
    ap.add_argument("--ckpt-dir", default="caps_checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--assert-improves", action="store_true",
                    help="exit nonzero unless the loss decreased and no "
                         "NaN-guard rollback fired (the CI smoke gate)")
    args = ap.parse_args(argv)

    if args.arch is not None:
        from repro.configs import registry
        cfg = (registry.get_smoke_config(args.arch) if args.smoke
               else registry.get_config(args.arch))
        if not isinstance(cfg, CapsNetConfig):
            ap.error(f"--arch {args.arch} is not a CapsuleNet workload "
                     f"(CapsuleNet archs: {registry.CAPSNET_ARCHS})")
    else:
        cfg = CONFIGS[args.config]
    enable_compile_cache()
    loop = CapsTrainLoop(cfg, CapsLoopConfig(
        total_steps=args.steps, batch=args.batch, lr=args.lr,
        optimizer=args.optimizer, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, backend=args.backend))
    hist = loop.run(resume=not args.no_resume)
    if not hist:
        print("nothing to do (already at the requested step)")
        return 0
    first = np.mean([h["loss"] for h in hist[:3]])
    last = np.mean([h["loss"] for h in hist[-3:]])
    print(f"loss {first:.4f} -> {last:.4f} over {len(hist)} steps "
          f"({loop.nan_skips} NaN-guard rollbacks)")
    if args.assert_improves and (last >= first or loop.nan_skips > 0):
        print("FAIL: loss did not decrease (or a NaN rollback fired)")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
