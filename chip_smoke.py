"""Chip smoke: CapsNet-MNIST serving and training on a TPU through the
normal entry points, with every Pallas kernel compiled by Mosaic.

    python chip_smoke.py              # one chip: serve + train phases
    python chip_smoke.py --chips 4    # four chips: sharded serving only

The serve phase runs ``CapsuleEngine(backend="pallas")`` on its default
(pipelined) plan at capsnet-mnist's published widths and checks every
request's capsule lengths against the jnp reference at "highest" matmul
precision; before it, the patches phase checks that ``im2col_patches``
equals the plain extraction bit for bit on the chip at capsnet-mnist's
Conv1 and PrimaryCaps shapes.  The train phase takes ``CapsTrainLoop``
steps with SGD and checks the first loss against the same reference.
The wide-layer phase runs one capsnet-cifar10 ResCaps half at published
widths (1024 capsules routed into 1024 x 8D) on its train plan's
schedule -- the routing kernels with the output capsules on the lanes --
forward and gradient against the jnp reference.  ``--chips 4`` runs
only the sharded engine (``n_shards=4``) against a one-device engine on
the same requests.  Params and images are random, made from ``--seed``.

The script runs only where JAX's default device is a TPU, and starts no
other process.  Any failed check exits nonzero before the result line;
the last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".smoke_ckpt"        # listed in .gitignore

SERVE_SLOTS = 8
SERVE_REQUESTS = 32
SHARDS = 4
SHARDED_SLOTS = 32
SHARDED_REQUESTS = 64
TRAIN_STEPS = 5
TRAIN_BATCH = 16
# Capsule lengths lie in [0, 1); fp32 kernels against the fp32 reference
# differ by summation order only (about 5e-7 on a v5e), far below the
# error of a matmul at the default (bf16-pass) precision.
LENGTH_ATOL = 1e-5
LOSS_RTOL = 1e-5
# Wide-layer outputs are squashed vectors (|v| < 1); gradients are held
# relative to their largest reference entry.
WIDE_ATOL = 1e-5
WIDE_GRAD_RTOL = 1e-4


class SmokeFailure(Exception):
    """A smoke check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_device_info(count: int) -> dict:
    """The device record of the result line; fails unless JAX's default
    devices are at least ``count`` TPUs."""
    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU found: JAX's default device platform is "
          f"{devices[0].platform!r}; this smoke runs only on a TPU")
    check(len(devices) >= count,
          f"{count} TPU chips needed, JAX sees {len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def reference_lengths(params, images, cfg):
    """The jnp reference's capsule lengths at full fp32 matmul precision."""
    import jax
    from repro.core import capsnet
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: capsnet.forward(p, x, cfg)["lengths"])(
            params, images)
    return jax.device_get(out)


def compare_lengths(results: dict, ref) -> float:
    """Max |lengths - reference| over all requests; predictions must match
    wherever the reference's top two lengths differ by more than the
    tolerance (random params make near-ties)."""
    import numpy as np
    worst = 0.0
    for rid, (lengths, pred) in results.items():
        want = np.asarray(ref[rid])
        worst = max(worst, float(np.max(np.abs(lengths - want))))
        top2 = np.sort(want)[-2:]
        if top2[1] - top2[0] > LENGTH_ATOL:
            check(pred == int(np.argmax(want)),
                  f"request {rid}: predicted {pred}, reference "
                  f"{int(np.argmax(want))}")
    check(worst <= LENGTH_ATOL,
          f"capsule lengths differ from the reference by {worst:.3g} "
          f"(tolerance {LENGTH_ATOL})")
    return worst


def serve_requests(engine, images, n: int) -> tuple[dict, float, float]:
    """Submit ``n`` requests, run the engine to completion, and check the
    serving counters.  Returns ``{rid: (lengths, pred)}``, the first
    tick's seconds (compile included) and the remaining run's seconds."""
    import numpy as np
    from repro.serve.capsule import CapsRequest
    for i in range(n):
        engine.submit(CapsRequest(rid=i, image=np.asarray(images[i])))
    t0 = time.perf_counter()
    engine.step()
    t1 = time.perf_counter()
    done = engine.run()
    t2 = time.perf_counter()
    stats = engine.stats()
    check(len(done) == n and all(r.status == "ok" for r in done),
          f"not every request ok: "
          f"{sorted({r.status for r in done})} over {len(done)}/{n}")
    check(stats["breaker_trips"] == 0 and stats["forward_failures"] == 0,
          f"engine fell back: {stats['breaker_trips']} breaker trips, "
          f"{stats['forward_failures']} forward failures")
    check(not stats["degraded"], "engine degraded")
    terminals = sum(sh[s] for sh in stats["per_shard"]
                    for s in ("ok", "timeout", "error", "shed"))
    terminals += sum(stats["queue_bucket"].values())
    check(terminals == stats["submitted"] == n,
          f"per-shard terminal counters sum to {terminals}, "
          f"submitted {stats['submitted']}")
    return ({r.rid: (np.asarray(r.lengths), r.pred) for r in done},
            t1 - t0, t2 - t1)


def serve_phase(cfg, params, images, *, slots: int = SERVE_SLOTS,
                requests: int = SERVE_REQUESTS) -> float:
    """CapsuleEngine on the pipelined Pallas plan vs the jnp reference."""
    from repro.serve.capsule import CapsuleEngine
    engine = CapsuleEngine(params, cfg, slots=slots, backend="pallas")
    plan = engine.plan
    for op in plan.ops:
        log(f"serve plan op {op.name}: kernel={op.kernel} mode={op.mode} "
            f"block_i={op.block_i} block_k={op.block_k} "
            f"vmem={op.vmem_bytes} B")
    check(any(op.kernel == "primary_routing" for op in plan.ops),
          "the serve plan does not run the pipelined primary_routing pair")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        results, first_s, rest_s = serve_requests(engine, images, requests)
    check_no_kernel_warnings(caught)
    log(f"serve first tick {first_s:.3f} s (compile included), "
        f"{requests - slots} more requests {rest_s:.3f} s")
    worst = compare_lengths(results, reference_lengths(params, images, cfg))
    log(f"serve max |lengths - reference| = {worst:.3e}")
    return worst


def patches_phase(cfg, seed: int, batch: int = TRAIN_BATCH) -> None:
    """``im2col_patches`` vs ``_patches_xla``, bit for bit, at Conv1's and
    PrimaryCaps' input shapes: each channel width's formulation as XLA
    compiles it for the chip."""
    import jax
    import numpy as np
    from repro.kernels.conv_im2col import _patches_xla, im2col_patches
    c1 = cfg.conv1_out
    layers = {"Conv1": ((batch, cfg.image_hw, cfg.image_hw,
                         cfg.in_channels), cfg.conv1_kernel, 1),
              "PrimaryCaps": ((batch, c1, c1, cfg.conv1_channels),
                              cfg.pc_kernel, cfg.pc_stride)}
    key = jax.random.PRNGKey(seed)
    for name, (shape, k, stride) in layers.items():
        x = jax.random.normal(key, shape)
        got, want = jax.device_get((
            im2col_patches(x, kh=k, kw=k, stride=stride),
            jax.jit(_patches_xla, static_argnums=(1, 2, 3))(x, k, k, stride)))
        same = got.shape == want.shape and np.array_equal(
            got.view(np.uint32), want.view(np.uint32))
        check(same, f"{name} patches {shape} k{k} s{stride} differ from the "
                    f"plain extraction on the chip")
        log(f"patches {name} {shape} k{k} s{stride}: bitwise equal")


def check_no_kernel_warnings(caught) -> None:
    """Fail on the wrappers' fallback warning: a backward run on the
    forward schedule, at a VMEM footprint no plan validated."""
    bad = [w for w in caught if issubclass(w.category, RuntimeWarning)
           and "no feasible" in str(w.message)]
    check(not bad, f"kernel RuntimeWarning: {bad[0].message if bad else ''}")


def train_batch(cfg, want: int) -> int:
    """``want``, or the largest batch below it the train plan admits."""
    from repro.core.execplan import PlanError, compile_plan
    batch = want
    while batch > 1:
        try:
            compile_plan(cfg, batch=batch, train=True, pipeline=True)
            return batch
        except PlanError:
            batch -= 1
    return batch


def train_phase(cfg, seed: int, *, steps: int = TRAIN_STEPS,
                want_batch: int = TRAIN_BATCH) -> float:
    """CapsTrainLoop (SGD, Pallas backend); first loss vs the reference."""
    import jax
    import numpy as np
    from repro.core import capsnet
    from repro.train.capsnet_loop import CapsLoopConfig, CapsTrainLoop
    from repro.train.data import mnist_batch
    batch = train_batch(cfg, want_batch)
    log(f"train batch {batch} (wanted {want_batch})")
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    loop = CapsTrainLoop(cfg, CapsLoopConfig(
        total_steps=steps, batch=batch, optimizer="sgd", ckpt_every=steps,
        ckpt_dir=str(CKPT_DIR), log_every=1, backend="pallas", seed=seed))
    for op in loop.plan.ops:
        log(f"train plan op {op.name}: kernel={op.kernel} mode={op.mode} "
            f"block_i={op.block_i} vmem={op.vmem_bytes} B")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        hist = loop.run(resume=False)
    check_no_kernel_warnings(caught)
    losses = [h["loss"] for h in hist]
    check(len(hist) == steps and bool(np.all(np.isfinite(losses))),
          f"train steps {len(hist)}/{steps}, losses {losses}")
    check(loop.nan_skips == 0, f"{loop.nan_skips} NaN rollbacks")
    log(f"train first step {hist[0]['time_s']:.3f} s (compile included), "
        f"step times {[round(h['time_s'], 4) for h in hist[1:]]} s")
    b0 = mnist_batch(loop.data_cfg, 0, image_hw=cfg.image_hw,
                     channels=cfg.in_channels)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x, y: capsnet.total_loss(p, x, y, cfg)[0])(
            loop.init_params(), b0["images"], b0["labels"])
    ref = float(ref)
    rel = abs(losses[0] - ref) / max(abs(ref), 1e-12)
    log(f"train losses {losses}; first loss {losses[0]:.6f} vs reference "
        f"{ref:.6f} (rel diff {rel:.3e})")
    check(rel <= LOSS_RTOL,
          f"first loss {losses[0]} vs reference {ref}: rel diff {rel:.3g} "
          f"over {LOSS_RTOL}")
    return rel


def wide_layer_phase(seed: int, *, cfg=None,
                     vmem_budget: int | None = None) -> tuple[float, float]:
    """One ResCaps half of capsnet-cifar10 through ``ops.votes_routing``
    on its train plan's schedule (output capsules on the lanes), forward
    and input/weight gradients vs the jnp reference at "highest" matmul
    precision.  Returns (max |v diff|, max relative gradient diff)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import capsnet_cifar10
    from repro.core import capsnet
    from repro.core.execplan import VMEM_BYTES, compile_plan
    from repro.kernels import ops
    cfg = cfg or capsnet_cifar10.config()
    lay = cfg.routing_stack()[0]
    plan = compile_plan(cfg, batch=1, train=True,
                        vmem_budget=vmem_budget or VMEM_BYTES)
    fwd_op = plan.op(lay.name)
    log(f"wide layer {lay.name}: {lay.in_caps} x {lay.in_dim}D -> "
        f"{lay.num_caps} x {lay.caps_dim}D, lanes={fwd_op.lanes} "
        f"mode={fwd_op.mode} block_i={fwd_op.block_i}")
    check(fwd_op.lanes == "classes",
          f"{lay.name} did not plan the classes-on-lanes layout")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = capsnet.squash(jax.random.normal(k1, (1, lay.in_caps, lay.in_dim)))
    w = 0.05 * jax.random.normal(k2, (lay.in_caps, lay.jd, lay.in_dim))
    dv = jax.random.normal(k3, (1, lay.jd))

    def fused(u, w):
        return ops.votes_routing(u, w, lay, plan)

    def ref(u, w):
        uh = jnp.einsum("bic,inc->bin", u, w)
        return capsnet.routing_by_agreement(
            uh.reshape(1, lay.in_caps, lay.num_caps, lay.caps_dim),
            lay.iters).reshape(1, lay.jd)

    def run(fn):
        out, pull = jax.vjp(fn, u, w)
        return jax.device_get((out,) + pull(dv))

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        got = run(jax.jit(fused))
        t1 = time.perf_counter()
    check_no_kernel_warnings(caught)
    with jax.default_matmul_precision("highest"):
        want = run(jax.jit(ref))
    v_diff = float(np.max(np.abs(got[0] - want[0])))
    g_rel = max(float(np.max(np.abs(g - r)) / max(np.max(np.abs(r)), 1e-30))
                for g, r in zip(got[1:], want[1:]))
    log(f"wide layer fwd+grad {t1 - t0:.3f} s (compile included); max "
        f"|v - reference| = {v_diff:.3e}, max relative grad diff = "
        f"{g_rel:.3e}")
    check(bool(np.all(np.isfinite(got[0]))), "wide layer output not finite")
    check(v_diff <= WIDE_ATOL,
          f"wide layer output differs by {v_diff:.3g} (tolerance "
          f"{WIDE_ATOL})")
    check(g_rel <= WIDE_GRAD_RTOL,
          f"wide layer gradients differ by {g_rel:.3g} relative "
          f"(tolerance {WIDE_GRAD_RTOL})")
    return v_diff, g_rel


def sharded_phase(cfg, params, images) -> float:
    """n_shards=4 engine vs a one-device engine on the same requests."""
    import numpy as np
    from repro.serve.capsule import CapsuleEngine
    sharded = CapsuleEngine(params, cfg, slots=SHARDED_SLOTS,
                            backend="pallas", n_shards=SHARDS)
    single = CapsuleEngine(params, cfg, slots=SERVE_SLOTS, backend="pallas")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got, s_first, s_rest = serve_requests(sharded, images,
                                              SHARDED_REQUESTS)
        want, o_first, o_rest = serve_requests(single, images,
                                               SHARDED_REQUESTS)
    check_no_kernel_warnings(caught)
    log(f"sharded first tick {s_first:.3f} s, rest {s_rest:.3f} s; "
        f"one-device first tick {o_first:.3f} s, rest {o_rest:.3f} s")
    for rid, (lengths, pred) in got.items():
        check(pred == want[rid][1],
              f"request {rid}: sharded pred {pred}, one-device "
              f"{want[rid][1]}")
    worst = max(float(np.max(np.abs(lengths - want[rid][0])))
                for rid, (lengths, _) in got.items())
    check(worst <= LENGTH_ATOL,
          f"sharded lengths differ from one device by {worst:.3g}")
    log(f"sharded vs one-device max |lengths diff| = {worst:.3e}; "
        f"per-shard ok {[sh['ok'] for sh in sharded.stats()['per_shard']]}")
    return worst


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDS), default=1,
                    help="1: serve + train on one chip; 4: sharded "
                         "serving against one device, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = tpu_device_info(args.chips)
        sys.path.insert(0, str(ROOT / "src"))
        try:
            import jax
            from repro.configs import capsnet_mnist
            from repro.core import capsnet
            from repro.core.compile_cache import enable_compile_cache
            from repro.kernels import ops
            from repro.train.data import DataConfig, mnist_batch
            import repro.serve.capsule  # noqa: F401
            import repro.train.capsnet_loop  # noqa: F401
        except ImportError as err:
            raise SmokeFailure(f"the repro package is not beside "
                               f"chip_smoke.py ({err})") from None
        check("repro.launch.dryrun" not in sys.modules,
              "the serve/train path imported repro.launch.dryrun, which "
              "forces host devices through XLA_FLAGS")
        check(not ops.should_interpret(),
              "Pallas kernels would run in interpret mode")
        log(f"device {device['kind']} x{device['count']}, compile cache "
            f"{enable_compile_cache()}")
        cfg = capsnet_mnist.config()
        params = capsnet.init_params(jax.random.PRNGKey(args.seed), cfg)
        n = SHARDED_REQUESTS if args.chips == SHARDS else SERVE_REQUESTS
        images = mnist_batch(DataConfig(kind="mnist", global_batch=n,
                                        seed=args.seed), 0,
                             image_hw=cfg.image_hw)["images"]
        if args.chips == SHARDS:
            sharded_phase(cfg, params, images)
        else:
            patches_phase(cfg, args.seed)
            serve_phase(cfg, params, images)
            train_phase(cfg, args.seed)
            wide_layer_phase(args.seed)
    except SmokeFailure as err:
        print(f"chip_smoke: FAIL: {err}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
