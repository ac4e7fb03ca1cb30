"""Serve CapsuleNet classifications through the plan-driven batched engine.

Compiles ONE ExecutionPlan for the configured CapsNet, prints its
per-operation schedule (block shapes, VMEM footprints, PMU phases), then
streams MNIST-like requests through the slot-based ``CapsuleEngine`` and
reports per-request latency and throughput.

    PYTHONPATH=src python examples/serve_capsnet.py [--backend pallas]

``--shards N`` shards the slot batch over an N-device mesh (ONE
compile_plan producing the per-shard plan, ``slots = n_shards *
plan.batch``); on a CPU-only machine force virtual devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/serve_capsnet.py --shards 4

``--use-async`` drives the same engine through ``AsyncCapsuleServer``:
requests are submitted concurrently from asyncio tasks and each awaits
its own terminal status while the driver recycles slots continuously.
"""

import argparse
import asyncio
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import capsnet  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.energy import SRAMConfig  # noqa: E402
from repro.core.execplan import compile_plan  # noqa: E402
from repro.core.pmu import schedule_from_plan  # noqa: E402
from repro.serve.capsule import (AsyncCapsuleServer, CapsRequest,  # noqa: E402
                                 CapsuleEngine)
from repro.train.data import DataConfig, mnist_batch  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard the slot batch over this many devices "
                         "(slots must divide; needs that many visible "
                         "devices)")
    ap.add_argument("--use-async", action="store_true",
                    help="submit through the asyncio host loop")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = capsnet.CapsNetConfig(image_hw=14, conv1_channels=16,
                                conv1_kernel=5, pc_kernel=3,
                                num_primary_groups=4, primary_dim=4,
                                class_dim=8, use_decoder=False)
    params = capsnet.init_params(jax.random.PRNGKey(0), cfg)
    # pipeline=True: PrimaryCaps -> ClassCaps served as ONE fused
    # kernel when the pair fits VMEM (per-op plan otherwise).  Sharded,
    # the plan is compiled for the PER-SHARD batch: slots = shards *
    # plan.batch, and each shard runs the same schedule.
    per_shard = args.slots // (args.shards or 1)
    plan = compile_plan(cfg, batch=per_shard, pipeline=True)

    print("== ExecutionPlan (one schedule: kernels + PMU + serving) ==")
    print(f"{'op':14s} {'kernel':18s} {'block':>18s} {'vmem KiB':>9s} "
          f"{'phase KiB':>10s}")
    for r in plan.summary():
        print(f"{r['name']:14s} {r['kernel']:18s} {str(r['block']):>18s} "
              f"{r['vmem_kib']:9.1f} {r['req_kib']:10.1f}")

    mem = SRAMConfig("shared", 1 << 20, power_gated=True, sectors_per_bank=64)
    sched = schedule_from_plan(mem, plan)
    print("\n== PMU gating schedule derived from the SAME plan ==")
    for ph in sched.phases:
        print(f"{ph.name:14s} on={ph.on_fraction:5.1%} "
              f"woken={ph.sectors_woken:3d} leak={ph.leakage_mj:.4f} mJ")

    engine = CapsuleEngine(params, cfg, slots=args.slots,
                           backend=args.backend, plan=plan,
                           n_shards=args.shards)
    dc = DataConfig(kind="mnist", global_batch=args.requests)
    batch = mnist_batch(dc, 0, image_hw=cfg.image_hw)
    images = np.asarray(batch["images"])
    if args.use_async:
        async def serve_async():
            async with AsyncCapsuleServer(engine) as server:
                return await asyncio.gather(
                    *(server.submit(images[i % images.shape[0]])
                      for i in range(args.requests)))

        done = asyncio.run(serve_async())
    else:
        for i in range(args.requests):
            engine.submit(CapsRequest(rid=i,
                                      image=images[i % images.shape[0]]))
        done = engine.run()
    s = engine.stats()

    mesh_note = (f", {engine.n_shards} shards x {engine.slots_per_shard} "
                 f"slots/shard" if args.shards else "")
    print(f"\n== served {s['requests']} requests "
          f"({args.backend} backend, {args.slots} slots{mesh_note}"
          f"{', async' if args.use_async else ''}) ==")
    for r in done[:8]:
        print(f"req {r.rid:3d}: pred={r.pred} "
              f"latency={1e3 * r.latency_s:7.2f} ms "
              f"queued {1e3 * (r.admitted_s - r.submitted_s):7.2f} ms")
    print(f"throughput {s['requests_per_s']:8.1f} req/s   "
          f"occupancy {s['occupancy']:.2f}   "
          f"mean latency {s['mean_latency_ms']:.2f} ms")


if __name__ == "__main__":
    main()
