"""Plan-driven CapsNet execution: jnp vs Pallas forward + batched serving.

Times the reference jnp forward against the ExecutionPlan-driven Pallas
forward (interpret mode on CPU -- the comparison is about the shared plan,
not raw speed off-TPU), the PIPELINED plan (Conv1 -> one
``primary_routing`` megakernel) against the per-op plan with the modeled
inter-layer HBM bytes the pipelining eliminates, times the im2col conv
kernels and the fused votes+routing megakernel against the split
``caps_votes`` -> ``routing`` pair (with the modeled HBM bytes each moves
-- the u_hat round-trip the fusion kills), prints the compiled plan,
times the 3-block CIFAR-10 ResCaps stack (per-layer fused OpPlans,
modeled per-layer HBM bytes, reversible-backward grad vs jnp), and
drives the slot-based ``CapsuleEngine`` over a request stream reporting
its full ``stats()`` (the CI perf-trajectory rows in
``BENCH_capsule.json``).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from benchmarks.common import row, timed
from repro.configs import registry
from repro.core import capsnet, execplan
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (BWD_SUFFIX, FUSED_NAME, PIPE_NAME,
                                 compile_plan, plan_votes_routing,
                                 primary_intermediate_hbm_bytes,
                                 spilled_votes_routing_bwd_hbm_bytes,
                                 split_votes_routing_hbm_bytes,
                                 votes_routing_bwd_hbm_bytes,
                                 votes_routing_hbm_bytes)
from repro.kernels import ops
from repro.serve.capsule import CapsRequest, CapsuleEngine

CFG = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                    pc_kernel=3, num_primary_groups=4, primary_dim=4,
                    class_dim=8, use_decoder=False)
BATCH = 4
DEEP_BATCH = 2                 # the 3-block CIFAR-10 smoke stack rows
REQUESTS = 16


def main() -> None:
    key = jax.random.PRNGKey(0)
    params = capsnet.init_params(key, CFG)
    imgs = jax.random.uniform(key, (BATCH, CFG.image_hw, CFG.image_hw, 1))
    plan = compile_plan(CFG, batch=BATCH)

    for r in plan.summary():
        row(f"plan/{r['name']}", 0.0,
            f"kernel={r['kernel']} block={r['block']} mode={r['mode']} "
            f"vmem_kib={r['vmem_kib']:.1f} "
            f"uhat_hbm_bytes={r['uhat_hbm_bytes']}")

    f_jnp = jax.jit(lambda p, x: capsnet.forward(p, x, CFG)["lengths"])
    f_pal = jax.jit(lambda p, x: capsnet.forward(p, x, CFG, backend="pallas",
                                                 plan=plan)["lengths"])
    want, us = timed(lambda: np.asarray(f_jnp(params, imgs)))
    row("capsnet-forward-jnp", us, f"batch={BATCH}")
    got, us = timed(lambda: np.asarray(f_pal(params, imgs)))
    row("capsnet-forward-pallas", us,
        f"maxdiff={np.abs(got - want).max():.2e}")

    # PIPELINED plan: Conv1 -> ONE primary_routing megakernel (PrimaryCaps
    # conv + squash + votes + routing, the inter-layer u resident in VMEM)
    # vs the per-op plan above -- same forward, one fewer HBM round-trip.
    pipe_plan = compile_plan(CFG, batch=BATCH, pipeline=True)
    pipe_op = pipe_plan.op(PIPE_NAME)
    f_pipe = jax.jit(lambda p, x: capsnet.forward(
        p, x, CFG, backend="pallas", plan=pipe_plan)["lengths"])
    piped, us = timed(lambda: np.asarray(f_pipe(params, imgs)))
    row("capsnet-forward-pallas-pipelined", us,
        f"mode={pipe_op.mode} block_i={pipe_op.block_i} "
        f"block_k={pipe_op.block_k} maxdiff={np.abs(piped - got).max():.2e}")
    inter = primary_intermediate_hbm_bytes(BATCH, CFG.num_primary,
                                           CFG.primary_dim)
    row("primary-routing/fwd-hbm-bytes-pipelined", 0.0,
        f"{pipe_plan.forward_hbm_bytes():.0f}")
    row("primary-routing/fwd-hbm-bytes-perop", 0.0,
        f"{plan.forward_hbm_bytes():.0f}")
    row("primary-routing/hbm-bytes-intermediate-saved", 0.0,
        f"{inter:.0f} (u round-trip killed; pipelined "
        f"intermediate_hbm_bytes={pipe_op.intermediate_hbm_bytes:.0f})")

    # Individual plan-driven conv kernels (the PR-2 im2col path).
    c1 = plan.op("Conv1")
    x1, us = timed(lambda: np.asarray(ops.conv2d(
        imgs, params["conv1_w"], params["conv1_b"], stride=1, plan_op=c1,
        epilogue="relu")))
    row("conv1-im2col", us,
        f"block={c1.block.block_m}x{c1.block.block_k}x{c1.block.block_n}")
    pc = plan.op("PrimaryCaps")
    _, us = timed(lambda: np.asarray(ops.conv2d(
        x1, params["pc_w"], params["pc_b"], stride=CFG.pc_stride, plan_op=pc,
        squash_dim=CFG.primary_dim)))
    row("primarycaps-im2col", us,
        f"block={pc.block.block_m}x{pc.block.block_k}x{pc.block.block_n} "
        f"fused_squash={pc.fuses_squash}")

    # Fused votes+routing megakernel vs the split caps_votes -> routing
    # pair, plus the modeled HBM bytes each schedule moves per forward.
    fused_op = plan.op(FUSED_NAME)
    jd = CFG.num_classes * CFG.class_dim
    u = capsnet.squash(jax.random.normal(
        key, (BATCH, CFG.num_primary, CFG.primary_dim)))
    w = params["cc_w"].reshape(CFG.num_primary, jd, CFG.primary_dim)
    fused, us = timed(lambda: np.asarray(ops.votes_routing(
        u, w, plan=plan)))
    row("votes-routing-fused", us,
        f"mode={fused_op.mode} block_i={fused_op.block_i}")
    split, us = timed(lambda: np.asarray(ops.routing(
        ops.caps_votes(u, w, plan=plan), plan=plan)))
    row("votes-routing-split", us,
        f"maxdiff={np.abs(fused - split).max():.2e}")
    split_bytes, uhat_bytes = split_votes_routing_hbm_bytes(
        BATCH, CFG.num_primary, CFG.primary_dim, jd)
    row("votes-routing/hbm-bytes-fused", 0.0, f"{fused_op.hbm_bytes:.0f}")
    row("votes-routing/hbm-bytes-split", 0.0, f"{split_bytes:.0f}")
    row("votes-routing/hbm-bytes-uhat-saved", 0.0,
        f"{uhat_bytes:.0f} (u_hat round-trip killed; fused uhat_hbm_bytes="
        f"{fused_op.uhat_hbm_bytes:.0f})")

    # STREAMED schedule (forced by a budget under the resident floor):
    # the fused s+b pass streams W iters+1 times per forward -- timed,
    # plus the modeled W traffic and the fused backward's iters+4.
    iters = CFG.routing_iters
    floor = execplan._fused_resident_vmem(
        BATCH, CFG.num_primary, execplan._min_block_i(CFG.num_primary),
        CFG.primary_dim, jd, CFG.num_classes)
    tight = plan_votes_routing(CFG.num_primary, CFG.primary_dim, jd,
                               CFG.num_classes, batch=BATCH, iters=iters,
                               vmem_budget=floor - 1)
    _, us = timed(lambda: np.asarray(ops.votes_routing(
        u, w, iters=iters, mode=tight.mode, block_i=tight.block_i,
        bwd_mode=tight.mode, bwd_block_i=tight.block_i)))
    row("votes-routing-streamed-fused", us,
        f"mode={tight.mode} block_i={tight.block_i} w_passes={tight.n_passes}")
    stre_bytes = votes_routing_hbm_bytes(BATCH, CFG.num_primary,
                                         CFG.primary_dim, jd, tight.n_passes)
    row("votes-routing/hbm-bytes-streamed", 0.0,
        f"{stre_bytes:.0f} (W x {tight.n_passes} = iters+1 passes)")
    row("votes-routing-bwd/hbm-bytes-streamed", 0.0,
        f"{votes_routing_bwd_hbm_bytes(BATCH, CFG.num_primary, CFG.primary_dim, jd, mode='streamed', iters=iters):.0f} "
        f"(W x {iters + 4} = iters+4 passes)")

    # Backward: the custom-VJP training step through both backends, and
    # the fused backward's modeled HBM bytes vs a recompute-from-HBM
    # backward (u_hat spilled by the forward, d u_hat round-tripping the
    # same way -- the traffic the fused backward never moves).
    tplan = compile_plan(CFG, batch=BATCH, train=True)
    bwd_op = tplan.op(FUSED_NAME + BWD_SUFFIX)
    labels = jax.random.randint(key, (BATCH,), 0, CFG.num_classes)
    g_jnp = jax.jit(jax.grad(
        lambda p, x, y: capsnet.total_loss(p, x, y, CFG)[0]))
    g_pal = jax.jit(jax.grad(
        lambda p, x, y: capsnet.total_loss(p, x, y, CFG, backend="pallas",
                                           plan=tplan)[0]))
    _, us = timed(lambda: np.asarray(g_jnp(params, imgs, labels)["cc_w"]))
    row("capsnet-grad-jnp", us, f"batch={BATCH}")
    _, us = timed(lambda: np.asarray(g_pal(params, imgs, labels)["cc_w"]))
    row("capsnet-grad-pallas", us,
        f"bwd_mode={bwd_op.mode} bwd_block_i={bwd_op.block_i}")
    spilled_bytes, uhat_bwd = spilled_votes_routing_bwd_hbm_bytes(
        BATCH, CFG.num_primary, CFG.primary_dim, jd)
    row("votes-routing-bwd/hbm-bytes-fused", 0.0, f"{bwd_op.hbm_bytes:.0f}")
    row("votes-routing-bwd/hbm-bytes-spilled", 0.0, f"{spilled_bytes:.0f}")
    row("votes-routing-bwd/hbm-bytes-uhat-saved", 0.0,
        f"{uhat_bwd:.0f} (u_hat + d_u_hat round-trips killed; fused bwd "
        f"uhat_hbm_bytes={bwd_op.uhat_hbm_bytes:.0f})")

    # DEEP STACK: the 3-block CIFAR-10 ResCaps graph (smoke widths -- the
    # comparison is the per-layer plan + reversible backward, not raw
    # speed off-TPU).  One fused votes_routing OpPlan per routing-layer
    # instance, per-layer modeled HBM bytes, and the flat-in-depth
    # activation residency of the reversible backward.
    deep_cfg = dataclasses.replace(registry.get_smoke_config("capsnet-cifar10"),
                                   use_decoder=False)
    dkey = jax.random.PRNGKey(1)
    dparams = capsnet.init_params(dkey, deep_cfg)
    dimgs = jax.random.uniform(
        dkey, (DEEP_BATCH, deep_cfg.image_hw, deep_cfg.image_hw,
               deep_cfg.in_channels))
    dplan = compile_plan(deep_cfg, batch=DEEP_BATCH, train=True)
    stack = deep_cfg.routing_stack()
    for op in dplan.ops:
        if op.name.startswith(FUSED_NAME) and not op.name.endswith(BWD_SUFFIX):
            row(f"deep-stack/hbm-bytes/{op.name}", 0.0,
                f"{op.hbm_bytes:.0f} (mode={op.mode} block_i={op.block_i})")
    row("deep-stack/activation-bytes-reversible", 0.0,
        f"{execplan.activation_residency_bytes(deep_cfg, batch=DEEP_BATCH):.0f}"
        f" ({len(stack)} routing layers, 3 ResCaps blocks)")
    row("deep-stack/activation-bytes-saved", 0.0,
        f"{execplan.activation_residency_bytes(deep_cfg, batch=DEEP_BATCH, reversible=False):.0f}")
    d_jnp = jax.jit(lambda p, x: capsnet.forward(p, x, deep_cfg)["lengths"])
    d_pal = jax.jit(lambda p, x: capsnet.forward(
        p, x, deep_cfg, backend="pallas", plan=dplan)["lengths"])
    dwant, us = timed(lambda: np.asarray(d_jnp(dparams, dimgs)), repeats=5)
    row("deep-stack-forward-jnp", us,
        f"batch={DEEP_BATCH} layers={len(stack)}")
    dgot, us = timed(lambda: np.asarray(d_pal(dparams, dimgs)), repeats=5)
    row("deep-stack-forward-pallas", us,
        f"maxdiff={np.abs(dgot - dwant).max():.2e}")
    dlabels = jax.random.randint(dkey, (DEEP_BATCH,), 0, deep_cfg.num_classes)
    dg_jnp = jax.jit(jax.grad(
        lambda p, x, y: capsnet.total_loss(p, x, y, deep_cfg)[0]))
    dg_pal = jax.jit(jax.grad(
        lambda p, x, y: capsnet.total_loss(
            p, x, y, deep_cfg, backend="pallas", plan=dplan)[0]))
    _, us = timed(lambda: np.asarray(dg_jnp(dparams, dimgs, dlabels)["cc_w"]),
                  repeats=5)
    row("deep-stack-grad-jnp", us, f"batch={DEEP_BATCH}")
    _, us = timed(lambda: np.asarray(dg_pal(dparams, dimgs, dlabels)["cc_w"]),
                  repeats=5)
    row("deep-stack-grad-pallas", us,
        "reversible bwd: block inputs recomputed, not saved")

    engine = CapsuleEngine(params, CFG, slots=BATCH, plan=plan)
    pool = np.asarray(imgs)
    for i in range(REQUESTS):
        engine.submit(CapsRequest(rid=i, image=pool[i % BATCH]))
    engine.run()
    s = engine.stats()
    row("capsule-serving", 1e6 * s["elapsed_s"] / max(s["requests"], 1),
        f"req/s={s['requests_per_s']:.1f} occupancy={s['occupancy']:.2f} "
        f"mean_lat_ms={s['mean_latency_ms']:.2f}", gate=False)
    for key in ("requests", "ticks", "requests_per_s", "mean_latency_ms",
                "max_latency_ms", "occupancy"):
        row(f"capsule-serving/{key}", 0.0, f"{s[key]}")

    # Degraded-mode throughput next to the healthy row: a mid-run
    # vmem_shrink makes the engine swap in the degrade_plan schedule
    # (shrunk tiles / streamed routing), so the delta IS the price of
    # serving through a gated-down VMEM budget.  Trajectory row, no gate.
    deg = CapsuleEngine(params, CFG, slots=BATCH, backend="pallas")
    for i in range(REQUESTS):
        deg.submit(CapsRequest(rid=i, image=pool[i % BATCH]))
    from repro.core import faults
    with faults.inject(faults.FaultSpec(site=faults.SITE_ENGINE_TICK,
                                        kind="vmem_shrink", at=1, times=1,
                                        factor=0.012)):
        deg.run()
    d = deg.stats()
    row("capsule-serving-degraded",
        1e6 * d["elapsed_s"] / max(d["requests"], 1),
        f"req/s={d['requests_per_s']:.1f} replans={d['replans']} "
        f"degraded={d['degraded']} vmem_budget={d['vmem_budget']} "
        f"ok={d['ok']}/{d['submitted']}", gate=False)
    row("capsule-serving-degraded/requests_per_s", 0.0,
        f"{d['requests_per_s']}")

    # Req/s scaling vs device count: the slot batch row-sharded over a
    # CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8 in
    # the sharded-serving CI job; on a 1-device run only x1 times and
    # the rest are recorded as skipped).  Wall-clock trajectory rows, no
    # gate -- virtual CPU devices contend for the same cores, so the
    # interesting signal is the trend, not the absolute ratio.
    sps = 2
    for n in (1, 2, 4, 8):
        name = f"capsule-serving-sharded/x{n}"
        if n > jax.device_count():
            row(name, 0.0,
                f"skipped: {jax.device_count()} visible device(s)",
                gate=False)
            continue
        sh = CapsuleEngine(params, CFG, slots=n * sps, n_shards=n)
        for i in range(4 * n * sps):
            sh.submit(CapsRequest(rid=i, image=pool[i % BATCH]))
        sh.run()
        st = sh.stats()
        row(name, 1e6 * st["elapsed_s"] / max(st["requests"], 1),
            f"req/s={st['requests_per_s']:.1f} shards={n} "
            f"slots={n * sps} traces={sh._forward_traces} "
            f"ok={st['ok']}/{st['submitted']}", gate=False)
        row(f"{name}/requests_per_s", 0.0, f"{st['requests_per_s']}")


if __name__ == "__main__":
    main()
