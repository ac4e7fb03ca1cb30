"""ExecutionPlan: invariants, plan-derived PMU schedules, PMU edge cases,
and the plan-driven Pallas forward vs the jnp reference."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import analysis, capsnet, dse
from repro.core.capsnet import CapsNetConfig
from repro.core.energy import SRAMConfig
from repro.core.execplan import PlanError, compile_plan
from repro.core.planner import VMEM_BYTES
from repro.core.pmu import PhaseRequirement, build_schedule, schedule_from_plan

KEY = jax.random.PRNGKey(0)
CFG = CapsNetConfig()                     # the paper's MNIST network
SMOKE = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                      pc_kernel=3, num_primary_groups=4, primary_dim=4,
                      class_dim=8, decoder_hidden=(32, 64))
# pc_out = (10 - 6)//2 + 1 = 3, groups = 3 -> num_primary = 27: odd and
# non-power-of-two, the case that used to collapse the i-tile pick to 1.
ODD = CapsNetConfig(image_hw=14, conv1_channels=8, conv1_kernel=5,
                    pc_kernel=6, pc_stride=2, num_primary_groups=3,
                    primary_dim=4, class_dim=8, use_decoder=False)
# Odd image, 24 capsule groups: every conv im2col matmul dimension is
# non-power-of-two (Conv1 M = B*121, K = 25, N = 24; PrimaryCaps M = B*25,
# K = 216, N = 96), so the Pallas conv kernels run ragged final M/N blocks
# and K zero-padding end to end.
NONPOW2 = CapsNetConfig(image_hw=15, conv1_channels=24, conv1_kernel=5,
                        pc_kernel=3, pc_stride=2, num_primary_groups=24,
                        primary_dim=4, class_dim=8, use_decoder=False)


# ---------------------------------------------------------------------------
# Plan invariants
# ---------------------------------------------------------------------------

def test_plan_covers_all_five_operations():
    """Three EXECUTED ops (ClassCaps is one fused megakernel) covering the
    five dataflow-model operations."""
    plan = compile_plan(CFG)
    assert [op.name for op in plan.ops] == [
        "Conv1", "PrimaryCaps", "ClassCaps-Routing"]
    assert [p.name for p in plan.profiles] == [
        "Conv1", "PrimaryCaps", "ClassCaps-FC", "Sum+Squash", "Update+Sum"]
    assert plan.phase_groups() == (
        ("Conv1", ("Conv1",)),
        ("PrimaryCaps", ("PrimaryCaps",)),
        ("ClassCaps-Routing", ("ClassCaps-FC", "Sum+Squash", "Update+Sum")))
    assert [r.name for r in plan.phase_requirements()] == [
        op.name for op in plan.ops]


@pytest.mark.parametrize("cfg", [CFG, SMOKE, ODD],
                         ids=["mnist", "smoke", "odd"])
@pytest.mark.parametrize("batch", [1, 4])
def test_plan_footprints_fit_vmem(cfg, batch):
    plan = compile_plan(cfg, batch=batch)
    plan.validate()
    for op in plan.ops:
        assert op.vmem_bytes <= plan.vmem_budget <= VMEM_BYTES
        assert op.requirement.required_bytes > 0
        assert op.requirement.duration_cycles > 0
    assert plan.peak_vmem_bytes <= VMEM_BYTES


def test_plan_profiles_match_analysis():
    """The plan's dataflow profiles ARE the paper's Fig. 4 model."""
    plan = compile_plan(CFG)
    want = analysis.capsnet_profiles()
    assert [dataclasses.asdict(p) for p in plan.profiles] == [
        dataclasses.asdict(p) for p in want]


def test_plan_block_i_not_degenerate_for_odd_caps():
    plan = compile_plan(ODD)
    bi = plan.op("ClassCaps-Routing").block_i
    assert 1 < bi <= ODD.num_primary
    assert bi >= 8              # the old //=2 loop would have returned 1


@pytest.mark.parametrize("cfg", [CFG, SMOKE, ODD, NONPOW2],
                         ids=["mnist", "smoke", "odd", "nonpow2"])
def test_plan_runs_whole_network_through_pallas(cfg):
    """No conv2d.xla asterisk left, and no separate caps_votes+routing
    pair: the ClassCaps head is ONE fused votes_routing op."""
    plan = compile_plan(cfg, batch=2)
    kernels = {op.name: op.kernel for op in plan.ops}
    assert not any("xla" in k for k in kernels.values()), kernels
    assert kernels["Conv1"] == "conv_im2col"
    assert kernels["PrimaryCaps"].startswith("conv_im2col")
    assert kernels["ClassCaps-Routing"] == "votes_routing"
    assert "caps_votes" not in kernels.values()
    assert "routing" not in kernels.values()
    fused = plan.op("ClassCaps-Routing")
    assert fused.mode in ("resident", "streamed")
    assert fused.uhat_hbm_bytes == 0            # the votes never hit HBM
    for name in ("Conv1", "PrimaryCaps"):
        blk = plan.op(name).block
        assert blk is not None and blk.block_m >= 1 and blk.block_k >= 1


def test_primarycaps_squash_fuses_when_tile_capsule_aligned():
    plan = compile_plan(CFG)
    pc = plan.op("PrimaryCaps")
    assert pc.block.block_n % CFG.primary_dim == 0
    assert pc.kernel == "conv_im2col+squash" and pc.fuses_squash
    assert pc.block_rows is not None          # fallback tile still planned


def test_primarycaps_squash_fuses_on_clamped_tile():
    """Fusion keys on the CLAMPED n-tile: primary_dim=12 does not divide a
    planner block_n of 128, but the kernel clamps the tile to pc_cout=96,
    which 12 does divide."""
    cfg = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                        pc_kernel=3, num_primary_groups=8, primary_dim=12,
                        class_dim=8, use_decoder=False)
    plan = compile_plan(cfg, batch=2)
    pc = plan.op("PrimaryCaps")
    assert cfg.pc_channels == 96
    assert min(pc.block.block_n, cfg.pc_channels) % cfg.primary_dim == 0
    assert pc.fuses_squash
    # and the forward still matches the reference through the fused path
    params = capsnet.init_params(KEY, cfg)
    imgs = jax.random.uniform(KEY, (2, 14, 14, 1))
    want = capsnet.forward(params, imgs, cfg)
    got = capsnet.forward(params, imgs, cfg, backend="pallas", plan=plan)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-4)


def test_plan_rejects_impossible_budget():
    with pytest.raises(ValueError):          # PlanError or planner failure
        compile_plan(CFG, vmem_budget=1024)


# The benchmark cells' plans, pinned: (name, kernel, mode, lanes, block_i,
# block_k, conv tiles (m, k, n), block_rows, W passes, nota, vmem_bytes)
# per OpPlan, then the first routing layer's kernel statics as the pipe
# wrapper resolves them (iters, classes, mode, block_i, bwd_mode,
# bwd_block_i, lanes, bwd_lanes, nota) -- the static arguments of the
# ``primary_caps_routing``/``primary_caps_routing_nota`` jits.
_MNIST_FWD = [
    ("Conv1", "conv_im2col", None, None, None, None, (512, 128, 256), None,
     None, False, 1327104),
    ("PrimaryCaps-Routing", "primary_routing", "streamed", "caps", 128, 256,
     (256, 256, 256), None, 4, False, 11345920),
]
_MNIST_BWD = [
    ("ClassCaps-Routing-bwd", "votes_routing_bwd", "streamed", "caps", 128,
     None, None, None, 7, False, 12845056),
    ("PrimaryCaps-bwd", "conv_im2col_bwd", None, None, None, None,
     (256, 256, 256), 1024, None, False, 1327104),
    ("Conv1-bwd", "conv_im2col_bwd", None, None, None, None,
     (512, 128, 256), None, None, False, 1662976),
]
_SABOUR_FWD = [
    ("Conv1", "conv_im2col", None, None, None, None, (4096, 256, 256), None,
     None, False, 13123584),
    ("PrimaryCaps-Routing", "primary_routing", "streamed", "caps", 256, 256,
     (256, 256, 512), None, 4, True, 13123584),
]
_SABOUR_BWD = [
    ("ClassCaps-Routing-bwd", "votes_routing_bwd", "streamed", "caps", 256,
     None, None, None, 7, True, 15859712),
    ("PrimaryCaps-bwd", "conv_im2col_bwd", None, None, None, None,
     (256, 256, 512), 1024, None, False, 2129920),
    ("Conv1-bwd", "conv_im2col_bwd", None, None, None, None,
     (4096, 256, 256), None, None, False, 8658944),
]
_MNIST_PIPE = (3, 10, "streamed", 128, "streamed", 128, "caps", "caps", False)
_SABOUR_PIPE = (3, 10, "streamed", 256, "streamed", 256, "caps", "caps", True)


@pytest.mark.parametrize("arch,train,ops,pipe", [
    ("capsnet-mnist", False, _MNIST_FWD, _MNIST_PIPE),
    ("capsnet-mnist", True, _MNIST_FWD + _MNIST_BWD, _MNIST_PIPE),
    ("capsnet-cifar10-sabour", False, _SABOUR_FWD, _SABOUR_PIPE),
    ("capsnet-cifar10-sabour", True, _SABOUR_FWD + _SABOUR_BWD,
     _SABOUR_PIPE),
], ids=["mnist-serve", "mnist-train", "cifar10-serve", "cifar10-train"])
def test_benchmark_cell_plans_are_pinned(arch, train, ops, pipe):
    """The plans the benchmark cells run -- 16 serving slots, or a
    training batch of 16 -- and the pipe kernel's statics resolved from
    them, as they stand; a change to either is a change to every cell's
    chip program."""
    from repro.configs.registry import get_config
    from repro.kernels.ops import _schedule
    cfg = get_config(arch)
    plan = compile_plan(cfg, batch=16, train=train, pipeline=True)
    got = [(op.name, op.kernel, op.mode, op.lanes, op.block_i, op.block_k,
            (op.block.block_m, op.block.block_k, op.block.block_n)
            if op.block else None, op.block_rows, op.n_passes, op.nota,
            op.vmem_bytes) for op in plan.ops]
    assert got == ops
    assert tuple(_schedule(cfg.routing_stack()[0], 16, plan,
                           pipe=True)) == pipe


def test_compile_plan_surfaces_fused_plan_error():
    """compile_plan at a batch no fused schedule can serve reports the
    megakernel's message: PlanError names the smallest streamed i-tile
    (the convs fit; the resident AND streamed footprints are what break)."""
    with pytest.raises(PlanError, match="even streamed block_i="):
        compile_plan(SMOKE, batch=2000, vmem_budget=400_000)


def test_plan_validate_catches_oversized_op():
    plan = compile_plan(CFG)
    bad = dataclasses.replace(plan.ops[0], vmem_bytes=plan.vmem_budget + 1)
    broken = dataclasses.replace(plan, ops=(bad,) + plan.ops[1:])
    with pytest.raises(PlanError):
        broken.validate()


def test_plan_unknown_op_lookup():
    with pytest.raises(KeyError):
        compile_plan(CFG).op("nonexistent")


# ---------------------------------------------------------------------------
# One schedule: the DSE/PMU consume what the kernels execute
# ---------------------------------------------------------------------------

def test_dse_default_uses_plan_schedule():
    """The default DSE scores the plan's FUSED phases (one gating phase
    for the votes+routing megakernel); explicit profiles keep the paper's
    five-phase model."""
    via_plan = dse.best_design(plan=compile_plan(CFG))
    default = dse.best_design()
    assert via_plan.org_name == default.org_name
    assert via_plan.total_mj == pytest.approx(default.total_mj)
    grouped = via_plan.evaluation.schedules[0]
    assert [ph.name for ph in grouped.phases] == [
        "Conv1", "PrimaryCaps", "ClassCaps-Routing"]
    explicit = dse.best_design(analysis.capsnet_profiles())
    assert len(explicit.evaluation.schedules[0].phases) == 5


def test_dse_rejects_profiles_and_plan_together():
    with pytest.raises(ValueError):
        dse.explore(analysis.capsnet_profiles(), plan=compile_plan(CFG))


def test_schedule_from_plan_matches_manual_requirements():
    plan = compile_plan(CFG)
    mem = SRAMConfig("m", 1 << 20, power_gated=True, banks=16,
                     sectors_per_bank=64)
    got = schedule_from_plan(mem, plan)
    want = build_schedule(mem, plan.phase_requirements())
    assert got == want
    assert [p.name for p in got.phases] == [op.name for op in plan.ops]


def test_evaluate_plan_gates_fused_phases():
    """evaluate_plan == evaluate with the plan's phase groups: the fused
    megakernel is ONE gating phase with the peak demand and summed
    duration of the operations it covers, and identical dynamic energy."""
    plan = compile_plan(CFG)
    org = dse.design_organizations(list(plan.profiles))["PG-SEP"]
    via_plan = dse.evaluate_plan(org, plan)
    grouped = dse.evaluate(org, list(plan.profiles),
                           phase_groups=plan.phase_groups())
    ungrouped = dse.evaluate(org, list(plan.profiles))
    assert via_plan.total_mj == pytest.approx(grouped.total_mj)
    assert via_plan.dynamic_mj == pytest.approx(ungrouped.dynamic_mj)
    for sched, raw in zip(via_plan.schedules, ungrouped.schedules):
        assert len(sched.phases) == 3 and len(raw.phases) == 5
        fused, covered = sched.phases[-1], raw.phases[2:]
        assert fused.duration_s == pytest.approx(
            sum(ph.duration_s for ph in covered))
        assert fused.on_fraction == pytest.approx(
            max(ph.on_fraction for ph in covered))


# ---------------------------------------------------------------------------
# Training plans: backward OpPlans gated like the forward's
# ---------------------------------------------------------------------------

def test_train_plan_appends_backward_ops_in_reverse_order():
    plan = compile_plan(CFG, batch=2, train=True)
    assert plan.train
    assert [op.name for op in plan.ops] == [
        "Conv1", "PrimaryCaps", "ClassCaps-Routing",
        "ClassCaps-Routing-bwd", "PrimaryCaps-bwd", "Conv1-bwd"]
    assert [p.name for p in plan.profiles] == [
        "Conv1", "PrimaryCaps", "ClassCaps-FC", "Sum+Squash", "Update+Sum",
        "Update+Sum-bwd", "Sum+Squash-bwd", "ClassCaps-FC-bwd",
        "PrimaryCaps-bwd", "Conv1-bwd"]
    plan.validate()
    for op in plan.ops:
        assert op.vmem_bytes <= plan.vmem_budget
        assert op.requirement.duration_cycles > 0
    # conv backwards reuse the forward block tiles
    for name in ("Conv1", "PrimaryCaps"):
        assert plan.op(name + "-bwd").block == plan.op(name).block
        assert plan.op(name + "-bwd").kernel == "conv_im2col_bwd"
    bwd = plan.op("ClassCaps-Routing-bwd")
    assert bwd.mode in ("resident", "streamed")
    assert bwd.uhat_hbm_bytes == 0


def test_train_plan_gates_backward_phases_in_dse_and_pmu():
    plan = compile_plan(CFG, train=True)
    mem = SRAMConfig("m", 1 << 20, power_gated=True, banks=16,
                     sectors_per_bank=64)
    sched = schedule_from_plan(mem, plan)
    assert [ph.name for ph in sched.phases] == [op.name for op in plan.ops]
    org = dse.design_organizations(list(plan.profiles))["PG-SEP"]
    ev = dse.evaluate_plan(org, plan)
    for s in ev.schedules:
        assert len(s.phases) == 6            # 3 forward + 3 backward
    assert "ClassCaps-Routing-bwd" in ev.per_op_mj
    # the train=True default DSE sizes organizations for the full step
    via_train = dse.best_design(train=True)
    assert [ph.name for ph in
            via_train.evaluation.schedules[0].phases][-1] == "Conv1-bwd"


# ---------------------------------------------------------------------------
# PMU edge cases
# ---------------------------------------------------------------------------

def test_pmu_zero_capacity_memory():
    mem = SRAMConfig("m", 0, power_gated=True, sectors_per_bank=8)
    sched = build_schedule(mem, [PhaseRequirement("a", 1024, 100),
                                 PhaseRequirement("b", 0, 100)])
    for ph in sched.phases:
        assert ph.on_fraction == 0.0
        assert ph.sectors_woken == 0
        assert ph.leakage_mj == 0.0
        assert ph.wakeup_mj == 0.0
    assert np.isfinite(sched.static_mj)


def test_pmu_non_gated_always_fully_on_zero_wakeups():
    mem = SRAMConfig("m", 1 << 16, power_gated=False, sectors_per_bank=8)
    sched = build_schedule(mem, [PhaseRequirement("a", 10, 100),
                                 PhaseRequirement("b", 1 << 16, 100),
                                 PhaseRequirement("c", 0, 100)])
    for ph in sched.phases:
        assert ph.on_fraction == 1.0
        assert ph.sectors_woken == 0
        assert ph.wakeup_mj == 0.0
        assert ph.wakeup_latency_cycles == 0.0
    assert sched.total_transitions == 0
    assert sched.wakeup_mj == 0.0


def test_pmu_shrinking_phases_never_negative_wakeups():
    mem = SRAMConfig("m", 1 << 16, power_gated=True, sectors_per_bank=16)
    reqs = [PhaseRequirement(f"p{i}", b, 100)
            for i, b in enumerate([1 << 16, 1 << 14, 1 << 12, 256, 0])]
    sched = build_schedule(mem, reqs)
    assert all(ph.sectors_woken >= 0 for ph in sched.phases)
    assert [ph.sectors_woken for ph in sched.phases][1:] == [0, 0, 0, 0]
    fr = [ph.on_fraction for ph in sched.phases]
    assert fr == sorted(fr, reverse=True)


def test_pmu_quantization_granularity():
    mem = SRAMConfig("m", 1 << 20, power_gated=True, banks=16,
                     sectors_per_bank=4)
    for want in (0.01, 0.26, 0.5, 0.51, 0.99, 1.0):
        sched = build_schedule(
            mem, [PhaseRequirement("x", want * mem.capacity_bytes, 100)])
        frac = sched.phases[0].on_fraction
        assert frac >= want - 1e-9                    # covers the demand
        assert frac * 4 == pytest.approx(round(frac * 4))  # whole sectors


# ---------------------------------------------------------------------------
# Plan-driven Pallas forward == jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [SMOKE, ODD, NONPOW2],
                         ids=["smoke", "odd", "nonpow2"])
def test_pallas_backend_matches_jnp(cfg):
    params = capsnet.init_params(KEY, cfg)
    imgs = jax.random.uniform(KEY, (3, cfg.image_hw, cfg.image_hw, 1))
    want = capsnet.forward(params, imgs, cfg)
    got = capsnet.forward(params, imgs, cfg, backend="pallas")
    np.testing.assert_allclose(np.asarray(got["class_caps"]),
                               np.asarray(want["class_caps"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-4)
    if "reconstruction" in want:
        np.testing.assert_allclose(np.asarray(got["reconstruction"]),
                                   np.asarray(want["reconstruction"]),
                                   rtol=1e-4, atol=1e-4)


def test_pallas_backend_accepts_precompiled_plan():
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (2, 14, 14, 1))
    plan = compile_plan(SMOKE, batch=2)
    got = capsnet.forward(params, imgs, SMOKE, backend="pallas", plan=plan)
    want = capsnet.forward(params, imgs, SMOKE)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-4)


def test_unknown_backend_rejected():
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (1, 14, 14, 1))
    with pytest.raises(ValueError):
        capsnet.forward(params, imgs, SMOKE, backend="torch")
