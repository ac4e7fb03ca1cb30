"""Fused votes+routing megakernel: parity vs the jnp reference (ragged
i-blocks, non-power-of-two capsule counts, batch>1, bf16 inputs, both
schedules), the wrapper's schedule from the plan, the plan's
resident-vs-streamed decision, PlanError boundaries, and the modeled
u_hat HBM savings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config
from repro.core import capsnet, execplan
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (FUSED_NAME, PlanError, compile_plan,
                                 plan_votes_routing, plan_votes_routing_bwd,
                                 votes_routing_hbm_bytes)
from repro.core.planner import VMEM_BYTES
from repro.kernels import ops, ref
from repro.kernels.votes_routing import votes_routing as vr_kernel

KEY = jax.random.PRNGKey(0)

# Odd image + 24 capsule groups (the NONPOW2 config of test_execplan):
# num_primary = 600, every dimension non-power-of-two.
NONPOW2 = CapsNetConfig(image_hw=15, conv1_channels=24, conv1_kernel=5,
                        pc_kernel=3, pc_stride=2, num_primary_groups=24,
                        primary_dim=4, class_dim=8, use_decoder=False)


def _uv(b, i, c, jd, seed=0):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, seed))
    u = 0.5 * jax.random.normal(k1, (b, i, c))
    w = 0.3 * jax.random.normal(k2, (i, jd, c))
    return u, w


def fused(u, w, *, j, iters=3, **schedule):
    """The kernel on a schedule of the test's choosing."""
    return vr_kernel(u, w, iters=iters, num_classes=j,
                     interpret=ops.should_interpret(), **schedule)


def _ref(u, w, j, iters=3):
    b, i, _ = u.shape
    return ref.routing(ref.caps_votes(u, w).reshape(b, i, j, -1),
                       iters).reshape(b, -1)


# ---------------------------------------------------------------------------
# Kernel parity: fused == jnp reference
# ---------------------------------------------------------------------------

# (b, i, c, j, d, block_i): the MNIST head's widths and its batch-1 case,
# wider capsules, and i-tiles that do not divide I.
SHAPES = [
    (1, 64, 8, 10, 16, 64),
    (2, 256, 8, 10, 16, 128),
    (3, 128, 16, 5, 16, 128),
    (1, 1152, 8, 10, 16, 128),
]


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("b,i,c,j,d,bi", [
    (1, 64, 8, 10, 16, 32),       # divisible blocks
    (2, 100, 8, 10, 16, 32),      # ragged final i-block (100 % 32)
    (3, 135, 8, 5, 8, 64),        # batch > 1 + ragged tail
    (2, 27, 4, 4, 8, 8),          # odd non-power-of-two capsule count
    (2, 300, 8, 5, 8, 128),       # ragged tail of a lane-wide i-tile
    (2, 100, 8, 5, 8, 256),       # i-tile wider than I (clamped)
] + SHAPES)
def test_fused_matches_reference(mode, b, i, c, j, d, bi):
    u, w = _uv(b, i, c, j * d, seed=i)
    got = fused(u, w, j=j, mode=mode, block_i=bi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(u, w, j)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("b,i,c,j,d,bi", SHAPES)
def test_fused_bf16_inputs(mode, b, i, c, j, d, bi):
    """bf16 capsules and weights route to the fp32 reference of the same
    (rounded) inputs within bf16's precision."""
    u, w = (a.astype(jnp.bfloat16) for a in _uv(b, i, c, j * d, seed=i))
    got = fused(u, w, j=j, mode=mode, block_i=bi)
    want = _ref(u.astype(jnp.float32), w.astype(jnp.float32), j)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("iters", [1, 2, 5])
@pytest.mark.parametrize("b,i,j,d,bi", [(2, 96, 5, 8, 32),
                                        (1, 64, 10, 16, 32),
                                        (2, 1152, 10, 16, 128)])
def test_fused_iteration_sweep(mode, iters, b, i, j, d, bi):
    u, w = _uv(b, i, 8, j * d, seed=iters)
    got = fused(u, w, j=j, iters=iters, mode=mode, block_i=bi)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ref(u, w, j, iters)),
                               rtol=1e-5, atol=1e-6)


def test_fused_routing_matches_capsnet_module():
    """The kernel routes exactly like the model's own jnp routing."""
    u, w = _uv(2, 128, 8, 160, seed=5)
    got = fused(u, w, j=10, mode="resident", block_i=128)
    want = capsnet.routing_by_agreement(
        jnp.einsum("bic,inc->bin", u, w).reshape(2, 128, 10, 16), 3)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(2, 160)),
                               rtol=1e-5, atol=1e-6)


def test_fused_rejects_bad_mode_and_classes():
    u, w = _uv(1, 16, 4, 20)
    with pytest.raises(ValueError, match="unknown mode"):
        fused(u, w, j=5, mode="hybrid", block_i=8)
    with pytest.raises(ValueError, match="not divisible"):
        fused(u, w, j=3, mode="resident", block_i=8)


def test_fused_wrapper_takes_schedule_from_plan():
    """The wrapper runs the layer on its plan op's schedule, plans a
    forward-only plan's backward at the plan's budget, and matches the
    reference."""
    plan = compile_plan(NONPOW2, batch=2)
    lay = NONPOW2.routing_stack()[0]
    u, w = _uv(2, lay.in_caps, lay.in_dim, lay.jd, seed=7)
    got = ops.votes_routing(u, w, lay, plan)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ref(u, w, lay.num_caps)),
                               rtol=1e-5, atol=1e-6)
    op = plan.op(lay.name)
    bwd = plan_votes_routing_bwd(lay.in_caps, lay.in_dim, lay.jd,
                                 lay.num_caps, batch=2, iters=lay.iters)
    assert ops._schedule(lay, 2, plan) == (
        lay.iters, lay.num_caps, op.mode, op.block_i, bwd.mode,
        bwd.block_i, op.lanes, bwd.lanes, False)
    assert op.mode == "resident" and op.lanes == "caps"


# ---------------------------------------------------------------------------
# Plan decision: resident by default, streamed under pressure, PlanError
# only when even streamed block_i=1 cannot fit
# ---------------------------------------------------------------------------

# Between NONPOW2's streamed and resident footprints at batch 2.
TIGHT = 1_000_000


def test_small_budget_flips_plan_to_streamed():
    args = dict(batch=2, iters=3)
    roomy = plan_votes_routing(600, 4, 80, 10, **args)
    assert roomy.mode == "resident" and roomy.n_passes == 1
    tight = plan_votes_routing(600, 4, 80, 10, vmem_budget=TIGHT, **args)
    # fused s+b pass: W streams once per iteration + the final readout,
    # NOT the old 2-pass schedule's 2*iters+1
    assert tight.mode == "streamed" and tight.n_passes == 3 + 1
    assert tight.vmem_bytes <= TIGHT
    # the flip is forced: not even the smallest lane-legal resident
    # i-tile fits this budget
    bi = execplan._min_block_i(600)
    assert execplan._fused_resident_vmem(2, 600, bi, 4, 80, 10) > TIGHT


def test_plan_error_only_when_streamed_block1_unfit():
    bi = execplan._min_block_i(600)
    floor = execplan._fused_streamed_vmem(2, 600, bi, 4, 80, 10)
    at_floor = plan_votes_routing(600, 4, 80, 10, batch=2,
                                  vmem_budget=floor)
    assert at_floor.mode == "streamed" and at_floor.block_i == bi
    with pytest.raises(PlanError, match=f"streamed block_i={bi}"):
        plan_votes_routing(600, 4, 80, 10, batch=2, vmem_budget=floor - 1)


def test_streamed_plan_executes_config_old_path_could_not():
    """num_primary >> budget: the votes (and the old resident-only routing
    state) exceed VMEM, so the pre-fusion path raised; the streamed
    schedule compiles AND matches the jnp reference end to end."""
    budget = TIGHT
    plan = compile_plan(NONPOW2, batch=2, vmem_budget=budget)
    fused = plan.op(FUSED_NAME)
    assert fused.mode == "streamed"
    assert fused.vmem_bytes <= budget
    # the resident floor: the whole votes tensor in VMEM
    assert execplan._fused_resident_vmem(
        2, NONPOW2.num_primary, execplan._min_block_i(NONPOW2.num_primary),
        NONPOW2.primary_dim, NONPOW2.num_classes * NONPOW2.class_dim,
        NONPOW2.num_classes) > budget
    params = capsnet.init_params(KEY, NONPOW2)
    imgs = jax.random.uniform(KEY, (2, 15, 15, 1))
    want = capsnet.forward(params, imgs, NONPOW2)
    got = capsnet.forward(params, imgs, NONPOW2, backend="pallas", plan=plan)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-4)


def test_fused_wrapper_rejects_batch_over_plan():
    """A batch larger than the plan's would scale the VMEM scratch past
    the validated footprint; smaller batches are within the bound."""
    cfg = CapsNetConfig(use_decoder=False)
    plan = compile_plan(cfg, batch=2)
    lay = cfg.routing_stack()[0]
    u, w = _uv(4, cfg.num_primary, cfg.primary_dim,
               cfg.num_classes * cfg.class_dim, seed=11)
    with pytest.raises(ValueError, match="exceeds the plan's batch"):
        ops.votes_routing(u, w, lay, plan)
    out = ops.votes_routing(u[:1], w, lay, plan)          # smaller: fine
    assert out.shape == (1, cfg.num_classes * cfg.class_dim)


def test_fused_modes_agree_on_same_network():
    """Resident and streamed schedules are numerically interchangeable."""
    u, w = _uv(2, 600, 4, 80, seed=3)
    res = fused(u, w, j=10, mode="resident", block_i=128)
    stre = fused(u, w, j=10, mode="streamed", block_i=16)
    np.testing.assert_allclose(np.asarray(res), np.asarray(stre),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Fused s+b streamed pass vs the resident schedule and the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,i,c,j,d,bi,iters", [
    (1, 64, 8, 10, 16, 32, 3),       # divisible blocks
    (2, 100, 8, 10, 16, 32, 3),      # ragged final i-block + batch>1
    (3, 135, 8, 5, 8, 64, 2),        # batch > 1 + ragged tail
    (2, 27, 4, 4, 8, 8, 1),          # odd non-power-of-two capsule count
    (2, 96, 8, 5, 8, 32, 5),         # deeper iteration count
])
def test_fused_streamed_pass_matches_resident_and_reference(b, i, c, j, d,
                                                            bi, iters):
    """The one-iteration software pipeline (b-update folded into the
    s-accumulation stream, votes recomputed per pass) matches the
    resident schedule (votes computed once) and the jnp reference."""
    u, w = _uv(b, i, c, j * d, seed=i + iters)
    streamed = fused(u, w, j=j, iters=iters, mode="streamed", block_i=bi)
    resident = fused(u, w, j=j, iters=iters, mode="resident", block_i=bi)
    np.testing.assert_allclose(np.asarray(streamed), np.asarray(resident),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(streamed),
                               np.asarray(_ref(u, w, j, iters)),
                               rtol=1e-5, atol=1e-6)


def test_plan_modes_are_the_kernel_modes():
    """Every plan mode is one the kernel runs (resident or streamed), and
    validate() rejects any other name."""
    from repro.kernels.votes_routing import MODES
    plan = compile_plan(NONPOW2, batch=2, vmem_budget=TIGHT)
    assert plan.op(FUSED_NAME).mode in MODES
    import dataclasses
    bad = dataclasses.replace(
        plan, ops=tuple(dataclasses.replace(op, mode="streamed-2pass")
                        if op.name == FUSED_NAME else op
                        for op in plan.ops))
    with pytest.raises(PlanError, match="unknown mode"):
        bad.validate()


def test_streamed_w_traffic_halved_vs_2pass():
    """Forward W traffic drops from 2*iters+1 to iters+1 passes; the
    modeled per-forward savings is exactly iters W sweeps."""
    iters = 3
    tight = plan_votes_routing(600, 4, 80, 10, batch=2, iters=iters,
                               vmem_budget=TIGHT)
    fused_bytes = votes_routing_hbm_bytes(2, 600, 4, 80, tight.n_passes)
    oracle_bytes = votes_routing_hbm_bytes(2, 600, 4, 80, 2 * iters + 1)
    w_sweep = 600 * 80 * 4 * execplan.ELEM_BYTES
    assert tight.n_passes == iters + 1
    assert oracle_bytes - fused_bytes == iters * w_sweep
    # the plan's streamed ClassCaps-Routing entry models the fused count
    # at the lowering's padded i-grid (W rows pad to the block_i tiles),
    # plus the wrapper's relayout of u and W onto lanes
    plan = compile_plan(NONPOW2, batch=2, vmem_budget=TIGHT)
    fused_op = plan.op(FUSED_NAME)
    assert fused_op.mode == "streamed"
    assert fused_op.uhat_hbm_bytes == 0
    jd = NONPOW2.num_classes * NONPOW2.class_dim
    assert fused_op.hbm_bytes == votes_routing_hbm_bytes(
        2, NONPOW2.num_primary, NONPOW2.primary_dim, jd,
        NONPOW2.routing_iters + 1, block_i=fused_op.block_i
    ) + execplan.lane_relayout_hbm_bytes(2, NONPOW2.num_primary,
                                         NONPOW2.primary_dim, jd)


# ---------------------------------------------------------------------------
# Modeled HBM traffic: the u_hat round-trip is gone
# ---------------------------------------------------------------------------

def test_plan_reports_zero_uhat_traffic_and_savings():
    """The fused op's modeled traffic is u, W and v alone (plus the
    relayout onto lanes): the u_hat round-trip a votes kernel and a
    routing kernel would pay between them is never counted."""
    plan = compile_plan(CapsNetConfig(), batch=8)
    fused_op = plan.op(FUSED_NAME)
    assert fused_op.uhat_hbm_bytes == 0
    b, i, c, jd = (8, CapsNetConfig().num_primary,
                   CapsNetConfig().primary_dim,
                   CapsNetConfig().num_classes * CapsNetConfig().class_dim)
    assert fused_op.mode == "resident"
    fused_total = votes_routing_hbm_bytes(b, i, c, jd, n_passes=1)
    assert fused_total == (b * i * c + i * jd * c + b * jd) \
        * execplan.ELEM_BYTES
    relayout = execplan.lane_relayout_hbm_bytes(b, i, c, jd)
    assert fused_op.hbm_bytes == fused_total + relayout


# ---------------------------------------------------------------------------
# Output capsules on the lanes: the layout wide routing layers plan
# ---------------------------------------------------------------------------

def _routing_ref(u, w, j):
    b, i, _ = u.shape
    uh = jnp.einsum("bic,inc->bin", u, w).reshape(b, i, j, -1)
    return capsnet.routing_by_agreement(uh, 3).reshape(b, -1)


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("b,i,j,d,bi", [
    (1, 64, 32, 8, 8),            # divisible i-blocks
    (2, 100, 16, 4, 16),          # ragged final i-block (100 % 16)
    (3, 27, 10, 8, 27),           # one block over a non-multiple-of-8 I
])
def test_class_lanes_matches_reference_fwd_and_grad(mode, b, i, j, d, bi):
    u, w = _uv(b, i, 4, j * d, seed=i + j)
    dv = jax.random.normal(jax.random.fold_in(KEY, 99), (b, j * d))

    def run(u, w):
        return fused(u, w, j=j, mode=mode, block_i=bi, lanes="classes")

    np.testing.assert_allclose(np.asarray(run(u, w)),
                               np.asarray(_routing_ref(u, w, j)),
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda u, w: jnp.sum(run(u, w) * dv), (0, 1))(u, w)
    want = jax.grad(lambda u, w: jnp.sum(_routing_ref(u, w, j) * dv),
                    (0, 1))(u, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_wide_layers_plan_class_lanes_at_published_widths():
    """capsnet-cifar10's ResCaps halves (1024 -> 1024 x 8D) have no
    caps-on-lanes schedule (one 128-capsule W tile is 32 MiB); they plan
    with the classes on the lanes, forward and backward, while the
    narrow classification head and MNIST keep the caps layout."""
    plan = compile_plan(get_config("capsnet-cifar10"), batch=1, train=True)
    lanes = {op.name: op.lanes for op in plan.ops if op.lanes}
    assert lanes.pop(FUSED_NAME) == "caps"
    assert lanes.pop(FUSED_NAME + "-bwd") == "caps"
    assert set(lanes.values()) == {"classes"} and len(lanes) == 12
    for op in plan.ops:
        if op.lanes == "classes":
            assert op.block_i % 8 == 0 and op.vmem_bytes <= VMEM_BYTES
    mnist = compile_plan(CapsNetConfig(), batch=8, train=True)
    assert {op.lanes for op in mnist.ops if op.lanes} == {"caps"}


def test_class_lanes_is_the_fallback_under_the_caps_floor():
    """SVHN's plain 2048 -> 64 x 8D layer at a quarter budget: the
    caps-on-lanes streamed floor does not fit, the classes layout does."""
    budget = VMEM_BYTES // 4
    caps_floor = execplan._fused_streamed_vmem(1, 2048, 128, 8, 512, 64)
    assert caps_floor > budget
    sched = plan_votes_routing(2048, 8, 512, 64, batch=1,
                               vmem_budget=budget)
    assert sched.lanes == "classes" and sched.mode == "streamed"
    assert sched.vmem_bytes <= budget and sched.block_i % 8 == 0


def test_class_lanes_plan_runs_end_to_end():
    """A budget that flips the ResCaps halves of a deep stack onto the
    class-lanes layout changes the schedule, never the math: forward
    and gradients match the jnp reference through the plan."""
    cfg = get_smoke_config("capsnet-cifar10")
    plan = compile_plan(cfg, batch=2, vmem_budget=900_000, train=True)
    assert any(op.lanes == "classes" for op in plan.ops)
    params = capsnet.init_params(KEY, cfg)
    imgs = jax.random.uniform(KEY, (2, cfg.image_hw, cfg.image_hw,
                                    cfg.in_channels))
    labels = jnp.array([1, 7])
    got = capsnet.forward(params, imgs, cfg, backend="pallas", plan=plan)
    want = capsnet.forward(params, imgs, cfg)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-5)

    def loss(p, backend):
        kw = dict(plan=plan) if backend == "pallas" else {}
        return capsnet.total_loss(p, imgs, labels, cfg, backend=backend,
                                  **kw)[0]

    g_got = jax.grad(loss)(params, "pallas")
    g_want = jax.grad(loss)(params, "jnp")
    for a, r in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)
