"""Pipelined PrimaryCaps->ClassCaps megakernel: fused-vs-unfused parity
(ragged / non-power-of-two capsule counts, batch>1, both consumer
schedules), jax.grad parity, the plan's pipelined-vs-per-op selection
(budget-forced fallback, PlanError boundary), and the modeled
inter-layer HBM savings."""

import jax
import numpy as np
import pytest

from repro.core import analysis, capsnet, execplan
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (FUSED_NAME, PIPE_NAME, BWD_SUFFIX,
                                 PlanError, compile_plan,
                                 plan_primary_routing,
                                 primary_intermediate_hbm_bytes,
                                 primary_routing_hbm_bytes)
from repro.core.planner import LANES
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)

SMOKE = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                      pc_kernel=3, num_primary_groups=4, primary_dim=4,
                      class_dim=8, decoder_hidden=(32, 64))
# Odd image + 24 capsule groups: num_primary = 600, every dimension
# non-power-of-two (the NONPOW2 config of test_execplan).
NONPOW2 = CapsNetConfig(image_hw=15, conv1_channels=24, conv1_kernel=5,
                        pc_kernel=3, pc_stride=2, num_primary_groups=24,
                        primary_dim=4, class_dim=8, use_decoder=False)


def _net(b, h, cin, kh, stride, n_ch, caps_dim, j, d, seed=0):
    """Random producer input + both layers' weights for one pair shape."""
    oh = (h - kh) // stride + 1
    i_dim = oh * oh * (n_ch // caps_dim)
    k = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    x = jax.random.uniform(k[0], (b, h, h, cin))
    w_pc = 0.2 * jax.random.normal(k[1], (kh, kh, cin, n_ch))
    b_pc = 0.1 * jax.random.normal(k[2], (n_ch,))
    w_cc = 0.3 * jax.random.normal(k[3], (i_dim, j * d, caps_dim))
    return x, w_pc, b_pc, w_cc


def _unfused(x, w_pc, b_pc, w_cc, *, stride, iters, j, caps_dim):
    """The per-op oracle: conv_im2col with fused squash -> reshape ->
    votes_routing -- exactly the fallback path a per-op plan runs."""
    pc = ops.conv2d(x, w_pc, b_pc, stride=stride, epilogue="squash",
                    squash_dim=caps_dim)
    u = pc.reshape(x.shape[0], w_cc.shape[0], caps_dim)
    return ops.votes_routing(u, w_cc, iters=iters, num_classes=j)


# ---------------------------------------------------------------------------
# Kernel parity: pipelined megakernel == per-op pair, both schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("b,h,cin,kh,stride,n_ch,c,j,d,bi,bk", [
    (2, 10, 16, 3, 2, 16, 4, 10, 8, 32, 64),   # divisible blocks (I=64)
    (2, 10, 8, 6, 2, 12, 4, 4, 8, 8, 13),      # I=27: odd, ragged i + k
    (3, 7, 8, 3, 2, 60, 4, 5, 8, 64, 1024),    # I=135, batch>1, bi > I
    (1, 9, 6, 3, 2, 20, 4, 3, 16, 7, 29),      # I=80, prime-ish tiles
])
def test_pipelined_matches_unfused_pair(mode, b, h, cin, kh, stride, n_ch,
                                        c, j, d, bi, bk):
    x, w_pc, b_pc, w_cc = _net(b, h, cin, kh, stride, n_ch, c, j, d,
                               seed=h + n_ch)
    got = ops.primary_routing(x, w_pc, b_pc, w_cc, stride=stride, iters=3,
                              num_classes=j, mode=mode, block_i=bi,
                              block_k=bk)
    want = _unfused(x, w_pc, b_pc, w_cc, stride=stride, iters=3, j=j,
                    caps_dim=c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("iters", [1, 2, 5])
def test_pipelined_iteration_sweep(iters):
    x, w_pc, b_pc, w_cc = _net(2, 10, 16, 3, 2, 16, 4, 5, 8, seed=iters)
    for mode in ("resident", "streamed"):
        got = ops.primary_routing(x, w_pc, b_pc, w_cc, stride=2,
                                  iters=iters, num_classes=5, mode=mode,
                                  block_i=16, block_k=32)
        want = _unfused(x, w_pc, b_pc, w_cc, stride=2, iters=iters, j=5,
                        caps_dim=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_pipelined_planless_wrapper_picks_schedule():
    """Without a plan the wrapper resolves (mode, block_i, block_k, conv
    tiles) through the memoized plan decision and still matches."""
    x, w_pc, b_pc, w_cc = _net(2, 10, 16, 3, 2, 16, 4, 10, 8, seed=9)
    got = ops.primary_routing(x, w_pc, b_pc, w_cc, stride=2, iters=3,
                              num_classes=10)
    want = _unfused(x, w_pc, b_pc, w_cc, stride=2, iters=3, j=10,
                    caps_dim=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    mode, bi, bk, cb = ops.planned_primary_routing(16, 144, 16, 64, 4, 80,
                                                   10, 3, 2)
    assert mode == "resident"            # smoke-scale votes fit VMEM
    assert 1 <= bi <= 64 and 1 <= bk <= 144 and len(cb) == 3


def test_pipelined_rejects_bad_args():
    x, w_pc, b_pc, w_cc = _net(1, 10, 8, 3, 2, 12, 4, 4, 8)
    with pytest.raises(ValueError, match="unknown mode"):
        ops.primary_routing(x, w_pc, b_pc, w_cc, stride=2, num_classes=4,
                            mode="hybrid", block_i=8, block_k=16)
    with pytest.raises(ValueError, match="not divisible"):
        ops.primary_routing(x, w_pc, b_pc, w_cc, stride=2, num_classes=3,
                            mode="resident", block_i=8, block_k=16)


# ---------------------------------------------------------------------------
# Gradients: the recompute-from-patches VJP matches the per-op pair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["resident", "streamed"])
def test_grad_parity_vs_unfused(mode):
    x, w_pc, b_pc, w_cc = _net(2, 10, 8, 6, 2, 12, 4, 4, 8, seed=3)

    def loss_fused(x, w_pc, b_pc, w_cc):
        v = ops.primary_routing(x, w_pc, b_pc, w_cc, stride=2, iters=3,
                                num_classes=4, mode=mode, block_i=8,
                                block_k=32)
        return jax.numpy.sum(jax.numpy.sin(v))

    def loss_split(x, w_pc, b_pc, w_cc):
        v = _unfused(x, w_pc, b_pc, w_cc, stride=2, iters=3, j=4,
                     caps_dim=4)
        return jax.numpy.sum(jax.numpy.sin(v))

    got = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, w_pc, b_pc, w_cc)
    want = jax.grad(loss_split, argnums=(0, 1, 2, 3))(x, w_pc, b_pc, w_cc)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_grad_through_total_loss_matches_jnp():
    """End to end: jax.grad through the pipelined train plan equals the
    jnp backend's gradients on every parameter."""
    b = 3
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (b, 14, 14, 1))
    labels = jax.numpy.array([1, 7, 3])
    plan = compile_plan(SMOKE, batch=b, train=True, pipeline=True)
    assert any(op.name == PIPE_NAME for op in plan.ops)

    gp = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, SMOKE, backend="pallas", plan=plan)[0])(params)
    gr = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, SMOKE, backend="jnp")[0])(params)
    for k in gp:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# Plan selection: pipelined when it fits, per-op fallback under pressure,
# PlanError only when neither fits
# ---------------------------------------------------------------------------

def _pipe_args(cfg, batch):
    dims = analysis.dims_from_config(cfg)
    return dict(p_pos=dims.pc_out ** 2, k_in=dims.pc_k ** 2 * dims.pc_cin,
                n_ch=dims.pc_cout, num_caps=dims.num_primary,
                caps_dim=dims.primary_dim,
                jd=dims.num_classes * dims.class_dim,
                j=dims.num_classes, batch=batch)


def test_budget_forces_perop_fallback():
    """One byte under the pipelined streamed floor: compile_plan silently
    falls back to the per-op pair (which still fits -- its phases never
    coexist), and the unfused path keeps executing."""
    a = _pipe_args(SMOKE, 64)
    bi, bk = execplan._min_block_i(a["num_caps"]), min(a["k_in"], LANES)
    floor = execplan._pipe_streamed_vmem(
        a["batch"], a["p_pos"], a["n_ch"], bk, a["num_caps"], bi,
        a["caps_dim"], a["jd"], a["j"])
    budget = floor - 1
    with pytest.raises(PlanError,
                       match=f"streamed block_i={bi}, block_k={bk}"):
        plan_primary_routing(
            a["p_pos"], a["k_in"], a["n_ch"], a["num_caps"], a["caps_dim"],
            a["jd"], a["j"], batch=a["batch"], vmem_budget=budget)
    plan = compile_plan(SMOKE, batch=64, vmem_budget=budget, pipeline=True)
    names = [op.name for op in plan.ops]
    assert PIPE_NAME not in names
    assert "PrimaryCaps" in names and FUSED_NAME in names


def test_pipelined_plan_selected_when_it_fits():
    plan = compile_plan(SMOKE, batch=8, pipeline=True)
    names = [op.name for op in plan.ops]
    assert names == ["Conv1", PIPE_NAME]
    op = plan.op(PIPE_NAME)
    assert op.mode in ("resident", "streamed")
    assert op.block_i >= 1 and op.block_k >= 1
    # pipeline=False (the default) never emits the pair
    perop = compile_plan(SMOKE, batch=8)
    assert PIPE_NAME not in [o.name for o in perop.ops]


def test_pipelined_forward_matches_perop_plan_end_to_end():
    params = capsnet.init_params(KEY, NONPOW2)
    imgs = jax.random.uniform(KEY, (2, 15, 15, 1))
    pipe = compile_plan(NONPOW2, batch=2, pipeline=True)
    perop = compile_plan(NONPOW2, batch=2)
    assert any(op.name == PIPE_NAME for op in pipe.ops)
    want = capsnet.forward(params, imgs, NONPOW2)
    got = capsnet.forward(params, imgs, NONPOW2, backend="pallas",
                          plan=pipe)
    split = capsnet.forward(params, imgs, NONPOW2, backend="pallas",
                            plan=perop)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(want["lengths"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got["lengths"]),
                               np.asarray(split["lengths"]),
                               rtol=1e-5, atol=1e-6)


def test_wrapper_rejects_batch_over_plan():
    plan = compile_plan(SMOKE, batch=2, pipeline=True)
    x, w_pc, b_pc, w_cc = _net(4, 10, 16, 3, 2, 16, 4, 10, 8)
    with pytest.raises(ValueError, match="exceeds the plan's batch"):
        ops.primary_routing(x, w_pc, b_pc, w_cc, plan=plan)
    out = ops.primary_routing(x[:1], w_pc, b_pc, w_cc, plan=plan)
    assert out.shape == (1, 80)


def test_train_plan_keeps_perop_backward():
    """The pipelined VJP replays the producer from patches and composes
    the per-op backward kernels, so a pipelined TRAIN plan's backward
    OpPlans are the per-op ones -- with the PrimaryCaps backward always
    paying the 3-matmul squash-recompute."""
    plan = compile_plan(CapsNetConfig(), batch=8, train=True, pipeline=True)
    names = [op.name for op in plan.ops]
    assert names == ["Conv1", PIPE_NAME, FUSED_NAME + BWD_SUFFIX,
                     "PrimaryCaps" + BWD_SUFFIX, "Conv1" + BWD_SUFFIX]
    pc_bwd = plan.op("PrimaryCaps" + BWD_SUFFIX)
    patches = pc_bwd.workload.m * pc_bwd.workload.k * execplan.ELEM_BYTES
    relayout = 2 * pc_bwd.workload.k * pc_bwd.workload.n * execplan.ELEM_BYTES
    assert pc_bwd.hbm_bytes == (3 * pc_bwd.block.hbm_bytes + 2 * patches
                                + relayout)


# ---------------------------------------------------------------------------
# Modeled HBM traffic: the inter-layer u round-trip is gone
# ---------------------------------------------------------------------------

def test_pipelined_plan_zero_intermediate_and_lower_total():
    """The acceptance criterion: on the MNIST config the pipelined plan
    reports the PrimaryCaps->ClassCaps intermediate at 0 bytes AND a
    lower total forward HBM traffic than the per-op plan."""
    cfg = CapsNetConfig()
    pipe = compile_plan(cfg, batch=8, pipeline=True)
    perop = compile_plan(cfg, batch=8)
    op = pipe.op(PIPE_NAME)
    assert op.intermediate_hbm_bytes == 0.0
    assert op.uhat_hbm_bytes == 0.0
    inter = perop.op("PrimaryCaps").intermediate_hbm_bytes
    assert inter == primary_intermediate_hbm_bytes(8, cfg.num_primary,
                                                   cfg.primary_dim)
    assert inter == 2 * 8 * 1152 * 8 * execplan.ELEM_BYTES
    assert pipe.forward_hbm_bytes() < perop.forward_hbm_bytes()
    # the modeled pipelined traffic is the plan's own number
    a = _pipe_args(cfg, 8)
    dims = analysis.dims_from_config(cfg)
    extract = (execplan.conv_extract_hbm_bytes(
        dims.conv1_out, dims.pc_cin, dims.pc_k, dims.pc_out, batch=8)
        + execplan.lane_relayout_hbm_bytes(0, a["num_caps"], a["caps_dim"],
                                           a["jd"]))
    assert op.hbm_bytes == primary_routing_hbm_bytes(
        8, a["p_pos"], a["k_in"], a["n_ch"], a["num_caps"], a["caps_dim"],
        a["jd"], pipe.op(PIPE_NAME).mode == "streamed"
        and cfg.routing_iters + 1 or 1) + extract


def test_summary_and_pmu_cover_pipelined_phase():
    """The pipelined op appears in the plan summary with its intermediate
    column; the PMU gates the pair as ONE phase (one wakeup window, no
    spurious transition at the fused-away producer/consumer boundary),
    and ``phase_groups`` reports every covered profile for the DSE."""
    from repro.core.energy import SRAMConfig
    from repro.core.pmu import schedule_from_plan
    plan = compile_plan(CapsNetConfig(), batch=8, pipeline=True)
    rows = {r["name"]: r for r in plan.summary()}
    assert rows[PIPE_NAME]["intermediate_hbm_bytes"] == 0.0
    groups = dict(plan.phase_groups())
    assert groups[PIPE_NAME] == execplan.PIPE_COVERS
    mem = SRAMConfig("m", 1 << 20, power_gated=True, banks=16,
                     sectors_per_bank=8)
    sched = schedule_from_plan(mem, plan)
    assert [p.name for p in sched.phases] == ["Conv1", PIPE_NAME]


def test_plan_cache_bounded():
    assert ops.planned_primary_routing.cache_info().maxsize == 64
