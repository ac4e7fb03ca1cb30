"""Differentiable Pallas path: gradient parity vs the jnp reference.

``jax.grad`` through ``backend="pallas"`` runs the kernels' custom VJPs
(backward im2col: col2im scatter + patches^T dy matmul; fused
votes+routing backward: routing replay in VMEM scratch honoring the
reference's ``stop_gradient(u_hat)`` convention).  Property-based tests
sweep ragged i-blocks, non-power-of-two capsule counts (groups=24),
batch>1, and both routing modes -- including a VMEM budget that flips the
mode -- asserting parity with ``jax.grad`` of the jnp reference to <= 1e-5
relative error, plus the backward-plan invariants (``uhat_hbm_bytes=0``,
the forward-plans/backward-raises PlanError boundary).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import capsnet, execplan
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (BWD_SUFFIX, FUSED_NAME, PlanError,
                                 compile_plan, plan_votes_routing_bwd,
                                 spilled_votes_routing_bwd_hbm_bytes,
                                 votes_routing_bwd_hbm_bytes)
from repro.kernels import ops
from repro.kernels.conv_im2col import (col2im_patches, conv2d_im2col,
                                       im2col_patches, matmul_at_b)

KEY = jax.random.PRNGKey(0)
TOL = 1e-5

SMOKE = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                      pc_kernel=3, num_primary_groups=4, primary_dim=4,
                      class_dim=8, decoder_hidden=(32, 64))
# Odd image + 24 capsule groups: num_primary = 600, every dimension
# non-power-of-two (the NONPOW2 config of test_execplan).
NONPOW2 = CapsNetConfig(image_hw=15, conv1_channels=24, conv1_kernel=5,
                        pc_kernel=3, pc_stride=2, num_primary_groups=24,
                        primary_dim=4, class_dim=8, use_decoder=False)


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-12)


def _uv(b, i, c, jd, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    u = 0.5 * jax.random.normal(k1, (b, i, c))
    w = 0.3 * jax.random.normal(k2, (i, jd, c))
    return u, w, k3


# ---------------------------------------------------------------------------
# Backward building blocks
# ---------------------------------------------------------------------------

def test_matmul_at_b_matches_einsum_with_ragged_reduction():
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (45, 13))          # M=45 ragged vs block_m=16
    b = jax.random.normal(k2, (45, 21))
    got = matmul_at_b(a, b, block_m=16, block_k=8, block_n=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(a.T @ b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("c", [3, 128])
def test_col2im_is_adjoint_of_im2col(c):
    """<col2im(dp), x> == <dp, im2col(x)>: the scatter is the exact
    transpose of the patch extraction, for narrow and lane-wide channel
    axes."""
    k1, k2 = jax.random.split(KEY)
    for stride in (1, 2):
        x = jax.random.normal(k1, (2, 9, 9, c))
        oh = (9 - 3) // stride + 1
        dp = jax.random.normal(k2, (2, oh * oh, 3 * 3 * c))
        patches = im2col_patches(x, kh=3, kw=3, stride=stride)
        dx = col2im_patches(dp, kh=3, kw=3, stride=stride, h=9, w=9)
        lhs = float(jnp.sum(dx * x))
        rhs = float(jnp.sum(dp * patches))
        assert lhs == pytest.approx(rhs, rel=1e-5)


@pytest.mark.parametrize("epilogue,squash_dim,stride", [
    ("none", 0, 1), ("relu", 0, 1), ("relu", 0, 2), ("squash", 4, 2)])
def test_conv_grad_matches_lax_conv_reference(epilogue, squash_dim, stride):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = jax.random.normal(k1, (2, 11, 11, 3))
    w = 0.2 * jax.random.normal(k2, (3, 3, 3, 8))
    bias = 0.1 * jax.random.normal(k3, (8,))
    oh = (11 - 3) // stride + 1
    dy = jax.random.normal(k4, (2, oh, oh, 8))

    def f_pal(x, w, bias):
        out = conv2d_im2col(x, w, bias, stride=stride, block_m=16,
                            block_k=8, block_n=8, epilogue=epilogue,
                            squash_dim=squash_dim, interpret=True)
        return jnp.sum(out * dy)

    def f_ref(x, w, bias):
        out = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + bias
        if epilogue == "relu":
            out = jax.nn.relu(out)
        elif epilogue == "squash":
            s = out.shape
            out = capsnet.squash(out.reshape(*s[:3], s[3] // squash_dim,
                                             squash_dim)).reshape(s)
        return jnp.sum(out * dy)

    g_pal = jax.grad(f_pal, argnums=(0, 1, 2))(x, w, bias)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, bias)
    for got, want in zip(g_pal, g_ref):
        assert _rel(got, want) <= TOL


def test_squash_kernel_grad_matches_reference():
    x = jax.random.normal(KEY, (3, 37, 6))       # ragged rows vs block 16
    dy = jax.random.normal(jax.random.fold_in(KEY, 1), x.shape)
    g_pal = jax.grad(lambda x: jnp.sum(
        ops.squash(x, block_rows=16) * dy))(x)
    g_ref = jax.grad(lambda x: jnp.sum(capsnet.squash(x) * dy))(x)
    assert _rel(g_pal, g_ref) <= TOL


# ---------------------------------------------------------------------------
# Fused votes+routing backward: the full (mode x bwd_mode x shape) matrix
# ---------------------------------------------------------------------------

def _vr_grad_pair(u, w, dv, *, iters, j, d, mode, bwd_mode, bi, bwd_bi):
    b, i, c = u.shape

    def loss_pal(u, w):
        v = ops.votes_routing(u, w, iters=iters, num_classes=j, mode=mode,
                              block_i=bi, bwd_mode=bwd_mode,
                              bwd_block_i=bwd_bi)
        return jnp.sum(v.reshape(b, j, d) * dv)

    def loss_ref(u, w):
        uh = capsnet.compute_votes(u, w.reshape(i, j, d, c))
        return jnp.sum(capsnet.routing_by_agreement(uh, iters) * dv)

    return (jax.grad(loss_pal, argnums=(0, 1))(u, w),
            jax.grad(loss_ref, argnums=(0, 1))(u, w))


@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("bwd_mode", ["resident", "streamed"])
@pytest.mark.parametrize("b,i,c,j,d,bi,iters", [
    (1, 64, 8, 10, 16, 32, 3),       # divisible blocks
    (2, 100, 8, 10, 16, 32, 3),      # ragged final i-block + batch>1
    (2, 27, 4, 4, 8, 8, 1),          # odd non-power-of-two capsule count
], ids=["even", "ragged", "nonpow2"])
def test_votes_routing_grad_parity(mode, bwd_mode, b, i, c, j, d, bi, iters):
    u, w, k3 = _uv(b, i, c, j * d, seed=i + iters)
    dv = jax.random.normal(k3, (b, j, d))
    got, want = _vr_grad_pair(u, w, dv, iters=iters, j=j, d=d, mode=mode,
                              bwd_mode=bwd_mode, bi=bi,
                              bwd_bi=max(bi // 2, 1))
    for g, r in zip(got, want):
        assert _rel(g, r) <= TOL


@given(i=st.integers(9, 80), bi=st.integers(1, 48),
       bwd_mode=st.sampled_from(["resident", "streamed"]))
@settings(max_examples=8, deadline=None)
def test_votes_routing_grad_property(i, bi, bwd_mode):
    """Property sweep: ANY capsule count / i-tile pair stays at parity
    (ragged tails, block_i > I clamping, degenerate block_i=1)."""
    b, c, j, d = 2, 4, 4, 4
    u, w, k3 = _uv(b, i, c, j * d, seed=1000 + i + bi)
    dv = jax.random.normal(k3, (b, j, d))
    got, want = _vr_grad_pair(u, w, dv, iters=2, j=j, d=d, mode="streamed",
                              bwd_mode=bwd_mode, bi=min(bi, i),
                              bwd_bi=min(bi, i))
    for g, r in zip(got, want):
        assert _rel(g, r) <= TOL


@pytest.mark.parametrize("b,i,c,j,d,bi,iters", [
    (1, 64, 8, 10, 16, 32, 3),       # divisible blocks
    (2, 100, 8, 10, 16, 32, 3),      # ragged final i-block + batch>1
    (2, 27, 4, 4, 8, 8, 1),          # odd non-power-of-two capsule count
], ids=["even", "ragged", "nonpow2"])
def test_streamed_bwd_matches_resident_bwd(b, i, c, j, d, bi, iters):
    """The streamed replay (votes recomputed from W on each of the
    iters+4 passes) produces the SAME gradients as the resident replay
    (votes rebuilt once into scratch) -- and both match the jnp
    reference."""
    u, w, k3 = _uv(b, i, c, j * d, seed=50 + i + iters)
    dv = jax.random.normal(k3, (b, j, d))
    fused, want = _vr_grad_pair(u, w, dv, iters=iters, j=j, d=d,
                                mode="streamed", bwd_mode="streamed",
                                bi=bi, bwd_bi=max(bi // 2, 1))
    oracle, _ = _vr_grad_pair(u, w, dv, iters=iters, j=j, d=d,
                              mode="streamed", bwd_mode="resident",
                              bi=bi, bwd_bi=max(bi // 2, 1))
    for g_f, g_o, g_r in zip(fused, oracle, want):
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_o),
                                   rtol=1e-5, atol=1e-7)
        assert _rel(g_f, g_r) <= TOL


def test_grad_through_planless_wrapper():
    """Without a plan the wrapper resolves the backward schedule through
    the memoized backward plan decision and still matches the reference."""
    u, w, k3 = _uv(2, 150, 8, 80, seed=7)
    dv = jax.random.normal(k3, (2, 10, 8))

    def loss(u, w):
        return jnp.sum(ops.votes_routing(u, w, iters=3, num_classes=10
                                         ).reshape(2, 10, 8) * dv)

    def loss_ref(u, w):
        uh = capsnet.compute_votes(u, w.reshape(150, 10, 8, 8))
        return jnp.sum(capsnet.routing_by_agreement(uh, 3) * dv)

    got = jax.grad(loss, argnums=(0, 1))(u, w)
    want = jax.grad(loss_ref, argnums=(0, 1))(u, w)
    for g, r in zip(got, want):
        assert _rel(g, r) <= TOL
    mode, bi, lanes = ops.planned_votes_routing_bwd(150, 8, 80, 10, 3, 2)
    assert lanes == "caps"
    assert mode in ("resident", "streamed") and 1 <= bi <= 150


# ---------------------------------------------------------------------------
# End-to-end: margin loss + reconstruction through the whole network
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg,batch", [(SMOKE, 3), (NONPOW2, 2)],
                         ids=["smoke", "nonpow2"])
def test_total_loss_grad_parity(cfg, batch):
    params = capsnet.init_params(KEY, cfg)
    imgs = jax.random.uniform(KEY, (batch, cfg.image_hw, cfg.image_hw, 1))
    labels = jnp.arange(batch) % cfg.num_classes

    def loss(backend):
        return lambda p: capsnet.total_loss(p, imgs, labels, cfg,
                                            backend=backend)[0]

    g_jnp = jax.grad(loss("jnp"))(params)
    g_pal = jax.grad(loss("pallas"))(params)
    for k in g_jnp:
        assert _rel(g_pal[k], g_jnp[k]) <= TOL, k


def test_budget_flip_to_streamed_keeps_grad_parity():
    """A VMEM budget under the backward's resident floor flips the
    backward to streamed while the forward stays resident -- and the
    gradients still match the jnp reference (the mode-flip case of the
    parity matrix)."""
    budget = 1_600_000
    dims_i, c = NONPOW2.num_primary, NONPOW2.primary_dim
    jd = NONPOW2.num_classes * NONPOW2.class_dim
    bi = execplan._min_block_i(dims_i)
    assert execplan._fused_resident_vmem(2, dims_i, bi, c, jd, 10) <= budget
    assert execplan._fused_resident_bwd_vmem(
        2, dims_i, bi, c, jd, 10, NONPOW2.routing_iters) > budget
    plan = compile_plan(NONPOW2, batch=2, vmem_budget=budget, train=True)
    assert plan.op(FUSED_NAME).mode == "resident"
    assert plan.op(FUSED_NAME + BWD_SUFFIX).mode == "streamed"

    params = capsnet.init_params(KEY, NONPOW2)
    imgs = jax.random.uniform(KEY, (2, 15, 15, 1))
    labels = jnp.array([2, 8])
    g_pal = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, NONPOW2, backend="pallas", plan=plan)[0])(params)
    g_jnp = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, NONPOW2)[0])(params)
    for k in g_jnp:
        assert _rel(g_pal[k], g_jnp[k]) <= TOL, k


def test_train_step_improves_loss_on_pallas_backend():
    params = capsnet.init_params(KEY, SMOKE)
    from repro.train.data import DataConfig, mnist_batch
    dc = DataConfig(kind="mnist", global_batch=16)
    losses = []
    for step in range(14):
        b = mnist_batch(dc, step, image_hw=14)
        params, m = capsnet.train_step(params, b["images"], b["labels"],
                                       SMOKE, lr=3e-2, backend="pallas")
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    # per-batch losses are noisy; compare window means like the jnp test
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


# ---------------------------------------------------------------------------
# Backward plan: uhat_hbm_bytes=0, traffic model, PlanError boundary
# ---------------------------------------------------------------------------

def test_backward_plan_reports_zero_uhat_traffic():
    plan = compile_plan(CapsNetConfig(), batch=8, train=True)
    bwd = plan.op(FUSED_NAME + BWD_SUFFIX)
    assert bwd.uhat_hbm_bytes == 0
    assert bwd.kernel == "votes_routing_bwd"
    cfg = CapsNetConfig()
    jd = cfg.num_classes * cfg.class_dim
    fused = votes_routing_bwd_hbm_bytes(8, cfg.num_primary, cfg.primary_dim,
                                        jd, mode=bwd.mode,
                                        iters=cfg.routing_iters)
    relayout = execplan.lane_relayout_hbm_bytes(8, cfg.num_primary,
                                                cfg.primary_dim, jd)
    assert bwd.hbm_bytes == fused + 2 * relayout   # inputs in, grads out
    spilled, uhat = spilled_votes_routing_bwd_hbm_bytes(
        8, cfg.num_primary, cfg.primary_dim, jd)
    # u_hat is written+read and its cotangent round-trips the same way
    assert uhat == 4 * 8 * cfg.num_primary * jd * execplan.ELEM_BYTES
    assert fused < spilled                # the fused backward moves less
    # the backward phases are gated like the forward's
    groups = dict(plan.phase_groups())
    assert groups[FUSED_NAME + BWD_SUFFIX] == (
        "Update+Sum-bwd", "Sum+Squash-bwd", "ClassCaps-FC-bwd")
    assert "Conv1-bwd" in groups and "PrimaryCaps-bwd" in groups


def test_forward_only_backward_fallback_warns_once():
    """A forward-only caller whose backward cannot plan gets a ONE-TIME
    RuntimeWarning naming the exceeded budget (the old silent fallback
    left a later jax.grad running an unvalidated footprint with no
    trace), and the forward still executes and matches the reference."""
    import warnings as _warnings
    from repro.core import analysis
    from repro.kernels.ops import _warn_bwd_fallback_once
    dims = analysis.dims_from_config(NONPOW2)
    jd = dims.num_classes * dims.class_dim
    floor = execplan._fused_streamed_bwd_vmem(
        2, dims.num_primary, execplan._min_block_i(dims.num_primary),
        dims.primary_dim, jd, dims.num_classes, dims.routing_iters)
    plan = compile_plan(NONPOW2, batch=2, vmem_budget=floor - 1)
    u, w, _ = _uv(2, dims.num_primary, dims.primary_dim, jd, seed=77)
    _warn_bwd_fallback_once.cache_clear()
    with pytest.warns(RuntimeWarning, match="no feasible backward") as rec:
        got = ops.votes_routing(u, w, plan=plan)
    assert f"{floor - 1} B" in str(rec[0].message)      # names the budget
    assert FUSED_NAME + BWD_SUFFIX in str(rec[0].message)  # names the op
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")                 # second call: silent
        ops.votes_routing(u, w, plan=plan)
    want = capsnet.routing_by_agreement(
        capsnet.compute_votes(u, w.reshape(dims.num_primary,
                                           dims.num_classes, dims.class_dim,
                                           dims.primary_dim)),
        dims.routing_iters).reshape(2, jd)
    assert _rel(got, want) <= 1e-4


def test_backward_traffic_model_counts_fused_passes():
    """votes_routing_bwd_hbm_bytes streams W iters+4 times in streamed
    mode (the fused replay), not the old 2*iters+4."""
    cfg = CapsNetConfig()
    jd = cfg.num_classes * cfg.class_dim
    stre = votes_routing_bwd_hbm_bytes(2, cfg.num_primary, cfg.primary_dim,
                                       jd, mode="streamed", iters=3)
    res = votes_routing_bwd_hbm_bytes(2, cfg.num_primary, cfg.primary_dim,
                                      jd, mode="resident", iters=3)
    w_sweep = cfg.num_primary * jd * cfg.primary_dim * execplan.ELEM_BYTES
    # streamed - resident = (iters+4-2) W sweeps; u is read once by both
    assert stre - res == (3 + 4 - 2) * w_sweep


def test_smallest_backward_infeasible_budget_raises_at_source():
    """The smallest budget that plans the forward but not the backward
    raises a PlanError naming the backward op and the largest feasible
    batch -- not an opaque validate() footprint complaint."""
    from repro.core import analysis
    dims = analysis.dims_from_config(NONPOW2)
    jd = dims.num_classes * dims.class_dim
    bi = execplan._min_block_i(dims.num_primary)
    floor = execplan._fused_streamed_bwd_vmem(
        2, dims.num_primary, bi, dims.primary_dim, jd, dims.num_classes,
        dims.routing_iters)
    # one byte under the backward floor: the forward still plans...
    fwd_plan = compile_plan(NONPOW2, batch=2, vmem_budget=floor - 1)
    assert fwd_plan.op(FUSED_NAME).mode in ("resident", "streamed")
    # ...but the training plan fails with the named boundary
    with pytest.raises(PlanError) as exc:
        compile_plan(NONPOW2, batch=2, vmem_budget=floor - 1, train=True)
    msg = str(exc.value)
    assert FUSED_NAME + BWD_SUFFIX in msg
    assert "batch=2" in msg
    assert "largest feasible batch is 1" in msg
    # at the floor itself the backward plans (smallest streamed i-tile)
    at_floor = compile_plan(NONPOW2, batch=2, vmem_budget=floor, train=True)
    bwd = at_floor.op(FUSED_NAME + BWD_SUFFIX)
    assert bwd.mode == "streamed" and bwd.block_i == bi


def test_plan_votes_routing_bwd_prefers_resident_when_roomy():
    sched = plan_votes_routing_bwd(600, 4, 80, 10, batch=2, iters=3)
    assert sched.mode == "resident" and sched.n_passes == 2
    tight = plan_votes_routing_bwd(600, 4, 80, 10, batch=2, iters=3,
                                   vmem_budget=1_600_000)
    # fused replay: one W stream per replayed iteration + readout, then
    # seed / reverse / emit -- NOT the old 2-pass replay's 2*iters+4
    assert tight.mode == "streamed" and tight.n_passes == 3 + 4
    assert tight.vmem_bytes <= 1_600_000


def test_train_false_plan_unchanged():
    """Inference plans are untouched: no backward ops, train=False."""
    plan = compile_plan(CapsNetConfig(), batch=2)
    assert not plan.train
    assert [op.name for op in plan.ops] == [
        "Conv1", "PrimaryCaps", FUSED_NAME]
    with pytest.raises(KeyError):
        plan.op(FUSED_NAME + BWD_SUFFIX)
