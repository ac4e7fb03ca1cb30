"""The none-of-the-above routing category (Sabour et al. 2017, section 7):
the leaky coupling itself, the fused votes+routing kernel in both lane
layouts and both schedules, the pipelined PrimaryCaps->routing kernel and
the reversible residual segment against the jnp leaky routing, forward
and VJP; the default path unchanged; the plan's schedules and bytes
unchanged by the leak; the split oracles refusing it; the auditor
tracing the leaky kernels."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config
from repro.core import capsnet
from repro.core.capsnet import CapsNetConfig, ResCapsBlock
from repro.core.execplan import BWD_SUFFIX, PIPE_NAME, compile_plan
from repro.kernels import ops, ref
from repro.kernels import routing as routing_kernel
from repro.verify import lowering

KEY = jax.random.PRNGKey(0)
SABOUR = get_config("capsnet-cifar10-sabour")
SMOKE = get_smoke_config("capsnet-cifar10-sabour")


def _uv(b, i, c, jd, seed=0):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, seed))
    return (0.5 * jax.random.normal(k1, (b, i, c)),
            0.3 * jax.random.normal(k2, (i, jd, c)))


def _routing_ref(u, w, j, nota):
    b, i, _ = u.shape
    uh = jnp.einsum("bic,inc->bin", u, w).reshape(b, i, j, -1)
    return capsnet.routing_by_agreement(uh, 3, nota=nota).reshape(b, -1)


def _assert_close(got, want, tol=1e-5):
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# The coupling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("axis", [1, 2, -1])
def test_leaky_coupling_is_a_softmax_over_zero_and_b_minus_the_zero(axis):
    b = jax.random.normal(KEY, (3, 7, 10)) - 2.0
    b = b.at[0, 0].add(60.0)                  # an exponent that overflows
    got = capsnet.couplings(b, axis=axis, nota=True)
    padded = jnp.concatenate(
        [jnp.zeros_like(jax.lax.slice_in_dim(b, 0, 1, axis=axis)), b],
        axis=axis)
    want = jax.lax.slice_in_dim(jax.nn.softmax(padded, axis=axis), 1, None,
                                axis=axis)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)
    sums = np.asarray(jnp.sum(got, axis=axis))
    assert np.all(sums <= 1.0) and np.any(sums < 0.9)
    np.testing.assert_array_equal(
        np.asarray(capsnet.couplings(b, axis=axis)),
        np.asarray(jax.nn.softmax(b, axis=axis)))


def test_leaky_coupling_vjp_is_the_softmax_formula_of_its_output():
    """The dropped column carries no cotangent, so the VJP given the
    output c is the plain softmax's: c * (dc - sum_k c_k dc_k)."""
    b = 2.0 * jax.random.normal(KEY, (4, 10))
    dc = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 10))
    c, pull = jax.vjp(lambda x: capsnet.couplings(x, nota=True), b)
    want = c * (dc - jnp.sum(c * dc, axis=-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(pull(dc)[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Kernels against the jnp leaky routing, forward and VJP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["resident", "streamed"])
@pytest.mark.parametrize("lanes,b,i,c,j,d,bi", [
    ("caps", 2, 100, 8, 10, 16, 32),      # ragged final i-block
    ("caps", 3, 64, 4, 5, 8, 64),
    ("classes", 2, 100, 4, 16, 4, 16),    # ragged final i-block
    ("classes", 1, 27, 8, 10, 8, 27),     # one block, I not a multiple of 8
])
def test_votes_routing_leak_matches_reference_fwd_and_grad(mode, lanes, b,
                                                           i, c, j, d, bi):
    u, w = _uv(b, i, c, j * d, seed=i + j)
    dv = jax.random.normal(jax.random.fold_in(KEY, 7), (b, j * d))

    def fused(u, w):
        return ops.votes_routing(u, w, iters=3, num_classes=j, mode=mode,
                                 block_i=bi, lanes=lanes, bwd_mode=mode,
                                 bwd_block_i=bi, bwd_lanes=lanes, nota=True)

    got = fused(u, w)
    want = _routing_ref(u, w, j, nota=True)
    _assert_close([got], [want], 1e-6)
    plain = _routing_ref(u, w, j, nota=False)
    assert float(jnp.max(jnp.abs(want - plain))) > 1e-3
    g_got = jax.grad(lambda u, w: jnp.sum(fused(u, w) * dv), (0, 1))(u, w)
    g_want = jax.grad(lambda u, w: jnp.sum(
        _routing_ref(u, w, j, True) * dv), (0, 1))(u, w)
    _assert_close(g_got, g_want)


def _net(b, h, cin, kh, stride, n_ch, caps_dim, j, d, seed=0):
    oh = (h - kh) // stride + 1
    i_dim = oh * oh * (n_ch // caps_dim)
    k = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    return (jax.random.uniform(k[0], (b, h, h, cin)),
            0.2 * jax.random.normal(k[1], (kh, kh, cin, n_ch)),
            0.1 * jax.random.normal(k[2], (n_ch,)),
            0.3 * jax.random.normal(k[3], (i_dim, j * d, caps_dim)))


def _pipe_ref(x, w_pc, b_pc, w_cc, *, stride, j, caps_dim):
    """PrimaryCaps conv + squash, then the jnp leaky routing."""
    y = jax.lax.conv_general_dilated(
        x, w_pc, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) + b_pc
    u = capsnet.squash(y.reshape(x.shape[0], -1, caps_dim))
    return _routing_ref(u, w_cc, j, nota=True)


@pytest.mark.parametrize("mode,bwd_lanes", [("resident", "caps"),
                                            ("streamed", "caps"),
                                            ("streamed", "classes")])
def test_primary_caps_routing_leak_matches_reference_fwd_and_grad(
        mode, bwd_lanes):
    x, w_pc, b_pc, w_cc = _net(2, 10, 8, 6, 2, 12, 4, 4, 8, seed=3)

    def fused(*a):
        return ops.primary_routing(*a, stride=2, iters=3, num_classes=4,
                                   mode=mode, block_i=8, block_k=32,
                                   bwd_mode=mode, bwd_block_i=8,
                                   bwd_lanes=bwd_lanes, nota=True)

    def want(*a):
        return _pipe_ref(*a, stride=2, j=4, caps_dim=4)

    args = (x, w_pc, b_pc, w_cc)
    _assert_close([fused(*args)], [want(*args)], 1e-6)
    argnums = (0, 1, 2, 3)
    _assert_close(
        jax.grad(lambda *a: jnp.sum(jnp.sin(fused(*a))), argnums)(*args),
        jax.grad(lambda *a: jnp.sum(jnp.sin(want(*a))), argnums)(*args))


def test_residual_stack_with_the_leak_matches_jnp_fwd_and_grad():
    """The reversible segment's halves route with the leak too."""
    cfg = dataclasses.replace(
        get_smoke_config("capsnet-mnist"), none_of_the_above=True,
        caps_layers=(ResCapsBlock(),), use_decoder=False)
    assert all(lay.nota for lay in cfg.routing_stack())
    params = capsnet.init_params(KEY, cfg)
    imgs = jax.random.uniform(KEY, (2, cfg.image_hw, cfg.image_hw, 1))
    labels = jnp.array([1, 4])
    plan = compile_plan(cfg, batch=2, train=True, pipeline=True)

    def loss(p, backend):
        return capsnet.total_loss(p, imgs, labels, cfg, backend=backend,
                                  plan=plan if backend == "pallas" else None
                                  )[0]

    _assert_close(
        [capsnet.forward(params, imgs, cfg, backend="pallas",
                         plan=plan)["lengths"]],
        [capsnet.forward(params, imgs, cfg)["lengths"]], 1e-6)
    gp, gr = (jax.grad(loss)(params, be) for be in ("pallas", "jnp"))
    _assert_close([gp[k] for k in gr], [gr[k] for k in gr], 1e-4)


def test_smoke_program_with_the_leak_matches_jnp_fwd_and_grad():
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (3, SMOKE.image_hw, SMOKE.image_hw, 3))
    labels = jnp.array([1, 7, 3])
    plan = compile_plan(SMOKE, batch=3, train=True, pipeline=True)
    assert any(op.name == PIPE_NAME and op.nota for op in plan.ops)
    gp = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, SMOKE, backend="pallas", plan=plan)[0])(params)
    gr = jax.grad(lambda p: capsnet.total_loss(
        p, imgs, labels, SMOKE, backend="jnp")[0])(params)
    _assert_close([gp[k] for k in gr], [gr[k] for k in gr], 1e-4)


# ---------------------------------------------------------------------------
# The default path and the plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["capsnet-mnist", "capsnet-cifar10"])
def test_default_jaxprs_equal_explicit_false(arch):
    """At the default the serving forward's and the train step's jaxprs
    are those of ``none_of_the_above=False`` given explicitly, and name
    no leaky kernel."""
    cfg = get_smoke_config(arch)
    off = dataclasses.replace(cfg, none_of_the_above=False)
    params = capsnet.init_params(KEY, cfg)
    imgs = jnp.zeros((2, cfg.image_hw, cfg.image_hw, cfg.in_channels))
    labels = jnp.array([0, 1])

    def jaxprs(c):
        plan = compile_plan(c, batch=2, pipeline=True)
        tplan = compile_plan(c, batch=2, pipeline=True, train=True)
        fwd = jax.make_jaxpr(lambda p, x: capsnet.forward(
            p, x, c, backend="pallas", plan=plan)["lengths"])(params, imgs)
        step = jax.make_jaxpr(jax.grad(lambda p, x, y: capsnet.total_loss(
            p, x, y, c, backend="pallas", plan=tplan)[0]))(
            params, imgs, labels)
        return str(fwd), str(step)

    default = jaxprs(cfg)
    assert default == jaxprs(off)
    assert not any("_nota" in j for j in default)


def test_leaky_kernels_run_under_jits_of_their_own_names():
    """A device trace names a Pallas call after the jit that makes it:
    the leaky pipe kernel is ``primary_caps_routing_nota``."""
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jnp.zeros((2, SMOKE.image_hw, SMOKE.image_hw, 3))
    plan = compile_plan(SMOKE, batch=2, pipeline=True)
    text = str(jax.make_jaxpr(lambda p, x: capsnet.forward(
        p, x, SMOKE, backend="pallas", plan=plan)["lengths"])(params, imgs))
    assert "name=primary_caps_routing_nota" in text
    u, w = _uv(2, 16, 4, 40)
    text = str(jax.make_jaxpr(lambda u, w: ops.votes_routing(
        u, w, iters=2, num_classes=10, nota=True))(u, w))
    assert "name=votes_routing_nota" in text


@pytest.mark.parametrize("train", [False, True])
@pytest.mark.parametrize("arch,batch", [("capsnet-cifar10-sabour", 16),
                                        ("capsnet-cifar10-sabour", 22),
                                        ("capsnet-mnist", 16),
                                        ("capsnet-svhn", 4)])
def test_plan_schedules_and_bytes_do_not_depend_on_the_leak(arch, batch,
                                                            train):
    cfg = get_config(arch)
    on = dataclasses.replace(cfg, none_of_the_above=True)
    off = dataclasses.replace(cfg, none_of_the_above=False)
    p_on, p_off = (compile_plan(c, batch=batch, pipeline=True, train=train)
                   for c in (on, off))
    routing = [op for op in p_on.ops if op.mode is not None]
    assert routing and all(op.nota for op in routing)
    assert [dataclasses.replace(op, nota=False) for op in p_on.ops] \
        == list(p_off.ops)
    assert p_on.summary() == p_off.summary()


def test_sabour_plan_is_pipelined_at_sixteen_slots_on_the_budget_edge():
    plan = compile_plan(SABOUR, batch=16, pipeline=True, train=True)
    pipe = plan.op(PIPE_NAME)
    assert (pipe.mode, pipe.block_i, pipe.vmem_bytes) == \
        ("streamed", 256, plan.vmem_budget)
    assert plan.op("ClassCaps-Routing" + BWD_SUFFIX).mode == "streamed"
    assert not any(op.kernel == "primary_routing" for op in compile_plan(
        SABOUR, batch=24, pipeline=True).ops)


# ---------------------------------------------------------------------------
# The split oracles refuse the leak; the auditor traces it
# ---------------------------------------------------------------------------

def test_split_oracles_refuse_the_leak():
    u, w = _uv(2, 16, 4, 40)
    uh = ref.caps_votes(u, w)
    with pytest.raises(NotImplementedError, match="none-of-the-above"):
        routing_kernel.routing(uh, iters=2, num_classes=10, nota=True,
                               interpret=True)
    with pytest.raises(NotImplementedError, match="none-of-the-above"):
        ref.routing(uh.reshape(2, 16, 10, 4), 2, nota=True)
    plan = compile_plan(SMOKE, batch=2)
    with pytest.raises(NotImplementedError, match="none-of-the-above"):
        ops.routing(uh, plan=plan)


@pytest.mark.parametrize("train", [False, True])
def test_auditor_traces_the_leaky_kernels(monkeypatch, train):
    from repro.kernels import primary_routing as pr
    from repro.kernels import votes_routing as vr
    seen = []
    for mod, fn in ((pr, "_pr_apply"), (vr, "_vr_apply"), (vr, "_vr_grad")):
        real = getattr(mod, fn)

        def spy(st, *a, _real=real, _fn=fn):
            seen.append((_fn, st.nota))
            return _real(st, *a)

        monkeypatch.setattr(mod, fn, spy)
    for pipeline in (True, False):
        plan = compile_plan(SABOUR, batch=1, pipeline=pipeline, train=train)
        rep = lowering.audit_plan(plan, label="sabour")
        assert rep.ok, rep.failures()
    assert seen and all(nota for _, nota in seen)
    assert {fn for fn, _ in seen} == (
        {"_pr_apply", "_vr_apply", "_vr_grad"} if train
        else {"_pr_apply", "_vr_apply"})


def test_config_registers_the_published_widths():
    assert SABOUR.none_of_the_above and SMOKE.none_of_the_above
    assert (SABOUR.conv1_out, SABOUR.pc_out, SABOUR.num_primary) == \
        (16, 4, 1024)
    shapes = jax.eval_shape(lambda: capsnet.init_params(KEY, SABOUR))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == 14369472
    assert CapsNetConfig().none_of_the_above is False
