"""``im2col_patches`` against the plain extraction ``_patches_xla``.

The patch matrix is built by one of two formulations, picked from the
channel width: by pixel rows when C is not a multiple of 128, by
lane-dense [B, P, C] slabs when it is.  Both only move data, so every
case must equal the reference bit for bit; the structure tests pin that
neither route brings back the slow forms (width-1 lane pieces, a
[..., KH*KW, C] intermediate), and the gradient tests pin that the
extraction's transpose is still ``col2im_patches``.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.kernels.conv_im2col import (_patches_xla, col2im_patches,
                                       im2col_patches)

# (batch, H, W, C), kernel, stride: every channel width, stride, kernel
# and batch below, on inputs just large enough for the kernel, plus
# capsnet-mnist's two layers at their published sizes.
GRID = [((b, k + 4, k + 5, c), k, s)
        for c, s, k, b in itertools.product((1, 3, 128, 256), (1, 2),
                                             (3, 9), (1, 16))]
MNIST = {"conv1": ((16, 28, 28, 1), 9, 1),
         "primary_caps": ((16, 20, 20, 256), 9, 2)}


def _case_id(case):
    (b, h, w, c), k, s = case
    return f"b{b}-{h}x{w}x{c}-k{k}-s{s}"


def _input(shape, salt=0):
    return jax.random.normal(jax.random.PRNGKey(sum(shape) + salt), shape)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _assert_bitwise(shape, k, s):
    x = _input(shape)
    got = im2col_patches(x, kh=k, kw=k, stride=s)
    want = _patches_xla(x, k, k, s)
    assert got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("case", GRID, ids=_case_id)
def test_patches_bitwise_equal_reference(case):
    _assert_bitwise(*case)


@pytest.mark.parametrize("layer", sorted(MNIST))
def test_patches_bitwise_equal_reference_capsnet_mnist(layer):
    _assert_bitwise(*MNIST[layer])


def test_patches_keep_signed_zeros_and_non_finite_pixels_in_place():
    """Data movement, not arithmetic: a -0.0 stays -0.0 and an inf pixel
    is one inf entry per patch that covers it, never a NaN window."""
    x = np.zeros((1, 12, 12, 1), np.float32)
    x[0, 5, 5, 0] = -0.0
    x[0, 0, 0, 0] = np.inf
    x[0, 7, 3, 0] = np.nan
    got = im2col_patches(jnp.asarray(x), kh=9, kw=9, stride=1)
    np.testing.assert_array_equal(_bits(got), _bits(_patches_xla(x, 9, 9, 1)))


# -- structure: which ops each route is built from -------------------------

def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


def _eqns_of(fn, shape, k, s):
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    return list(_eqns(jax.make_jaxpr(lambda x: fn(x, k, k, s))(x).jaxpr))


def _route(x, kh, kw, stride):
    return im2col_patches(x, kh=kh, kw=kw, stride=stride)


def _width1_concats(eqns):
    return [e for e in eqns if e.primitive.name == "concatenate"
            and any(v.aval.shape[-1] == 1 for v in e.invars)]


def _tap_by_channel(eqns, taps, c):
    return [v.aval.shape for e in eqns for v in e.outvars
            if v.aval.shape[-2:] == (taps, c)]


@pytest.mark.parametrize("layer", ["conv1"])
def test_narrow_route_concatenates_no_width1_pieces(layer):
    shape, k, s = MNIST[layer]
    assert _width1_concats(_eqns_of(_patches_xla, shape, k, s)), \
        "the check no longer sees the per-tap width-1 form it guards"
    assert not _width1_concats(_eqns_of(_route, shape, k, s))


@pytest.mark.parametrize("layer", ["primary_caps"])
def test_wide_route_has_no_tap_by_channel_intermediate(layer):
    shape, k, s = MNIST[layer]
    c = shape[-1]
    assert _tap_by_channel(_eqns_of(_patches_xla, shape, k, s), k * k, c), \
        "the check no longer sees the [..., KH*KW, C] form it guards"
    assert not _tap_by_channel(_eqns_of(_route, shape, k, s), k * k, c)
    assert not _width1_concats(_eqns_of(_route, shape, k, s))


# -- gradient: the extraction's transpose is still col2im_patches ----------

GRAD_CASES = [((2, 9, 10, c), 3, s) for c in (1, 3, 128, 256)
              for s in (1, 2)] + [MNIST["conv1"], MNIST["primary_caps"]]


def _vjp_and_col2im(shape, k, s, dp_of):
    x = _input(shape, salt=1)
    b, h, w, _ = shape
    out, pull = jax.vjp(lambda x: im2col_patches(x, kh=k, kw=k, stride=s), x)
    dp = dp_of(out.shape)
    got = pull(dp)[0]
    want = col2im_patches(dp, kh=k, kw=k, stride=s, h=h, w=w)
    return dp, np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("case", GRAD_CASES, ids=_case_id)
def test_patches_vjp_equals_col2im_on_exact_sums(case):
    """Small-integer cotangents make every summation order exact, so the
    VJP must scatter each patch entry to exactly col2im's pixel."""
    _, got, want = _vjp_and_col2im(*case, lambda shape: jax.random.randint(
        jax.random.PRNGKey(3), shape, -8, 9).astype(jnp.float32))
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("case", GRAD_CASES, ids=_case_id)
def test_patches_vjp_equals_col2im_within_summation_rounding(case):
    """Random cotangents: the two transposes may add a pixel's at most
    KH*KW terms in another order, so each may be off by (n-1) eps times
    the sum of the terms' magnitudes, and they by twice that."""
    shape, k, s = case
    dp, got, want = _vjp_and_col2im(*case, lambda shape: jax.random.normal(
        jax.random.PRNGKey(4), shape))
    b, h, w, _ = shape
    mags = np.asarray(col2im_patches(jnp.abs(dp), kh=k, kw=k, stride=s,
                                     h=h, w=w))
    bound = 2 * (k * k - 1) * np.finfo(np.float32).eps * mags
    assert np.all(np.abs(got - want) <= bound)
