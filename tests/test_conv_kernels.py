"""Plan-driven im2col conv kernels vs the XLA convolution oracle.

Covers ragged M/N grid tiles, K zero-padding, strided patch extraction,
the bias/ReLU/squash epilogues, and the plan-driven ``ops.conv2d`` wrapper.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.capsnet import squash
from repro.kernels import ops
from repro.kernels.conv_im2col import (conv2d_im2col, im2col_patches,
                                       matmul_bias_act)

KEY = jax.random.PRNGKey(0)


def _conv_ref(x, w, b, stride):
    out = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out + b


@pytest.mark.parametrize(
    "batch,hw,k,cin,cout,stride",
    [
        (2, 11, 3, 3, 24, 1),      # ragged M and N vs 8/128-ish tiles
        (3, 14, 5, 7, 20, 2),      # strided, K=175 forces zero-padding
        (1, 9, 4, 2, 12, 3),       # stride > kernel overlap, tiny channels
        (2, 28, 9, 1, 32, 1),      # MNIST Conv1 shape (narrow)
        (2, 11, 3, 128, 16, 2),    # lane-dense channels: Pallas extraction
    ])
def test_conv_im2col_matches_lax(batch, hw, k, cin, cout, stride):
    x = jax.random.uniform(KEY, (batch, hw, hw, cin))
    w = 0.1 * jax.random.normal(KEY, (k, k, cin, cout))
    b = 0.1 * jax.random.normal(KEY, (cout,))
    want = _conv_ref(x, w, b, stride)
    got = conv2d_im2col(x, w, b, stride=stride,
                        block_m=8, block_k=16, block_n=8, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_conv_relu_epilogue():
    x = jax.random.uniform(KEY, (2, 10, 10, 3))
    w = 0.5 * jax.random.normal(KEY, (3, 3, 3, 16))
    b = jnp.linspace(-0.5, 0.5, 16)
    want = jnp.maximum(_conv_ref(x, w, b, 1), 0.0)
    got = conv2d_im2col(x, w, b, stride=1, epilogue="relu",
                        block_m=16, block_k=8, block_n=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_conv_squash_epilogue_matches_unfused():
    """Fused per-capsule squash == conv + bias, then squash over dim-4
    channel groups (the PrimaryCaps activation)."""
    pd = 4
    x = jax.random.uniform(KEY, (2, 12, 12, 5))
    w = 0.3 * jax.random.normal(KEY, (3, 3, 5, 24))
    b = 0.1 * jax.random.normal(KEY, (24,))
    pre = _conv_ref(x, w, b, 2)
    want = squash(pre.reshape(*pre.shape[:-1], 24 // pd, pd)).reshape(pre.shape)
    got = conv2d_im2col(x, w, b, stride=2, epilogue="squash", squash_dim=pd,
                        block_m=8, block_k=16, block_n=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_squash_epilogue_rejects_misaligned_tile():
    x = jax.random.uniform(KEY, (1, 8, 8, 2))
    w = jax.random.normal(KEY, (3, 3, 2, 12))
    b = jnp.zeros((12,))
    with pytest.raises(ValueError):
        conv2d_im2col(x, w, b, epilogue="squash", squash_dim=5, block_n=8,
                      interpret=True)
    with pytest.raises(ValueError):            # default squash_dim=0: clear
        conv2d_im2col(x, w, b, epilogue="squash",  # error, not ZeroDivision
                      interpret=True)


def test_unknown_epilogue_rejected():
    with pytest.raises(ValueError):
        matmul_bias_act(jnp.ones((4, 4)), jnp.ones((4, 4)), jnp.ones((4,)),
                        epilogue="gelu", interpret=True)


@pytest.mark.parametrize("c,stride", [(2, 2), (128, 2), (128, 1)])
def test_patches_match_manual_extraction(c, stride):
    """Patch column order is (kh, kw, c)-major -- what w.reshape expects,
    for narrow and lane-wide channel axes, strided or not."""
    b, hw, k = 2, 7, 3
    x = np.asarray(jax.random.uniform(KEY, (b, hw, hw, c)))
    oh = (hw - k) // stride + 1
    got = np.asarray(im2col_patches(jnp.asarray(x), kh=k, kw=k,
                                    stride=stride))
    assert got.shape == (b, oh * oh, k * k * c)
    for bi in range(b):
        for i in range(oh):
            for j in range(oh):
                patch = x[bi, i * stride:i * stride + k,
                          j * stride:j * stride + k, :]
                np.testing.assert_array_equal(got[bi, i * oh + j],
                                              patch.reshape(-1))


def _plan():
    from repro.core.capsnet import CapsNetConfig
    from repro.core.execplan import compile_plan
    cfg = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                        pc_kernel=3, num_primary_groups=4, primary_dim=4,
                        class_dim=8, use_decoder=False)
    return compile_plan(cfg, batch=2)


def test_ops_conv2d_primarycaps_takes_stride_and_squash_from_plan():
    """The PrimaryCaps op runs at the config's stride with the squash
    epilogue its plan op fuses, over the op's tiles."""
    plan = _plan()
    op = plan.op("PrimaryCaps")
    assert op.fuses_squash and plan.cfg.pc_stride == 2
    x = jax.random.uniform(KEY, (2, 10, 10, 16))
    w = 0.1 * jax.random.normal(KEY, (3, 3, 16, 16))
    b = 0.1 * jax.random.normal(KEY, (16,))
    pre = _conv_ref(x, w, b, 2)
    want = squash(pre.reshape(2, 4, 4, 4, 4)).reshape(pre.shape)
    got = ops.conv2d(x, w, b, plan, "PrimaryCaps")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ops_conv2d_uses_plan_op_blocks():
    plan = _plan()
    params_w = 0.1 * jax.random.normal(KEY, (5, 5, 1, 16))
    params_b = jnp.zeros((16,))
    x = jax.random.uniform(KEY, (2, 14, 14, 1))
    want = jnp.maximum(_conv_ref(x, params_w, params_b, 1), 0.0)
    got = ops.conv2d(x, params_w, params_b, plan, "Conv1")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
