"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import squash as squash_mod
from repro.kernels.flash_attention import flash_attention

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype=jnp.float32, k=KEY, scale=1.0):
    return (scale * jax.random.normal(k, shape)).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def squash(x, block_rows=1024):
    """The squash kernel on a block of the test's choosing."""
    return squash_mod.squash(x, block_rows=block_rows,
                             interpret=ops.should_interpret())


# ---------------------------------------------------------------------------
# squash / rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 16), (4, 100, 16), (1, 1152, 8),
                                   (2, 3, 5, 8)])
def test_squash(shape):
    x = rand(shape)
    np.testing.assert_allclose(np.asarray(squash(x)),
                               np.asarray(ref.squash(x)), rtol=1e-5,
                               atol=1e-6)


def test_squash_norm_bound():
    x = 100.0 * rand((16, 32))
    v = squash(x)
    norms = np.linalg.norm(np.asarray(v), axis=-1)
    assert (norms <= 1.0 + 1e-5).all()


def test_squash_ragged_rows():
    x = rand((7, 5, 8))                      # 35 rows, not a block multiple
    got = squash(x, block_rows=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.squash(x)),
                               rtol=1e-5, atol=1e-6)


def test_squash_single_canonical_implementation():
    """The kernel and the fused routing kernels share core.capsnet.squash
    (ref.squash stays an independent oracle)."""
    from repro.core.capsnet import squash as canonical
    from repro.kernels import primary_routing, votes_routing
    assert squash_mod.squash_reference is canonical
    assert votes_routing.squash is canonical
    assert primary_routing.squash is canonical
    x = rand((13, 16), scale=5.0)
    np.testing.assert_allclose(np.asarray(canonical(x)),
                               np.asarray(ref.squash(x)),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(squash(x)),
                               np.asarray(canonical(x)),
                               rtol=1e-5, atol=1e-6)
    # the wrapper: the primary capsules, blocked over the plan's rows
    from repro.core.capsnet import CapsNetConfig
    from repro.core.execplan import compile_plan
    u = rand((2, 1152, 8), scale=5.0)
    np.testing.assert_allclose(
        np.asarray(ops.squash(u, compile_plan(CapsNetConfig(), batch=2))),
        np.asarray(canonical(u)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rows,d", [(8, 64), (1024, 512), (7, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rows, d, dtype):
    x = rand((rows, d), dtype)
    w = rand((d,), scale=0.1)
    got = ops.rmsnorm(x, w)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tq,tk,win,cap,causal", [
    (128, 128, None, None, True),
    (256, 256, 64, None, True),
    (128, 128, None, 50.0, True),
    (1, 256, None, None, True),          # decode
    (8, 264, 32, 30.0, True),            # non-pow2 kv + window + softcap
    (64, 64, None, None, False),         # bidirectional
    (96, 96, 16, None, True),
])
def test_flash_attention(tq, tk, win, cap, causal):
    ks = jax.random.split(KEY, 3)
    q = rand((2, 4, tq, 64), k=ks[0])
    k = rand((2, 4, tk, 64), k=ks[1])
    v = rand((2, 4, tk, 64), k=ks[2])
    got = ops.flash_attention(q, k, v, causal=causal, window=win,
                              softcap=cap)
    want = ref.attention(q, k, v, causal=causal, window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [64, 128, 256])
def test_flash_attention_head_dims(d):
    ks = jax.random.split(KEY, 3)
    q = rand((1, 2, 128, d), k=ks[0])
    k = rand((1, 2, 128, d), k=ks[1])
    v = rand((1, 2, 128, d), k=ks[2])
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_block_sweep():
    ks = jax.random.split(KEY, 3)
    q, k, v = (rand((1, 2, 256, 64), k=kk) for kk in ks)
    want = ref.attention(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=ops.should_interpret())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_flash_vs_model_attention():
    """Flash kernel == the model's grouped_attention on expanded heads."""
    from repro.models.attention import grouped_attention
    ks = jax.random.split(KEY, 3)
    b, h, t, d = 2, 4, 64, 32
    q = rand((b, t, h, d), k=ks[0])
    k = rand((b, t, h, d), k=ks[1])
    v = rand((b, t, h, d), k=ks[2])
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    want = grouped_attention(q, k, v, pos, pos, causal=True, window=None,
                             softcap=None, scale=d ** -0.5)
    got = ops.flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(got.transpose(0, 2, 1, 3)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
