"""CapsuleNet (the paper's model) behaviour tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import capsnet
from repro.train.data import DataConfig, mnist_batch

CFG = capsnet.CapsNetConfig()
SMOKE = capsnet.CapsNetConfig(image_hw=14, conv1_channels=16,
                              conv1_kernel=5, pc_kernel=3,
                              num_primary_groups=4, primary_dim=4,
                              class_dim=8, decoder_hidden=(32, 64))
KEY = jax.random.PRNGKey(0)


def test_shapes_match_sabour():
    assert CFG.conv1_out == 20
    assert CFG.pc_out == 6
    assert CFG.num_primary == 1152
    assert CFG.pc_channels == 256


def test_forward_shapes():
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (3, 14, 14, 1))
    out = capsnet.forward(params, imgs, SMOKE)
    assert out["class_caps"].shape == (3, 10, 8)
    assert out["lengths"].shape == (3, 10)
    assert out["reconstruction"].shape == (3, 14 * 14)
    assert np.isfinite(np.asarray(out["lengths"])).all()


def test_squash_properties():
    x = jax.random.normal(KEY, (32, 16)) * 10
    v = capsnet.squash(x)
    norms = np.linalg.norm(np.asarray(v), axis=-1)
    assert (norms < 1.0 + 1e-5).all()
    # direction preserved
    cos = np.sum(np.asarray(v) * np.asarray(x), -1)
    assert (cos > 0).all()


@given(scale=st.floats(0.01, 50.0))
@settings(max_examples=20, deadline=None)
def test_squash_monotone_norm(scale):
    x = jnp.ones((1, 8))
    a = np.linalg.norm(np.asarray(capsnet.squash(x * scale)))
    b = np.linalg.norm(np.asarray(capsnet.squash(x * scale * 2)))
    assert b >= a - 1e-6


def test_routing_coupling_sums_to_one():
    uh = 0.1 * jax.random.normal(KEY, (2, 32, 10, 8))
    v = capsnet.routing_by_agreement(uh, 3)
    assert v.shape == (2, 10, 8)
    assert np.isfinite(np.asarray(v)).all()


def test_routing_more_iters_sharpens_agreement():
    # With one dominant vote direction, more routing iterations should not
    # reduce the winning capsule's length.
    k1, k2 = jax.random.split(KEY)
    uh = 0.01 * jax.random.normal(k1, (1, 64, 10, 8))
    strong = jnp.zeros((1, 64, 10, 8)).at[:, :, 3, 0].set(0.5)
    uh = uh + strong
    v1 = capsnet.routing_by_agreement(uh, 1)
    v3 = capsnet.routing_by_agreement(uh, 3)
    n1 = np.linalg.norm(np.asarray(v1[0, 3]))
    n3 = np.linalg.norm(np.asarray(v3[0, 3]))
    assert n3 >= n1 - 1e-4


def test_margin_loss_zero_when_perfect():
    lengths = jnp.array([[0.95, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05,
                          0.05, 0.05]])
    loss = capsnet.margin_loss(lengths, jnp.array([0]))
    assert float(loss) == pytest.approx(0.0, abs=1e-6)


def test_margin_loss_penalizes_wrong_class():
    lengths = jnp.array([[0.95, 0.8, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05,
                          0.05, 0.05]])
    loss = capsnet.margin_loss(lengths, jnp.array([0]))
    assert float(loss) > 0.1


def test_training_reduces_loss():
    params = capsnet.init_params(KEY, SMOKE)
    dc = DataConfig(kind="mnist", global_batch=16)
    losses, accs = [], []
    for step in range(120):
        b = mnist_batch(dc, step, image_hw=14)
        params, m = capsnet.train_step(params, b["images"], b["labels"],
                                       SMOKE, lr=3e-2)
        losses.append(float(m["loss"]))
        accs.append(float(m["accuracy"]))
    assert np.isfinite(losses).all()
    # plain-SGD margin loss falls slowly but monotonically on average
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.01
    assert np.mean(accs[-40:]) > 0.12      # above 10% chance (batch=16 noise)


def test_decoder_masks_with_labels_when_given():
    """Training semantics (Sabour et al.): the decoder reconstructs the
    LABELED capsule, not the argmax one."""
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (4, 14, 14, 1))
    out = capsnet.forward(params, imgs, SMOKE)
    pred = np.asarray(jnp.argmax(out["lengths"], -1))
    wrong = jnp.asarray((pred + 1) % SMOKE.num_classes)
    out_lbl = capsnet.forward(params, imgs, SMOKE, labels=wrong)
    # class capsules identical; only the decoder mask changes
    np.testing.assert_array_equal(np.asarray(out["class_caps"]),
                                  np.asarray(out_lbl["class_caps"]))
    diff = np.abs(np.asarray(out["reconstruction"])
                  - np.asarray(out_lbl["reconstruction"])).max()
    assert diff > 1e-6
    # masking with the predicted class reproduces the argmax behaviour
    out_pred = capsnet.forward(params, imgs, SMOKE, labels=jnp.asarray(pred))
    np.testing.assert_allclose(np.asarray(out_pred["reconstruction"]),
                               np.asarray(out["reconstruction"]),
                               rtol=1e-6, atol=1e-6)


def test_recon_gradient_flows_through_labeled_capsule():
    """d(recon loss)/d(class capsules) is nonzero ONLY at the labeled
    capsule -- the regression the unconditional-argmax mask broke."""
    params = capsnet.init_params(KEY, SMOKE)
    v = jax.random.normal(KEY, (2, SMOKE.num_classes, SMOKE.class_dim))
    labels = jnp.array([3, 7])

    def recon_sum(v):
        return jnp.sum(capsnet.decode(params, v, SMOKE, labels=labels))

    g = np.asarray(jax.grad(recon_sum)(v))
    for b, lbl in enumerate([3, 7]):
        assert np.abs(g[b, lbl]).max() > 0.0
        others = np.delete(g[b], lbl, axis=0)
        np.testing.assert_array_equal(others, np.zeros_like(others))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_total_loss_recon_grad_only_through_labeled_capsule(backend):
    """Through ``total_loss`` (not just ``decode``), the masked
    reconstruction term backpropagates ONLY through the labeled capsule's
    pose -- on BOTH backends, now that ``total_loss`` takes ``backend=``
    and the Pallas path is differentiable.  Isolate the term by
    differencing out the margin loss (margin depends only on lengths, so
    its gradient w.r.t. the class capsules is mask-independent)."""
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (2, 14, 14, 1))
    labels = jnp.array([3, 7])

    def loss(params, recon_weight):
        return capsnet.total_loss(params, imgs, labels, SMOKE,
                                  recon_weight=recon_weight,
                                  backend=backend)[0]

    g_with = jax.grad(loss)(params, 1.0)
    g_without = jax.grad(loss)(params, 0.0)
    # recon-term gradient w.r.t. the ClassCaps weights, per capsule j:
    # cc_w is [I, J, D, C], so axis 1 indexes the class capsule.
    g_rec = np.asarray(g_with["cc_w"]) - np.asarray(g_without["cc_w"])
    per_caps = np.abs(g_rec).max(axis=(0, 2, 3))
    labeled = sorted(np.asarray(labels).tolist())
    # unlabeled capsules sit at the fp32 differencing noise floor, three
    # orders of magnitude below the labeled ones
    nonzero = [j for j in range(SMOKE.num_classes)
               if per_caps[j] > 1e-2 * per_caps.max()]
    assert nonzero == labeled, (nonzero, per_caps)
    # and the decoder itself only sees the labeled poses
    assert np.abs(np.asarray(g_with["dec_w1"])).max() > 0.0


def test_total_loss_reconstructs_labeled_capsule():
    params = capsnet.init_params(KEY, SMOKE)
    imgs = jax.random.uniform(KEY, (3, 14, 14, 1))
    labels = jnp.array([1, 2, 3])
    _, metrics = capsnet.total_loss(params, imgs, labels, SMOKE)
    out = capsnet.forward(params, imgs, SMOKE, labels=labels)
    flat = imgs.reshape(3, -1)
    want = jnp.mean(jnp.sum(jnp.square(out["reconstruction"] - flat), -1))
    assert float(metrics["recon_loss"]) == pytest.approx(float(want))


def test_pallas_capsnet_head_equivalence():
    """core.capsnet votes+routing == the plan's fused votes_routing."""
    from repro.core.execplan import compile_plan
    from repro.kernels import ops
    cfg = SMOKE
    params = capsnet.init_params(KEY, cfg)
    u = capsnet.squash(jax.random.normal(KEY, (2, cfg.num_primary,
                                               cfg.primary_dim)))
    want_votes = capsnet.compute_votes(u, params["cc_w"])
    want_v = capsnet.routing_by_agreement(want_votes, cfg.routing_iters)
    lay = cfg.routing_stack()[0]
    w = params["cc_w"].reshape(lay.in_caps, lay.jd, lay.in_dim)
    got_v = ops.votes_routing(u, w, lay, compile_plan(cfg, batch=2))
    np.testing.assert_allclose(np.asarray(got_v),
                               np.asarray(want_v.reshape(2, -1)),
                               rtol=1e-5, atol=1e-5)
