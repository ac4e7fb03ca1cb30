"""Plain reference of the CapsNet family with the none-of-the-above
category, in jax.numpy and float32.

Sabour et al. 2017 (arXiv:1710.09829, section 7) add to the routing
softmax of their CIFAR-10 network a category that no class capsule
stands for.  The authors' code (``_leaky_routing`` in research/capsules
of github.com/Sarasra/models) appends a zero logit before the softmax
over the classes and drops its column afterwards:

    c_ij = exp(b_ij) / (1 + sum_k exp(b_ik))

Everything else is the ``capsnet`` family (``capsnet.py`` beside this
file): the same seeded parameters and inputs, the same precisions, the
same loss and AdamW.  That file is loaded here as a private copy whose
``routing`` is this file's, so its ``class_capsules``, ``lengths`` and
``loss`` route through the category.  Like it, this file imports
nothing of the system under test.
"""

from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp


def _load_capsnet():
    path = pathlib.Path(__file__).resolve().with_name("capsnet.py")
    spec = importlib.util.spec_from_file_location("capsnet_nota_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_capsnet()

HIGHEST = _base.HIGHEST
PRECISIONS = _base.PRECISIONS
RECON_WEIGHT = _base.RECON_WEIGHT
conv1_out = _base.conv1_out
pc_out = _base.pc_out
num_primary = _base.num_primary
routing_stack = _base.routing_stack
param_shapes = _base.param_shapes
image_shape = _base.image_shape
key_of = _base.key_of
init_params = _base.init_params
request_images = _base.request_images
train_batch = _base.train_batch
squash = _base.squash
adamw_lr = _base.adamw_lr
adamw_step = _base.adamw_step


def leaky_softmax(b, axis: int):
    """A softmax over ``[0, b]`` along ``axis`` with the zero's column
    dropped, from ``m = max(0, max_k b_k)`` so that no exponent
    overflows; the result does not depend on ``m``."""
    m = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(b, axis=axis, keepdims=True), 0.0))
    e = jnp.exp(b - m)
    return e / (jnp.exp(-m) + jnp.sum(e, axis=axis, keepdims=True))


def routing(u, w, iters: int, precision: str):
    """``capsnet.routing`` with the none-of-the-above category in every
    coupling: votes, then routing by agreement; returns v [B, J, D]."""
    votes = _base._einsum("bic,ijdc->bijd", precision)
    weigh = _base._einsum("bij,bijd->bjd", precision)
    agree = _base._einsum("bijd,bjd->bij", precision)
    u_hat = votes(u, w)
    u_hat_ng = jax.lax.stop_gradient(u_hat)

    def body(it, b):
        c = leaky_softmax(b, axis=2)
        last = jnp.where(it < iters - 1, 0.0, 1.0)
        v = squash(weigh(c, u_hat_ng + last * (u_hat - u_hat_ng)))
        return b + agree(u_hat_ng, v)

    b = jax.lax.fori_loop(0, iters, body,
                          jnp.zeros(u_hat.shape[:3], u_hat.dtype))
    return squash(weigh(leaky_softmax(b, axis=2), u_hat))


_base.routing = routing
class_capsules = _base.class_capsules
lengths = _base.lengths
loss = _base.loss
