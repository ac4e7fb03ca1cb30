"""Plain reference of the CapsNet family, in jax.numpy and float32.

Dynamic routing between capsules (Sabour et al. 2017): Conv1 (ReLU) ->
PrimaryCaps (strided conv, squash) -> class capsules by dynamic routing,
with the masked reconstruction decoder and the margin loss.  Gradients
flow through the last routing iteration only (the coupling logits see
the votes through ``stop_gradient``), as in the system under test.

It imports nothing of the system under test and takes nothing it made:
parameters and inputs come from the seed through this file.  Every
contraction runs at ``precision``:

- ``"highest"``: float32 on the MXU (``lax.Precision.HIGHEST``), the
  precision the configurations state;
- ``"high"``: the next precision down, three bfloat16 passes: the TPU's
  own ``lax.Precision.HIGH``; elsewhere, where no such mode exists, each
  operand split into a bfloat16 head and tail with the tail x tail
  product dropped, forward and backward.  This is the control that the
  comparison has to reject.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")
SQUASH_EPS = 1e-7
RECON_WEIGHT = 0.0005


# -- sizes ----------------------------------------------------------------

def conv1_out(s: dict) -> int:
    return s["image_hw"] - s["conv1_kernel"] + 1


def pc_out(s: dict) -> int:
    return (conv1_out(s) - s["pc_kernel"]) // s["pc_stride"] + 1


def num_primary(s: dict) -> int:
    return pc_out(s) ** 2 * s["num_primary_groups"]


def routing_stack(s: dict) -> list[dict]:
    """Routing layers in order: the one class layer.  The reference has
    no residual capsule blocks, so ``caps_layers`` must be empty."""
    if s["caps_layers"]:
        raise ValueError("the reference has no residual capsule blocks")
    return [dict(param="cc_w", in_caps=num_primary(s),
                 in_dim=s["primary_dim"], num_caps=s["num_classes"],
                 caps_dim=s["class_dim"], iters=s["routing_iters"])]


def param_shapes(s: dict) -> dict[str, tuple[int, ...]]:
    k1, k2, cin = s["conv1_kernel"], s["pc_kernel"], s["in_channels"]
    c1 = s["conv1_channels"]
    pcc = s["num_primary_groups"] * s["primary_dim"]
    shapes = {"conv1_w": (k1, k1, cin, c1), "conv1_b": (c1,),
              "pc_w": (k2, k2, c1, pcc), "pc_b": (pcc,)}
    for lay in routing_stack(s):
        shapes[lay["param"]] = (lay["in_caps"], lay["num_caps"],
                                lay["caps_dim"], lay["in_dim"])
    h1, h2 = s["decoder_hidden"]
    d_in = s["num_classes"] * s["class_dim"]
    d_out = s["image_hw"] ** 2 * cin
    shapes.update(dec_w1=(d_in, h1), dec_b1=(h1,), dec_w2=(h1, h2),
                  dec_b2=(h2,), dec_w3=(h2, d_out), dec_b3=(d_out,))
    return shapes


def image_shape(s: dict) -> tuple[int, int, int]:
    return (s["image_hw"], s["image_hw"], s["in_channels"])


# -- seeded inputs ----------------------------------------------------------

def key_of(seed: int, stream: int) -> jax.Array:
    """A key for one stream of the seed; seeds beyond 32 bits keep their
    high bits (``PRNGKey`` alone would drop them)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shapes_items):
    params = {}
    for n, (name, shape) in enumerate(shapes_items):
        k = jax.random.fold_in(key, n)
        if name.endswith("_b") or name[:-1].endswith("_b"):
            params[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif name.startswith("cc"):
            params[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:   # He-normal over the fan-in
            fan_in = int(np.prod(shape[:-1]))
            params[name] = (np.sqrt(2.0 / fan_in)
                            * jax.random.normal(k, shape, jnp.float32))
    return params


def init_params(seed: int, s: dict) -> dict:
    """All parameters in float32, made on the device in one call."""
    return _init(key_of(seed, 0), tuple(sorted(param_shapes(s).items())))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _images_labels(key, shape, num_classes):
    k1, k2 = jax.random.split(key)
    return (jax.random.uniform(k1, shape, jnp.float32),
            jax.random.randint(k2, shape[:1], 0, num_classes, jnp.int32))


def request_images(seed: int, s: dict, n: int) -> np.ndarray:
    """``n`` distinct images in [0, 1), on the host."""
    return np.asarray(_images_labels(
        key_of(seed, 1), (n, *image_shape(s)), s["num_classes"])[0])


def train_batch(seed: int, s: dict, batch: int, index: int):
    """Images and labels of training batch ``index``, on the device."""
    return _images_labels(jax.random.fold_in(key_of(seed, 2), index),
                          (batch, *image_shape(s)), s["num_classes"])


# -- contractions at a stated precision --------------------------------------

def _bf16(x):
    # reduce_precision, not a round trip through bfloat16: XLA may drop a
    # convert pair as "excess precision", which on the TPU turned the
    # three passes into one.
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _x3(f, a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def _bf16x3(f):
    """``f`` (bilinear, at HIGHEST) computed in three bfloat16 passes,
    its backward too."""
    @jax.custom_vjp
    def g(a, b):
        return _x3(f, a, b)

    def fwd(a, b):
        return _x3(f, a, b), (a, b)

    def bwd(res, ct):
        a, b = res

        def ta(ct_, b_):
            return jax.vjp(lambda x: f(x, b_), a)[1](ct_)[0]

        def tb(ct_, a_):
            return jax.vjp(lambda y: f(a_, y), b)[1](ct_)[0]
        return _x3(ta, ct, b), _x3(tb, ct, a)

    g.defvjp(fwd, bwd)
    return g


def _bilinear(f, precision: str):
    """``f(a, b, lax_precision)`` at ``precision``."""
    if precision == "highest":
        return functools.partial(f, prec=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r} "
                         f"(known: {PRECISIONS})")
    if jax.default_backend() == "tpu":
        return functools.partial(f, prec=jax.lax.Precision.HIGH)
    return _bf16x3(functools.partial(f, prec=HIGHEST))


def _einsum(eq: str, precision: str):
    return _bilinear(
        lambda a, b, prec: jnp.einsum(eq, a, b, precision=prec), precision)


def _conv(stride: int, precision: str):
    return _bilinear(lambda x, w, prec: jax.lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec),
        precision)


# -- the network --------------------------------------------------------------

def squash(s, axis=-1):
    sq = jnp.sum(jnp.square(s), axis=axis, keepdims=True)
    return (sq / (1.0 + sq)) * s * jax.lax.rsqrt(sq + SQUASH_EPS)


def routing(u, w, iters: int, precision: str):
    """Votes u_hat[b,i,j,d] = sum_c W[i,j,d,c] u[b,i,c], then routing by
    agreement; returns v [B, J, D]."""
    votes = _einsum("bic,ijdc->bijd", precision)
    weigh = _einsum("bij,bijd->bjd", precision)
    agree = _einsum("bijd,bjd->bij", precision)
    u_hat = votes(u, w)
    u_hat_ng = jax.lax.stop_gradient(u_hat)

    def body(it, b):
        c = jax.nn.softmax(b, axis=2)
        last = jnp.where(it < iters - 1, 0.0, 1.0)
        v = squash(weigh(c, u_hat_ng + last * (u_hat - u_hat_ng)))
        return b + agree(u_hat_ng, v)

    b = jax.lax.fori_loop(0, iters, body,
                          jnp.zeros(u_hat.shape[:3], u_hat.dtype))
    return squash(weigh(jax.nn.softmax(b, axis=2), u_hat))


def class_capsules(params, images, s: dict, precision: str):
    x = jax.nn.relu(_conv(1, precision)(images, params["conv1_w"])
                    + params["conv1_b"])
    x = _conv(s["pc_stride"], precision)(x, params["pc_w"]) + params["pc_b"]
    h = squash(x.reshape(x.shape[0], num_primary(s), s["primary_dim"]))
    for lay in routing_stack(s):
        h = routing(h, params[lay["param"]], lay["iters"], precision)
    return h


def lengths(params, images, s: dict, precision: str = "highest"):
    """Class capsule lengths [B, num_classes]."""
    v = class_capsules(params, images, s, precision)
    return jnp.sqrt(jnp.sum(jnp.square(v), axis=-1))


def loss(params, images, labels, s: dict, precision: str = "highest"):
    """Margin loss + RECON_WEIGHT x the squared error of the decoder's
    reconstruction, masked with the true label."""
    v = class_capsules(params, images, s, precision)
    ln = jnp.sqrt(jnp.sum(jnp.square(v), axis=-1))
    t = jax.nn.one_hot(labels, s["num_classes"], dtype=v.dtype)
    margin = jnp.mean(jnp.sum(
        t * jnp.square(jnp.maximum(0.0, 0.9 - ln))
        + 0.5 * (1.0 - t) * jnp.square(jnp.maximum(0.0, ln - 0.1)), -1))
    mm = _einsum("bi,io->bo", precision)
    h = (v * t[..., None]).reshape(v.shape[0], -1)
    h = jax.nn.relu(mm(h, params["dec_w1"]) + params["dec_b1"])
    h = jax.nn.relu(mm(h, params["dec_w2"]) + params["dec_b2"])
    rec = jax.nn.sigmoid(mm(h, params["dec_w3"]) + params["dec_b3"])
    err = jnp.mean(jnp.sum(
        jnp.square(rec - images.reshape(images.shape[0], -1)), -1))
    return margin + RECON_WEIGHT * err


# -- AdamW ----------------------------------------------------------------------

def adamw_lr(step: int, opt: dict) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_ratio`` x
    ``lr`` at ``decay_steps``."""
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(opt["warmup_steps"], 1)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * frac)))


@functools.partial(jax.jit, static_argnames=("opt_items",),
                   donate_argnums=(0, 1, 2, 3))
def adamw_step(params, grads, m, v, step, lr, opt_items):
    """One AdamW step with global-norm clipping; ``step`` counts from 1.
    Returns the new params, m, v and the clipped gradient, in the
    buffers of the ones it is given."""
    opt = dict(opt_items)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    b1c = 1 - b1 ** step
    b2c = 1 - b2 ** step
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / b1c) / (jnp.sqrt(b / b2c) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, g
