"""CapsuleNet (Sabour et al. 2017) in pure JAX.

The network the paper profiles: Conv1 (9x9, 1->256, ReLU) -> PrimaryCaps
(9x9 conv, 256->32 capsules x 8D, stride 2) -> ClassCaps (routing-by-
agreement to 10 capsules x 16D), plus the optional reconstruction decoder
and margin loss, so the end-to-end example can actually train.

Routing-by-agreement is the feedback loop the paper highlights (Fig. 2);
it is expressed with ``jax.lax.fori_loop`` so it lowers to a single compact
HLO loop, mirroring the on-chip-resident routing state of CapStore.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

# Plan-op / PMU-phase name of one fused votes+routing layer.  The FINAL
# (classification) layer keeps the bare name -- the historical fixed-3-op
# plan -- while every intermediate layer of a deep stack gets an index
# suffix ("ClassCaps-Routing[0]", ...) so repeated layers never collide
# on a phase name.  ``execplan.FUSED_NAME`` aliases this constant.
ROUTING_NAME = "ClassCaps-Routing"


@dataclasses.dataclass(frozen=True)
class CapsLayerSpec:
    """One PLAIN routing-capsule layer of a deep stack: votes + routing
    from however many capsules flow in to ``num_caps`` capsules of
    ``caps_dim`` dimensions."""

    num_caps: int
    caps_dim: int
    routing_iters: int = 3


@dataclasses.dataclass(frozen=True)
class ResCapsBlock:
    """One REVERSIBLE residual capsule block (MoCapsNet-style).

    The incoming capsule tensor ``[B, I, C]`` is split along the capsule
    axis into ``x1 [B, I1, C]`` / ``x2 [B, I2, C]`` (``I1 = I // 2``) and
    run through an additive coupling of two routing-capsule halves::

        y1 = x1 + F(x2)        # F: routing layer  I2 caps -> I1 x C
        y2 = x2 + G(y1)        # G: routing layer  I1 caps -> I2 x C

    Shape-preserving AND invertible: ``x2 = y2 - G(y1)``, ``x1 = y1 -
    F(x2)``, so the backward pass recomputes each block's input from its
    output instead of saving activations -- activation memory stays flat
    in depth no matter how many blocks are stacked.
    """

    routing_iters: int = 3


@dataclasses.dataclass(frozen=True)
class RoutingLayer:
    """One RESOLVED votes+routing instance of the layer graph.

    ``CapsNetConfig.routing_stack()`` flattens the ``caps_layers`` entries
    (a ``ResCapsBlock`` contributes its two coupling halves) plus the
    implicit final ClassCaps layer into a chain of these; the plan
    compiler, both forwards, ``init_params``, and the analysis profiles
    all walk the same chain.  ``name`` is the plan-op / PMU-phase name
    (unique per instance), ``param`` the ``params`` dict key.  ``half``
    marks residual coupling halves (``"f"`` / ``"g"``); consecutive
    residual blocks form one reversible segment in the backward pass.
    """

    name: str
    param: str
    in_caps: int
    in_dim: int
    num_caps: int
    caps_dim: int
    iters: int
    block: int | None = None     # caps_layers entry index (residual only)
    half: str | None = None      # "f" | "g" coupling half
    nota: bool = False           # none-of-the-above category in routing

    @property
    def jd(self) -> int:
        return self.num_caps * self.caps_dim

    @property
    def residual(self) -> bool:
        return self.half is not None


@dataclasses.dataclass(frozen=True)
class CapsNetConfig:
    image_hw: int = 28
    in_channels: int = 1
    conv1_channels: int = 256
    conv1_kernel: int = 9
    pc_kernel: int = 9
    pc_stride: int = 2
    num_primary_groups: int = 32     # capsule groups (channels / primary_dim)
    primary_dim: int = 8
    num_classes: int = 10
    class_dim: int = 16
    routing_iters: int = 3
    decoder_hidden: tuple[int, int] = (512, 1024)
    use_decoder: bool = True
    # Intermediate routing layers between PrimaryCaps and the final
    # ClassCaps layer: a chain of ``CapsLayerSpec`` / ``ResCapsBlock``
    # entries.  Empty (the default) is the paper's fixed 3-op topology --
    # plans, params, and outputs are bit-identical to the pre-graph code.
    caps_layers: tuple = ()
    # Sabour et al.'s CIFAR-10 "none-of-the-above" category: a fixed zero
    # logit joins every routing softmax over the output capsules and its
    # share is dropped (``couplings``), so a capsule may route part of
    # itself nowhere.  Off (the default) is the plain softmax.
    none_of_the_above: bool = False

    @property
    def conv1_out(self) -> int:
        return self.image_hw - self.conv1_kernel + 1

    @property
    def pc_out(self) -> int:
        return (self.conv1_out - self.pc_kernel) // self.pc_stride + 1

    @property
    def num_primary(self) -> int:
        return self.pc_out * self.pc_out * self.num_primary_groups

    @property
    def pc_channels(self) -> int:
        return self.num_primary_groups * self.primary_dim

    def routing_stack(self) -> tuple[RoutingLayer, ...]:
        """Flatten ``caps_layers`` + the final ClassCaps layer into the
        resolved routing-layer chain (see ``RoutingLayer``)."""
        layers: list[RoutingLayer] = []
        i, c = self.num_primary, self.primary_dim
        nota = self.none_of_the_above
        idx = 0
        for k, entry in enumerate(self.caps_layers):
            if isinstance(entry, ResCapsBlock):
                if i < 2:
                    raise ValueError(
                        f"caps_layers[{k}]: ResCapsBlock needs >= 2 incoming "
                        f"capsules to split the coupling halves, got {i}")
                i1, i2 = i // 2, i - i // 2
                layers.append(RoutingLayer(
                    name=f"{ROUTING_NAME}[{idx}]", param=f"cc{idx}_w",
                    in_caps=i2, in_dim=c, num_caps=i1, caps_dim=c,
                    iters=entry.routing_iters, block=k, half="f",
                    nota=nota))
                idx += 1
                layers.append(RoutingLayer(
                    name=f"{ROUTING_NAME}[{idx}]", param=f"cc{idx}_w",
                    in_caps=i1, in_dim=c, num_caps=i2, caps_dim=c,
                    iters=entry.routing_iters, block=k, half="g",
                    nota=nota))
                idx += 1
            elif isinstance(entry, CapsLayerSpec):
                if entry.num_caps < 1 or entry.caps_dim < 1:
                    raise ValueError(
                        f"caps_layers[{k}]: num_caps/caps_dim must be >= 1, "
                        f"got {entry.num_caps}x{entry.caps_dim}")
                layers.append(RoutingLayer(
                    name=f"{ROUTING_NAME}[{idx}]", param=f"cc{idx}_w",
                    in_caps=i, in_dim=c, num_caps=entry.num_caps,
                    caps_dim=entry.caps_dim, iters=entry.routing_iters,
                    nota=nota))
                idx += 1
                i, c = entry.num_caps, entry.caps_dim
            else:
                raise TypeError(
                    f"caps_layers[{k}]: expected CapsLayerSpec or "
                    f"ResCapsBlock, got {type(entry).__name__}")
        layers.append(RoutingLayer(
            name=ROUTING_NAME, param="cc_w", in_caps=i, in_dim=c,
            num_caps=self.num_classes, caps_dim=self.class_dim,
            iters=self.routing_iters, nota=nota))
        return tuple(layers)


Params = dict[str, Any]


def init_params(key: jax.Array, cfg: CapsNetConfig = CapsNetConfig(),
                dtype=jnp.float32) -> Params:
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    he = jax.nn.initializers.he_normal()
    stack = cfg.routing_stack()
    final = stack[-1]
    params: Params = {
        "conv1_w": he(k1, (cfg.conv1_kernel, cfg.conv1_kernel,
                           cfg.in_channels, cfg.conv1_channels), dtype),
        "conv1_b": jnp.zeros((cfg.conv1_channels,), dtype),
        "pc_w": he(k2, (cfg.pc_kernel, cfg.pc_kernel,
                        cfg.conv1_channels, cfg.pc_channels), dtype),
        "pc_b": jnp.zeros((cfg.pc_channels,), dtype),
        # W[i, j, class_dim, in_dim]: the final layer consumes whatever
        # the stack flows into it (= num_primary x primary_dim when
        # caps_layers is empty -- same shape, same key, same bits).
        "cc_w": 0.1 * jax.random.normal(
            k3, (final.in_caps, final.num_caps, final.caps_dim,
                 final.in_dim), dtype),
    }
    # Intermediate routing layers of a deep stack.  Keys derive from k3
    # via fold_in so the base 6-way split (and every existing param) stays
    # bit-identical when caps_layers is empty.
    for lay in stack[:-1]:
        params[lay.param] = 0.1 * jax.random.normal(
            jax.random.fold_in(k3, 1 + int(lay.param[2:-2])),
            (lay.in_caps, lay.num_caps, lay.caps_dim, lay.in_dim), dtype)
    if cfg.use_decoder:
        d_in = cfg.num_classes * cfg.class_dim
        h1, h2 = cfg.decoder_hidden
        d_out = cfg.image_hw * cfg.image_hw * cfg.in_channels
        params["dec_w1"] = he(k4, (d_in, h1), dtype)
        params["dec_b1"] = jnp.zeros((h1,), dtype)
        params["dec_w2"] = he(k5, (h1, h2), dtype)
        params["dec_b2"] = jnp.zeros((h2,), dtype)
        params["dec_w3"] = he(k6, (h2, d_out), dtype)
        params["dec_b3"] = jnp.zeros((d_out,), dtype)
    return params


SQUASH_EPS = 1e-7


def squash(s: jax.Array, axis: int = -1,
           eps: float = SQUASH_EPS) -> jax.Array:
    """v = ||s||^2 / (1 + ||s||^2) * s / ||s|| (paper Sec. 2.1)."""
    sq = jnp.sum(jnp.square(s), axis=axis, keepdims=True)
    return (sq / (1.0 + sq)) * s * jax.lax.rsqrt(sq + eps)


def compute_votes(u: jax.Array, cc_w: jax.Array) -> jax.Array:
    """u_hat[b, i, j, d] = W[i, j, d, c] u[b, i, c]  (the CC-FC operation)."""
    return jnp.einsum("bic,ijdc->bijd", u, cc_w)


def couplings(b: jax.Array, axis: int = -1,
              nota: bool = False) -> jax.Array:
    """Routing couplings: a softmax of the logits over the output capsules.

    With ``nota`` (the none-of-the-above category of Sabour et al.'s
    CIFAR-10 network) a fixed zero logit joins the softmax and its column
    is dropped: ``c_j = exp(b_j) / (1 + sum_k exp(b_k))``, computed from
    ``m = max(0, max_k b_k)`` so that no exponent overflows.  The result
    does not depend on ``m``, so no gradient flows through it."""
    if not nota:
        return jax.nn.softmax(b, axis=axis)
    m = jax.lax.stop_gradient(jnp.maximum(
        jnp.max(b, axis=axis, keepdims=True), 0.0))
    e = jnp.exp(b - m)
    return e / (jnp.exp(-m) + jnp.sum(e, axis=axis, keepdims=True))


def routing_by_agreement(u_hat: jax.Array, iters: int, *,
                         nota: bool = False) -> jax.Array:
    """Dynamic routing (paper Fig. 2 feedback loop).  u_hat: [B, I, J, D].
    ``nota`` adds the none-of-the-above category (``couplings``)."""
    b0 = jnp.zeros(u_hat.shape[:3], u_hat.dtype)          # logits b[b, i, j]
    u_hat_ng = jax.lax.stop_gradient(u_hat)

    def body(it, b):
        c = couplings(b, axis=2, nota=nota)               # over classes j
        # Sum+Squash: s[b, j, d] = sum_i c * u_hat
        uh = jnp.where(it < iters - 1, 0.0, 1.0)          # scalar gate
        u_used = u_hat_ng + uh * (u_hat - u_hat_ng)       # grads last iter only
        s = jnp.einsum("bij,bijd->bjd", c, u_used)
        v = squash(s)
        # Update+Sum: b += <u_hat, v>
        return b + jnp.einsum("bijd,bjd->bij", u_hat_ng, v)

    b = jax.lax.fori_loop(0, iters, body, b0)
    c = couplings(b, axis=2, nota=nota)
    return squash(jnp.einsum("bij,bijd->bjd", c, u_hat))  # v[b, j, d]


def routing_stack_ref(params: Params, u: jax.Array,
                      cfg: CapsNetConfig) -> jax.Array:
    """Reference (jnp) walk of the routing-layer graph: squashed primary
    capsules ``u [B, I, C]`` -> class capsules ``[B, J, D]``.

    Residual blocks apply the additive coupling ``y1 = x1 + F(x2)``,
    ``y2 = x2 + G(y1)`` (see ``ResCapsBlock``); plain layers replace the
    capsule tensor.  The default (empty-stack) config reduces to exactly
    ``routing_by_agreement(compute_votes(u, cc_w), iters)``.
    """
    stack = cfg.routing_stack()
    h, k = u, 0
    while k < len(stack):
        lay = stack[k]
        if lay.half == "f":
            g_lay = stack[k + 1]
            x1, x2 = h[:, :lay.num_caps], h[:, lay.num_caps:]
            y1 = x1 + routing_by_agreement(
                compute_votes(x2, params[lay.param]), lay.iters,
                nota=lay.nota)
            y2 = x2 + routing_by_agreement(
                compute_votes(y1, params[g_lay.param]), g_lay.iters,
                nota=g_lay.nota)
            h, k = jnp.concatenate([y1, y2], axis=1), k + 2
        else:
            h = routing_by_agreement(
                compute_votes(h, params[lay.param]), lay.iters,
                nota=lay.nota)
            k += 1
    return h


def decode(params: Params, v: jax.Array,
           cfg: CapsNetConfig = CapsNetConfig(), *,
           labels: jax.Array | None = None,
           lengths: jax.Array | None = None) -> jax.Array:
    """Reconstruction decoder over the masked class capsules.

    Sabour et al. mask with the TRUE label during training (so the recon
    loss gradient flows through the labeled capsule) and with the predicted
    class at inference: pass ``labels`` when training, omit for argmax.
    """
    if labels is None:
        if lengths is None:
            lengths = jnp.linalg.norm(v, axis=-1)
        labels = jnp.argmax(lengths, -1)
    mask = jax.nn.one_hot(labels, cfg.num_classes, dtype=v.dtype)
    masked = (v * mask[..., None]).reshape(v.shape[0], -1)
    h = jax.nn.relu(masked @ params["dec_w1"] + params["dec_b1"])
    h = jax.nn.relu(h @ params["dec_w2"] + params["dec_b2"])
    return jax.nn.sigmoid(h @ params["dec_w3"] + params["dec_b3"])


def forward(params: Params, images: jax.Array,
            cfg: CapsNetConfig = CapsNetConfig(), *,
            labels: jax.Array | None = None,
            backend: str = "jnp", plan=None,
            interpret: bool | None = None) -> dict[str, jax.Array]:
    """images: [B, H, W, C] in [0, 1] -> class capsules + reconstruction.

    ``backend="jnp"`` (default) is the pure-JAX reference.
    ``backend="pallas"`` runs the WHOLE network through the Pallas kernels
    with block shapes and the resident/streamed routing schedule chosen
    by an ``ExecutionPlan`` (compiled on the fly from ``cfg`` unless
    ``plan`` is passed).  A pipelined plan (``compile_plan(...,
    pipeline=True)``, the on-the-fly default) runs Conv1 -> ONE
    ``primary_routing`` megakernel (PrimaryCaps conv + squash + votes +
    routing, the inter-layer activation u resident in VMEM); a per-op
    plan runs the three-call path (conv_im2col PrimaryCaps with fused
    squash -> fused votes_routing megakernel) -- the pipelined plan's
    fallback and parity oracle.  The kernels compile with Mosaic on a TPU
    and run in the Pallas interpreter elsewhere unless ``interpret`` says
    otherwise (``kernels.ops.should_interpret``).

    ``labels`` masks the reconstruction decoder with the true class
    (training semantics); when omitted the decoder masks with argmax.
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    b = images.shape[0]
    if backend == "pallas":
        from repro.core import execplan as _execplan
        from repro.kernels import ops as _kops
        if plan is None:
            plan = _execplan.compile_plan(cfg, batch=b, pipeline=True)
        x = _kops.conv2d(images, params["conv1_w"], params["conv1_b"],
                         stride=1, plan_op=plan.op("Conv1"),
                         epilogue="relu", interpret=interpret)
        stack = cfg.routing_stack()

        def w_of(lay):
            return params[lay.param].reshape(lay.in_caps, lay.jd, lay.in_dim)

        pipelined = any(op.kernel == "primary_routing" for op in plan.ops)
        if pipelined:
            # ONE pipelined megakernel: PrimaryCaps conv + squash + votes
            # + routing of the FIRST routing layer, with the inter-layer u
            # in VMEM scratch (neither u nor u_hat ever round-trips
            # through HBM).
            first = stack[0]
            h = _kops.primary_routing(
                x, params["pc_w"], params["pc_b"], w_of(first), plan=plan,
                iters=first.iters, num_classes=first.num_caps,
                routing_op_name=first.name,
                interpret=interpret).reshape(b, first.num_caps,
                                             first.caps_dim)
            k = 1
        else:
            pc = plan.op("PrimaryCaps")
            x = _kops.conv2d(x, params["pc_w"], params["pc_b"],
                             stride=cfg.pc_stride, plan_op=pc,
                             squash_dim=cfg.primary_dim, interpret=interpret)
            u = x.reshape(b, cfg.num_primary, cfg.primary_dim)
            if not pc.fuses_squash:
                u = _kops.squash(u, plan=plan, interpret=interpret)
            h, k = u, 0
        # Walk the remaining routing-layer graph: one fused votes+routing
        # megakernel per plain layer (u_hat never round-trips through
        # HBM), and one REVERSIBLE segment call per maximal run of
        # residual blocks (backward reconstructs each block's input from
        # its output -- no activations saved; see res_caps_segment).
        while k < len(stack):
            lay = stack[k]
            if lay.half == "f":
                pairs = []
                while k < len(stack) and stack[k].half == "f":
                    pairs.append((stack[k], stack[k + 1]))
                    k += 2
                ws = tuple(w_of(lyr) for pair in pairs for lyr in pair)
                h = _kops.res_caps_segment(h, ws, tuple(pairs), plan=plan,
                                           interpret=interpret)
            else:
                h = _kops.votes_routing(
                    h, w_of(lay), plan=plan, op_name=lay.name,
                    iters=lay.iters, num_classes=lay.num_caps,
                    interpret=interpret).reshape(b, lay.num_caps,
                                                 lay.caps_dim)
                k += 1
        v = h
    else:
        x = jax.lax.conv_general_dilated(
            images, params["conv1_w"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + params["conv1_b"])
        x = jax.lax.conv_general_dilated(
            x, params["pc_w"], window_strides=(cfg.pc_stride, cfg.pc_stride),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = x + params["pc_b"]
        u = squash(x.reshape(b, cfg.num_primary, cfg.primary_dim))
        v = routing_stack_ref(params, u, cfg)              # [B, J, D]
    lengths = jnp.linalg.norm(v, axis=-1)                  # class scores
    out = {"class_caps": v, "lengths": lengths}
    if cfg.use_decoder and "dec_w1" in params:
        out["reconstruction"] = decode(params, v, cfg, labels=labels,
                                       lengths=lengths)
    return out


def margin_loss(lengths: jax.Array, labels: jax.Array,
                m_pos: float = 0.9, m_neg: float = 0.1,
                lam: float = 0.5) -> jax.Array:
    """L_k = T_k max(0, m+ - ||v||)^2 + lam (1-T_k) max(0, ||v|| - m-)^2."""
    t = jax.nn.one_hot(labels, lengths.shape[-1], dtype=lengths.dtype)
    pos = jnp.square(jnp.maximum(0.0, m_pos - lengths))
    neg = jnp.square(jnp.maximum(0.0, lengths - m_neg))
    return jnp.mean(jnp.sum(t * pos + lam * (1.0 - t) * neg, axis=-1))


def total_loss(params: Params, images: jax.Array, labels: jax.Array,
               cfg: CapsNetConfig = CapsNetConfig(),
               recon_weight: float = 0.0005, *, backend: str = "jnp",
               plan=None,
               interpret: bool | None = None) -> tuple[jax.Array, dict]:
    """Margin loss + masked reconstruction, differentiable on BOTH backends.

    The decoder reconstructs the LABELED capsule (training semantics), so
    the reconstruction term backpropagates only through that capsule's
    pose -- on the Pallas path the gradient flows through the kernels'
    custom VJPs (compile the plan with ``train=True`` to pin the backward
    schedule; otherwise the memoized backward plan decision applies).
    """
    out = forward(params, images, cfg, labels=labels, backend=backend,
                  plan=plan, interpret=interpret)
    loss = margin_loss(out["lengths"], labels)
    metrics = {"margin_loss": loss}
    if "reconstruction" in out:
        flat = images.reshape(images.shape[0], -1)
        rec = jnp.mean(jnp.sum(jnp.square(out["reconstruction"] - flat), -1))
        loss = loss + recon_weight * rec
        metrics["recon_loss"] = rec
    metrics["accuracy"] = jnp.mean(
        (jnp.argmax(out["lengths"], -1) == labels).astype(jnp.float32))
    metrics["loss"] = loss
    return loss, metrics


@functools.partial(jax.jit, static_argnames=("cfg", "lr", "backend",
                                             "interpret"))
def train_step(params: Params, images: jax.Array, labels: jax.Array,
               cfg: CapsNetConfig = CapsNetConfig(),
               lr: float = 1e-3, *, backend: str = "jnp",
               interpret: bool | None = None) -> tuple[Params, dict]:
    (_, metrics), grads = jax.value_and_grad(total_loss, has_aux=True)(
        params, images, labels, cfg, backend=backend, interpret=interpret)
    params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return params, metrics
