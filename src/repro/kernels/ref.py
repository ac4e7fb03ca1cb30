"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the ground truth the kernels are validated against
(tests sweep shapes/dtypes and assert_allclose kernel-vs-ref).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def squash(s: jax.Array, axis: int = -1, eps: float = 1e-7) -> jax.Array:
    sq = jnp.sum(jnp.square(s), axis=axis, keepdims=True)
    return (sq / (1.0 + sq)) * s * jax.lax.rsqrt(sq + eps)


def caps_votes(u: jax.Array, w: jax.Array) -> jax.Array:
    """u: [B, I, C], w: [I, JD, C] -> votes [B, I, JD] (JD = classes*dim)."""
    return jnp.einsum("bic,inc->bin", u, w)


def routing(u_hat: jax.Array, iters: int, *, nota: bool = False
            ) -> jax.Array:
    """u_hat: [B, I, J, D] -> v: [B, J, D] (inference-mode dynamic routing).
    The oracle has no none-of-the-above category: ``nota`` is refused
    (``capsnet.routing_by_agreement`` routes it)."""
    if nota:
        raise NotImplementedError(
            "ref.routing has no none-of-the-above category; use "
            "capsnet.routing_by_agreement(..., nota=True)")
    b = jnp.zeros(u_hat.shape[:3], u_hat.dtype)
    for _ in range(iters):
        c = jax.nn.softmax(b, axis=2)
        v = squash(jnp.einsum("bij,bijd->bjd", c, u_hat))
        b = b + jnp.einsum("bijd,bjd->bij", u_hat, v)
    c = jax.nn.softmax(b, axis=2)
    return squash(jnp.einsum("bij,bijd->bjd", c, u_hat))


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + weight.astype(jnp.float32))
            ).astype(dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              softcap: float | None = None, scale: float | None = None
              ) -> jax.Array:
    """q: [B, H, Tq, D], k/v: [B, H, Tk, D] -> [B, H, Tq, D] (fp32 softmax).

    ``window`` is a sliding-window radius: query t attends to keys in
    (t - window, t] (causal) -- Gemma-style local attention.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    tq, tk = q.shape[2], k.shape[2]
    qi = jnp.arange(tq)[:, None] + (tk - tq)     # align ends (decode-friendly)
    ki = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
