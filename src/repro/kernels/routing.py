"""Fused routing-by-agreement kernel (legacy split-path fallback/oracle).

The paper's key memory observation: during the routing iterations *no value
leaves the chip* (Sec. 3.1 -- "all the values that have to be saved during
the routing-by-agreement are stored on-chip").  The TPU translation: run
ALL routing iterations inside one ``pallas_call`` so the routing state
(logits b, couplings c, candidate outputs s/v) lives on-chip for the whole
loop, and only the votes (read once) and the final v (written once) cross
HBM.  The plan-driven path goes further: ``kernels/votes_routing.py``
fuses the vote computation in as well, so the votes themselves never
round-trip through HBM -- this kernel survives as the split-path
oracle/fallback consuming a materialized ``u_hat``.

VMEM budget per grid step (one batch element):
    votes  [I, J*D]  : the "accumulator memory" contents (fp32)
    b      [I, J]    : routing logits     (loop carry)
    v      [J*D]     : squashed output    (stored as [1, J*D])

For CapsuleNet-MNIST (I=1152, J=10, D=16) that is ~0.8 MiB -- comfortably
inside the 16 MiB VMEM envelope the planner manages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.capsnet import squash


def _routing_kernel(uhat_ref, o_ref, *, iters: int, j: int, d: int):
    uh = uhat_ref[0].astype(jnp.float32)                  # [I, J*D]
    i_dim = uh.shape[0]
    uh4 = uh.reshape(i_dim, j, d)

    def iteration(_, b):
        c = jax.nn.softmax(b, axis=1)                     # [I, J]
        s = jnp.einsum("ij,ijd->jd", c, uh4)              # Sum
        v = squash(s)                                     # Squash
        return b + jnp.einsum("ijd,jd->ij", uh4, v)       # Update(+Sum)

    b = jax.lax.fori_loop(0, iters, iteration,
                          jnp.zeros((i_dim, j), jnp.float32))
    c = jax.nn.softmax(b, axis=1)
    v = squash(jnp.einsum("ij,ijd->jd", c, uh4))
    o_ref[...] = v.reshape(1, j * d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("iters", "num_classes", "nota",
                                             "interpret"))
def routing(u_hat: jax.Array, *, iters: int = 3, num_classes: int = 10,
            nota: bool = False, interpret: bool) -> jax.Array:
    """u_hat: [B, I, J*D] -> v: [B, J*D]; fused dynamic routing.  The
    oracle has no none-of-the-above category: ``nota`` is refused."""
    if nota:
        raise NotImplementedError(
            "the split routing oracle has no none-of-the-above category; "
            "the planned votes_routing / primary_routing kernels route it")
    bsz, i_dim, jd = u_hat.shape
    j = num_classes
    if jd % j:
        raise ValueError(f"votes dim {jd} not divisible by classes {j}")
    d = jd // j
    kernel = functools.partial(_routing_kernel, iters=iters, j=j, d=d)
    return pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, i_dim, jd), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, jd), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, jd), u_hat.dtype),
        interpret=interpret,
    )(u_hat)
