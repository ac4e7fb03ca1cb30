"""Capsule vote kernel: u_hat[b, i, n] = sum_c W[i, n, c] * u[b, i, c].

This is the ClassCaps-FC operation the paper profiles as the *memory-bound*
stage (its weights have zero reuse -- every W element is read exactly once
per inference).  The CapStore insight on TPU: the only thing tiling can do
for a reuse-free operand is (1) stream it through VMEM in blocks big enough
to saturate HBM (the paper's weight-memory prefetch buffer) and (2) keep
the *reused* operands (u: the data memory, accumulator tile) resident.

The plan-driven path no longer materializes u_hat at all --
``kernels/votes_routing.py`` fuses this operation into the routing loop.
This kernel survives as the split-path oracle/fallback.

Block layout per grid step (i-block `bi` of size TI):
    data memory   : u tile   [B, TI, C]      (reused across all N outputs)
    weight memory : W tile   [TI, N, C]      (streamed, read once)
    accumulator   : out tile [B, TI, N]      (written once)

The i-dimension is the only grid axis -> "arbitrary" semantics, a pure
streaming pass, exactly the paper's CC-FC dataflow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _votes_kernel(u_ref, w_ref, o_ref):
    u = u_ref[...].astype(jnp.float32)        # [B, TI, C]
    w = w_ref[...].astype(jnp.float32)        # [TI, N, C]
    o_ref[...] = jnp.einsum(
        "bic,inc->bin", u, w,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "interpret"))
def caps_votes(u: jax.Array, w: jax.Array, *, block_i: int = 128,
               interpret: bool) -> jax.Array:
    """u: [B, I, C], w: [I, N, C] -> [B, I, N].

    ``block_i`` is the CapStore-planned i-tile (see
    ``repro.core.execplan``).  I need NOT be divisible by block_i: the grid
    is ``cdiv(I, block_i)`` and the final ragged block is safe because each
    output row depends only on the same input row (Pallas clamps/masks the
    tail block identically on the input and output side).
    """
    b, i, c = u.shape
    _, n, _ = w.shape
    block_i = max(1, min(block_i, i))
    grid = (pl.cdiv(i, block_i),)
    return pl.pallas_call(
        _votes_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_i, c), lambda bi: (0, bi, 0)),
            pl.BlockSpec((block_i, n, c), lambda bi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, block_i, n), lambda bi: (0, bi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, i, n), u.dtype),
        interpret=interpret,
    )(u, w)
