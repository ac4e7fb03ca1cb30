"""Squash nonlinearity kernel: v = ||s||^2/(1+||s||^2) * s/||s||.

Elementwise-with-reduction over the capsule dimension; blocked over rows so
arbitrarily many capsules stream through a fixed VMEM tile (the activation
-unit stage of the CapsAcc pipeline).

The squash math itself is ``repro.core.capsnet.squash`` -- the ONE canonical
implementation shared by the jnp reference model, this kernel, and the fused
routing kernel (``repro.kernels.ref.squash`` stays a deliberately separate
oracle for validation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.capsnet import squash as squash_reference


def _squash_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = squash_reference(x).astype(o_ref.dtype)


def _squash_bwd_kernel(x_ref, g_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    _, pull = jax.vjp(squash_reference, x)
    o_ref[...] = pull(g_ref[...].astype(jnp.float32))[0].astype(o_ref.dtype)


def _squash_call(kernel, rows: int, d: int, block_rows: int,
                 interpret: bool, *operands):
    br = max(1, min(block_rows, rows))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, d), lambda r: (r, 0))
                  for _ in operands],
        out_specs=pl.BlockSpec((br, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), operands[0].dtype),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _squash_core(block_rows: int, interpret: bool, x2: jax.Array):
    return _squash_call(_squash_kernel, x2.shape[0], x2.shape[1],
                        block_rows, interpret, x2)


def _squash_core_fwd(block_rows, interpret, x2):
    return _squash_core(block_rows, interpret, x2), x2


def _squash_core_bwd(block_rows, interpret, x2, g):
    dx = _squash_call(_squash_bwd_kernel, x2.shape[0], x2.shape[1],
                      block_rows, interpret, x2, g)
    return (dx,)


_squash_core.defvjp(_squash_core_fwd, _squash_core_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def squash(x: jax.Array, *, block_rows: int = 1024,
           interpret: bool) -> jax.Array:
    """x: [..., R, D]; squash along the last axis, blocked over R.

    Rows need not divide ``block_rows``: the grid is ``cdiv`` and the
    ragged tail block is row-parallel safe.  Differentiable: the custom
    VJP replays the saved input through a blocked Pallas backward kernel
    (the exact ``jax.vjp`` of the reference squash, tile by tile).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    out = _squash_core(block_rows, interpret, x.reshape(rows, d))
    return out.reshape(orig_shape)
