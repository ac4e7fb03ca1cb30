"""Plan-driven im2col convolution kernels: Conv1 / PrimaryCaps on the MXU.

CapsAcc (Marchisio et al. 2018) and DESCNet run the CapsuleNet conv stack
as im2col matmuls on the same PE array as the capsule operations; CapStore
sizes the on-chip memories from that schedule.  These kernels are the TPU
translation, in two stages:

  1. ``im2col_patches``: patch extraction by XLA into the
     [B, OH*OW, KH*KW*C] patch matrix in HBM.  No tap is written as a
     width-C lane piece, and none through a [..., KH*KW, C] intermediate
     that a relayout must then turn into [B, P, K].  The formulation
     follows the channel width C: a C that is not a multiple of 128
     (Conv1's C=1, or 3) goes by pixel rows, each row's (W, C) pair
     merged onto the lanes and cut into KW*C-wide windows; a lane-dense C
     (PrimaryCaps' C=256) splits the stride phases once and concatenates
     each tap's unit-stride [B, P, C] slab on the lanes.

  2. ``matmul_bias_act`` (Pallas): blocked [M, K] x [K, N] matmul over the plan's
     ``block_m/k/n`` grid tiles with a fused epilogue (bias + ReLU for
     Conv1, bias + per-capsule squash for PrimaryCaps).  The patch tile is
     the data memory, the weight tile streams (double-buffered), and the
     output block is the accumulator that stays resident across the K grid
     axis -- the paper's accumulator memory.

Ragged final M/N blocks are safe the same way ``caps_votes`` is: Pallas
clamps the tail block identically on the input and output side, and each
(mi, ni) grid cell recomputes its full K reduction, so overlapped rows are
rewritten with identical values.  The K axis is different -- a clamped tail
block would double-count the overlap -- so K is zero-padded up to a
multiple of ``block_k`` instead (zero rows contribute nothing).

``conv2d_im2col`` carries a ``jax.custom_vjp``, so ``jax.grad`` through the
Pallas backend works end to end.  The backward matmuls are Pallas too:

  * dL/dW = patchesT @ dy via ``matmul_at_b`` (a blocked A^T B matmul over
    the SAME plan ``block_m/k/n`` tiles, with the shared M axis as the
    zero-padded reduction -- no HBM transpose of the patch slab);
  * dL/dpatches = dy @ W^T through ``matmul_bias_act`` (the weight
    transpose is tiny), then dL/dx via ``col2im_patches``, the exact
    transpose of the patch extraction (XLA);
  * epilogue cotangents come from the saved output (ReLU mask) or a
    recomputed pre-activation (per-capsule squash), matching ``jax.grad``
    of the jnp reference to float32 accuracy.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.capsnet import SQUASH_EPS
from repro.core.capsnet import squash as squash_reference
from repro.core.planner import LANES, VMEM_LIMIT_BYTES

EPILOGUES = ("none", "relu", "squash")
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _patches_xla(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """The plain extraction: every tap sliced, stacked, reshaped.  Kept as
    the reference of ``im2col_patches`` and as the function whose VJP is
    ``col2im_patches``."""
    b, h, w, c = x.shape
    oh = (h - kh) // stride + 1
    ow = (w - kw) // stride + 1
    taps = [x[:, i:i + (oh - 1) * stride + 1:stride,
              j:j + (ow - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    return jnp.stack(taps, axis=3).reshape(b, oh * ow, kh * kw * c)


def _patches_by_rows(x: jax.Array, kh: int, kw: int,
                     stride: int) -> jax.Array:
    """Narrow channels: each pixel row's (W, C) pair goes onto the lanes.
    The KW taps of kernel row ``i`` at output column ``q`` are then one
    contiguous KW*C-lane window of that row, from lane ``q*stride*C``."""
    b, h, w, c = x.shape
    oh = (h - kh) // stride + 1
    ow = (w - kw) // stride + 1
    rows = x.reshape(b, h, w * c)
    per_row = []
    for i in range(kh):
        r = rows[:, i:i + (oh - 1) * stride + 1:stride]           # [B,OH,W*C]
        per_row.append(jnp.stack(
            [r[:, :, q * stride * c:(q * stride + kw) * c]
             for q in range(ow)], axis=2))                         # [B,OH,OW,KW*C]
    return jnp.concatenate(per_row, axis=-1).reshape(b, oh * ow, kh * kw * c)


def _patches_by_slabs(x: jax.Array, kh: int, kw: int,
                      stride: int) -> jax.Array:
    """Lane-dense channels: split the stride phases once, so every tap is
    a unit-stride [B, P, C] slab, and concatenate the slabs on the lanes
    -- the patch matrix is born in its [B, P, K] layout."""
    b, h, w, c = x.shape
    s = stride
    oh = (h - kh) // s + 1
    ow = (w - kw) // s + 1
    hp, wp = -(-h // s) * s, -(-w // s) * s
    x = jnp.pad(x, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)))
    phases = x.reshape(b, hp // s, s, wp // s, s, c).transpose(2, 4, 0, 1, 3, 5)
    taps = [phases[i % s, j % s, :, i // s:i // s + oh, j // s:j // s + ow]
            .reshape(b, oh * ow, c)
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(taps, axis=-1)


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride"))
def im2col_patches(x: jax.Array, *, kh: int, kw: int,
                   stride: int = 1) -> jax.Array:
    """x: [B, H, W, C] -> patches [B, OH*OW, KH*KW*C] (VALID padding).

    Patch column order is ``(kh, kw, c)``-major, matching
    ``w.reshape(KH*KW*C, Cout)`` of an HWIO weight tensor.  XLA extracts
    the patches: the matrix crosses HBM once either way (the matmul
    streams it in K tiles), and strided tap windows are not a Mosaic
    layout.  Both formulations only move data, so the patches equal
    ``_patches_xla``'s bit for bit; the channel width picks the one whose
    writes are tile-dense (module docstring, stage 1).
    """
    if x.shape[-1] % LANES:
        return _patches_by_rows(x, kh, kw, stride)
    return _patches_by_slabs(x, kh, kw, stride)


def _matmul_kernel(p_ref, w_ref, b_ref, o_ref, *, k_steps: int,
                   epilogue: str, squash_dim: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        p_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(ki == k_steps - 1)
    def _():
        acc = o_ref[...] + b_ref[...]              # [TM, TN] + [1, TN]
        if epilogue == "relu":
            acc = jnp.maximum(acc, 0.0)
        elif epilogue == "squash":
            # Each capsule's squared norm, broadcast back over its
            # squash_dim lanes by a block-diagonal 0/1 matmul (a lane
            # reshape into capsules is not a Mosaic layout).
            tn = acc.shape[1]
            lane = jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 0)
            peer = jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 1)
            same_caps = (lane // squash_dim == peer // squash_dim)
            sq = jnp.dot(acc * acc, same_caps.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
            acc = (sq / (1.0 + sq)) * acc * jax.lax.rsqrt(sq + SQUASH_EPS)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "block_n", "epilogue", "squash_dim", "interpret"))
def matmul_bias_act(p: jax.Array, w: jax.Array, bias: jax.Array, *,
                    block_m: int = 128, block_k: int = 128,
                    block_n: int = 128, epilogue: str = "none",
                    squash_dim: int = 0, interpret: bool) -> jax.Array:
    """p: [M, K], w: [K, N], bias: [N] -> epilogue(p @ w + bias): [M, N].

    ``epilogue="squash"`` treats every ``squash_dim`` consecutive output
    channels as one capsule and squashes it in-register before writeback
    (requires ``block_n`` and ``N`` to be multiples of ``squash_dim`` so
    ragged/clamped N tiles stay capsule-aligned).
    """
    m, k = p.shape
    _, n = w.shape
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    bm = max(1, min(block_m, m))
    bn = max(1, min(block_n, n))
    bk = max(1, min(block_k, k))
    if epilogue == "squash" and (squash_dim < 1 or bn % squash_dim
                                 or n % squash_dim):
        raise ValueError(
            f"squash epilogue needs a positive capsule dim dividing both "
            f"block_n ({bn}) and N ({n}); got squash_dim={squash_dim}")
    if k % bk:                                     # zero-pad K: a clamped tail
        pad = bk - k % bk                          # K-block would double-count
        p = jnp.pad(p, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        k += pad
    k_steps = k // bk
    kernel = functools.partial(_matmul_kernel, k_steps=k_steps,
                               epilogue=epilogue, squash_dim=squash_dim)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(p, w, bias.reshape(1, n))


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _at_b_kernel(a_ref, b_ref, o_ref):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32).T, b_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "block_n", "interpret"))
def matmul_at_b(a: jax.Array, b: jax.Array, *, block_m: int = 128,
                block_k: int = 128, block_n: int = 128,
                interpret: bool) -> jax.Array:
    """a: [M, K], b: [M, N] -> a^T @ b: [K, N] without an HBM transpose.

    The backward-pass dW matmul (patches^T @ dy): the shared M axis is the
    reduction here, so like the forward K axis it is zero-padded up to a
    multiple of ``block_m`` (a clamped tail block would double-count the
    overlap); ragged K/N tail blocks are rewrite-safe as in the forward.
    """
    m, k = a.shape
    mb, n = b.shape
    if m != mb:
        raise ValueError(f"matmul_at_b: M mismatch {m} vs {mb}")
    bm = max(1, min(block_m, m))
    bk = max(1, min(block_k, k))
    bn = max(1, min(block_n, n))
    if m % bm:
        pad = bm - m % bm
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        m += pad
    return pl.pallas_call(
        _at_b_kernel,
        grid=(pl.cdiv(k, bk), pl.cdiv(n, bn), m // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda ki, ni, mi: (mi, ki)),
            pl.BlockSpec((bm, bn), lambda ki, ni, mi: (mi, ni)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda ki, ni, mi: (ki, ni)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride", "h", "w"))
def col2im_patches(dp: jax.Array, *, kh: int, kw: int, stride: int,
                   h: int, w: int) -> jax.Array:
    """dp: [B, OH*OW, KH*KW*C] -> dx: [B, H, W, C] (fp32).

    The exact transpose of ``im2col_patches``: each kernel tap's
    cotangent slab is scatter-added back onto the input positions it was
    sliced from (the VJP of the XLA extraction).
    """
    bsz = dp.shape[0]
    c = dp.shape[2] // (kh * kw)
    x0 = jnp.zeros((bsz, h, w, c), dp.dtype)
    _, pull = jax.vjp(lambda x: _patches_xla(x, kh, kw, stride), x0)
    return pull(dp)[0].astype(jnp.float32)


# ---------------------------------------------------------------------------
# conv2d_im2col: forward + custom VJP
# ---------------------------------------------------------------------------

class _ConvStatics(NamedTuple):
    """Hashable non-differentiable schedule for the conv custom_vjp."""

    stride: int
    block_m: int
    block_k: int
    block_n: int
    epilogue: str
    squash_dim: int
    interpret: bool


def _conv_apply(st: _ConvStatics, x, w, bias):
    b, h, w_hw, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = (h - kh) // st.stride + 1
    ow = (w_hw - kw) // st.stride + 1
    patches = im2col_patches(x, kh=kh, kw=kw, stride=st.stride)
    out = matmul_bias_act(
        patches.reshape(b * oh * ow, kh * kw * cin),
        w.reshape(kh * kw * cin, cout), bias,
        block_m=st.block_m, block_k=st.block_k, block_n=st.block_n,
        epilogue=st.epilogue, squash_dim=st.squash_dim,
        interpret=st.interpret)
    return out.reshape(b, oh, ow, cout).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_core(st: _ConvStatics, x, w, bias):
    return _conv_apply(st, x, w, bias)


def _conv_core_fwd(st: _ConvStatics, x, w, bias):
    out = _conv_apply(st, x, w, bias)
    # Only the ReLU backward reads the saved output (its mask); keeping
    # the [B,OH,OW,Cout] activation alive to the backward for the other
    # epilogues would waste the largest conv tensor per layer per step.
    return out, (x, w, bias, out if st.epilogue == "relu" else None)


def _conv_core_bwd(st: _ConvStatics, res, dy):
    x, w, bias, out = res
    b, h, w_hw, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = (h - kh) // st.stride + 1
    ow = (w_hw - kw) // st.stride + 1
    m = b * oh * ow
    kk = kh * kw * cin
    dy2 = dy.reshape(m, cout).astype(jnp.float32)
    w2 = w.reshape(kk, cout)
    patches = im2col_patches(x, kh=kh, kw=kw, stride=st.stride)
    p2 = patches.reshape(m, kk)

    # Epilogue cotangent: ReLU masks from the saved output; the fused
    # per-capsule squash recomputes the pre-activation (one extra blocked
    # matmul -- the recompute the backward plan accounts for).
    if st.epilogue == "relu":
        dpre = dy2 * (out.reshape(m, cout) > 0)
    elif st.epilogue == "squash":
        pre = matmul_bias_act(p2, w2, bias, block_m=st.block_m,
                              block_k=st.block_k, block_n=st.block_n,
                              epilogue="none", interpret=st.interpret)
        caps = pre.reshape(m, cout // st.squash_dim, st.squash_dim)
        _, pull = jax.vjp(squash_reference, caps)
        dpre = pull(dy2.reshape(caps.shape))[0].reshape(m, cout)
    else:
        dpre = dy2

    dbias = jnp.sum(dpre, axis=0).astype(bias.dtype)
    dw = matmul_at_b(p2, dpre, block_m=st.block_m, block_k=st.block_k,
                     block_n=st.block_n, interpret=st.interpret)
    dpatches = matmul_bias_act(
        dpre, jnp.transpose(w2).astype(jnp.float32),
        jnp.zeros((kk,), jnp.float32),
        block_m=st.block_m, block_k=st.block_n, block_n=st.block_k,
        epilogue="none", interpret=st.interpret)
    dx = col2im_patches(dpatches.reshape(b, oh * ow, kk), kh=kh, kw=kw,
                        stride=st.stride, h=h, w=w_hw)
    return (dx.astype(x.dtype), dw.reshape(w.shape).astype(w.dtype), dbias)


_conv_core.defvjp(_conv_core_fwd, _conv_core_bwd)


@functools.partial(jax.jit, static_argnames=(
    "stride", "block_m", "block_k", "block_n", "epilogue", "squash_dim",
    "interpret"))
def conv2d_im2col(x: jax.Array, w: jax.Array, bias: jax.Array, *,
                  stride: int = 1, block_m: int = 128, block_k: int = 128,
                  block_n: int = 128, epilogue: str = "none",
                  squash_dim: int = 0, interpret: bool) -> jax.Array:
    """VALID conv as im2col matmul: x [B,H,W,Cin], w [KH,KW,Cin,Cout] HWIO.

    Returns ``epilogue(conv(x, w) + bias)`` as [B, OH, OW, Cout].  Block
    shapes come from the ExecutionPlan (see ``kernels/ops.py``).
    Differentiable: carries a custom VJP whose backward runs the Pallas
    ``matmul_at_b`` (dW) and ``matmul_bias_act`` (dpatches) kernels over
    the same block tiles, then ``col2im_patches`` (dx).
    """
    st = _ConvStatics(stride=stride, block_m=block_m, block_k=block_k,
                      block_n=block_n, epilogue=epilogue,
                      squash_dim=squash_dim, interpret=interpret)
    return _conv_core(st, x, w, bias)
