"""Pipelined PrimaryCaps -> ClassCaps megakernel: u never touches HBM.

CapStore's energy win is a WHOLE-network claim: the paper keeps
inter-layer activations on-chip (DESCNet's inter-layer scratchpad,
CapsAcc's cross-layer reuse), not just the per-op intermediates.  After
PR 3/5 the routing megakernel already keeps ``u_hat`` in VMEM, but the
PrimaryCaps output ``u [B, I, C]`` still round-tripped HBM between two
``pallas_call``s.  This kernel runs the producer AND the consumer as ONE
``pallas_call``:

  produce   grid steps ``0 .. k_steps-1``.  Each step streams one K
            tile of the im2col patches and conv weight past a resident
            ``[B, N, P]`` pre-activation scratch (transposed, so the
            positions lie on lanes); the last K step applies the bias +
            per-capsule squash epilogue and lays the capsules out in the
            ``[B, C, I_pad]`` scratch the consumer reads (u is the
            SMALLEST tensor in the pair -- ~I*C floats per batch element
            -- which is exactly why the paper parks it on-chip).
            Patches and the conv weight are read exactly ONCE (a
            per-i-block recompute would re-stream the 21 MB MNIST conv
            weight once per i-block -- strictly worse traffic than the
            unfused pair).

  consume   the remaining ``(iters + 1) * n_blocks`` grid steps are the
            fused ``votes_routing`` schedule (resident votes scratch or
            votes recomputed from re-streamed W tiles), reading u
            i-blocks from the produce scratch instead of an HBM operand.
            The FIRST consume block rides the last produce step (u is
            fully squashed by in-body program order), so the pair
            overlaps by one step.

The capsules land in the scratch group-major (lane ``i' = g*P + p``)
where the conv's rows are position-major (``i = p*G + g``); routing is a
sum over capsules, so the wrapper permutes W_cc's rows to match and the
output is unchanged.  Lanes ``>= I`` stay at their zero initialisation
and are inert under the routing reduction (the ``votes_routing`` padding
argument, minus the host-side copy).

**Backward** (``jax.custom_vjp``): recompute-from-patches.  The saved
residuals are the raw operands ``(x, W_pc, b_pc, W_cc)``; the backward
replays the producer (im2col + blocked matmul, epilogue recomputed like
the fused-squash conv backward), feeds the rebuilt u to the routing
backward kernels (``votes_routing._vr_grad`` -- ``d u_hat`` stays in
VMEM), pulls the squash VJP, and finishes with the conv backward's
``matmul_at_b`` / ``matmul_bias_act`` kernels and ``col2im_patches``.  It
composes exactly the per-op backward OpPlans, so a pipelined training
plan keeps the per-op backward schedule unchanged.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.capsnet import squash
from repro.kernels.conv_im2col import (COMPILER_PARAMS, col2im_patches,
                                       im2col_patches, matmul_at_b,
                                       matmul_bias_act)
from repro.kernels.votes_routing import (LAYOUTS, _CapsLanes, _route_block,
                                         _rows, _vr_grad, _VRStatics)

MODES = ("resident", "streamed")


def _produce_u(t, patches_ref, wpc_ref, bias_ref, pre_scr, u_scr, *,
               k_steps: int, groups: int, caps_dim: int, p_pos: int):
    """Produce phase: accumulate one K tile of the im2col matmul into the
    transposed pre-activation scratch ``[B, N, P]``; the last K step adds
    the bias, squashes each capsule (channels ``g*C .. (g+1)*C`` of a
    position) and writes group ``g`` to lanes ``g*P .. (g+1)*P`` of the
    capsule scratch ``u [B, C, I_pad]``.  Lanes ``>= I`` keep their zero
    initialisation -- the i-axis padding the consume phase relies on."""

    @pl.when(t == 0)
    def _():
        pre_scr[...] = jnp.zeros_like(pre_scr)
        u_scr[...] = jnp.zeros_like(u_scr)

    @pl.when(t < k_steps)
    def _():
        w_t = wpc_ref[...].astype(jnp.float32).T               # [N, bk]
        for b in range(pre_scr.shape[0]):
            pre_scr[b] += jax.lax.dot_general(
                w_t, patches_ref[b].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)              # [N, P]

        @pl.when(t == k_steps - 1)
        def _():
            bsz = pre_scr.shape[0]
            pre = pre_scr[...] + bias_ref[...][None]
            caps = squash(pre.reshape(bsz, groups, caps_dim, p_pos), axis=2)
            for g in range(groups):
                u_scr[:, :, pl.ds(g * p_pos, p_pos)] = caps[:, g]


def _pipe_kernel(patches_ref, wpc_ref, bias_ref, wcc_ref, o_ref, pre_scr,
                 u_scr, b_scr, s_scr, v_scr, *votes, k_steps: int,
                 p_pos: int, groups: int, caps_dim: int, n_passes: int,
                 n_blocks: int, block_i: int, resident: bool, nota: bool):
    """Grid ``k_steps - 1 + n_passes * n_blocks``.  The consume steps are
    ``votes_routing._routing_kernel``'s, reading u from the produce
    scratch instead of an HBM operand.  The first consume block OVERLAPS
    the last produce step: u is fully squashed by the time the body
    reaches it (in-body program order)."""
    t = pl.program_id(0)
    _produce_u(t, patches_ref, wpc_ref, bias_ref, pre_scr, u_scr,
               k_steps=k_steps, groups=groups, caps_dim=caps_dim,
               p_pos=p_pos)

    @pl.when(t >= k_steps - 1)
    def _():
        q = t - (k_steps - 1)
        p = q // n_blocks
        ib = q % n_blocks
        rows = _rows(ib, block_i)
        if resident:
            votes_scr = votes[0]

            @pl.when(p == 0)
            def _():
                votes_scr[:, :, :, rows] = _CapsLanes.votes(
                    u_scr[:, :, rows], wcc_ref[...])

            uh4 = votes_scr[:, :, :, rows]
        else:
            uh4 = _CapsLanes.votes(u_scr[:, :, rows], wcc_ref[...])
        _route_block(_CapsLanes, p, ib, uh4, rows, b_scr, s_scr, v_scr,
                     o_ref, None, n_passes=n_passes, n_blocks=n_blocks,
                     nota=nota)


# ---------------------------------------------------------------------------
# Forward dispatch + custom VJP
# ---------------------------------------------------------------------------

class _PRStatics(NamedTuple):
    """Hashable non-differentiable schedule for the pipelined custom_vjp."""

    stride: int
    iters: int
    num_classes: int
    mode: str
    block_i: int
    block_k: int             # produce-phase K tile
    bwd_mode: str            # routing backward (votes_routing._vr_grad)
    bwd_block_i: int
    conv_block_m: int        # producer-replay matmul tiles (backward)
    conv_block_k: int
    conv_block_n: int
    interpret: bool
    bwd_lanes: str = "caps"  # routing backward layout (votes_routing)
    nota: bool = False       # none-of-the-above category in the routing


def _pr_apply(st: _PRStatics, x, w_pc, b_pc, w_cc):
    bsz, h, w_hw, _ = x.shape
    kh, kw, cin, n_ch = w_pc.shape
    oh = (h - kh) // st.stride + 1
    ow = (w_hw - kw) // st.stride + 1
    p_pos = oh * ow
    kk = kh * kw * cin
    i_dim, jd, caps_dim = w_cc.shape
    groups = n_ch // caps_dim
    j = st.num_classes
    d = jd // j

    patches = im2col_patches(x, kh=kh, kw=kw, stride=st.stride)  # [B,P,K]
    wpc2 = w_pc.reshape(kk, n_ch)
    bk = max(1, min(st.block_k, kk))
    if kk % bk:                        # zero-pad K (conv_im2col idiom): a
        pad = bk - kk % bk             # clamped tail K block would
        patches = jnp.pad(patches, ((0, 0), (0, 0), (0, pad)))   # double-
        wpc2 = jnp.pad(wpc2, ((0, pad), (0, 0)))                 # count rows
    k_steps = patches.shape[2] // bk

    block_i = max(1, min(st.block_i, i_dim))
    n_blocks = pl.cdiv(i_dim, block_i)
    i_pad = n_blocks * block_i
    # Capsule lanes in the order the produce phase writes them: group-
    # major (i' = g*P + p), where the conv emits position-major rows
    # (i = p*G + g).  Routing sums over i, so only W_cc's rows move.
    w_t = w_cc.reshape(p_pos, groups, jd, caps_dim).transpose(3, 2, 1, 0)
    w_t = jnp.pad(w_t.reshape(caps_dim, j, d, i_dim),
                  ((0, 0),) * 3 + ((0, i_pad - i_dim),))
    n_passes = st.iters + 1
    resident = st.mode == "resident"
    last_k = k_steps - 1

    def consume_block(t):
        q = jnp.maximum(t - last_k, 0)
        if resident:     # W_cc parks on its last block after the votes pass
            return jnp.minimum(q, n_blocks - 1)
        return q % n_blocks

    scratch = [
        pltpu.VMEM((bsz, n_ch, p_pos), jnp.float32),       # pre-activation^T
        pltpu.VMEM((bsz, caps_dim, i_pad), jnp.float32),   # u
        pltpu.VMEM((bsz, j, i_pad), jnp.float32),          # logits b
        pltpu.VMEM((bsz, j, d, 1), jnp.float32),           # s accumulator
        pltpu.VMEM((bsz, j, d, 1), jnp.float32),           # squashed v
    ]
    if resident:
        scratch.append(pltpu.VMEM((bsz, j, d, i_pad), jnp.float32))  # votes
    kernel = functools.partial(_pipe_kernel, k_steps=k_steps, p_pos=p_pos,
                               groups=groups, caps_dim=caps_dim,
                               n_passes=n_passes, n_blocks=n_blocks,
                               block_i=block_i, resident=resident,
                               nota=st.nota)
    # Produce-phase operands park on their final tile after step
    # k_steps-1 (unchanged block index -> no refetch); W_cc holds its
    # first i-block until the consume steps start walking it.
    out = pl.pallas_call(
        kernel,
        grid=(last_k + n_passes * n_blocks,),
        in_specs=[
            pl.BlockSpec((bsz, p_pos, bk),
                         lambda t: (0, 0, jnp.minimum(t, last_k))),
            pl.BlockSpec((bk, n_ch), lambda t: (jnp.minimum(t, last_k), 0)),
            pl.BlockSpec((n_ch, 1), lambda t: (0, 0)),
            pl.BlockSpec((caps_dim, j, d, block_i),
                         lambda t: (0, 0, 0, consume_block(t))),
        ],
        out_specs=pl.BlockSpec((bsz, j, d, 1), lambda t: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, j, d, 1), x.dtype),
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=st.interpret,
    )(patches, wpc2, b_pc.reshape(n_ch, 1), w_t)
    return out.reshape(bsz, jd)


def _pr_grad(st: _PRStatics, x, w_pc, b_pc, w_cc, g):
    """Recompute-from-patches backward: replay the producer, run the
    routing backward on the rebuilt u, pull the squash VJP, finish with
    the conv backward kernels -- exactly the per-op backward OpPlans."""
    bsz, h, w_hw, cin = x.shape
    kh, kw, _, n_ch = w_pc.shape
    oh = (h - kh) // st.stride + 1
    ow = (w_hw - kw) // st.stride + 1
    p_pos = oh * ow
    m = bsz * p_pos
    kk = kh * kw * cin
    i_dim, jd, caps_dim = w_cc.shape
    groups = n_ch // caps_dim

    patches = im2col_patches(x, kh=kh, kw=kw, stride=st.stride)
    p2 = patches.reshape(m, kk)
    wpc2 = w_pc.reshape(kk, n_ch)
    pre = matmul_bias_act(p2, wpc2, b_pc, block_m=st.conv_block_m,
                          block_k=st.conv_block_k, block_n=st.conv_block_n,
                          epilogue="none", interpret=st.interpret)
    caps = pre.reshape(m, groups, caps_dim)
    u3, pull = jax.vjp(squash, caps)
    u = u3.reshape(bsz, i_dim, caps_dim)

    vr_st = _VRStatics(iters=st.iters, num_classes=st.num_classes,
                       mode=st.bwd_mode, block_i=st.bwd_block_i,
                       bwd_mode=st.bwd_mode, bwd_block_i=st.bwd_block_i,
                       interpret=st.interpret, bwd_lanes=st.bwd_lanes,
                       nota=st.nota)
    du, dw_cc = _vr_grad(vr_st, u, w_cc, g.astype(jnp.float32))

    dpre = pull(du.reshape(m, groups, caps_dim))[0].reshape(m, n_ch)
    dbias = jnp.sum(dpre, axis=0).astype(b_pc.dtype)
    dw_pc = matmul_at_b(p2, dpre, block_m=st.conv_block_m,
                        block_k=st.conv_block_k, block_n=st.conv_block_n,
                        interpret=st.interpret)
    dpatches = matmul_bias_act(
        dpre, jnp.transpose(wpc2).astype(jnp.float32),
        jnp.zeros((kk,), jnp.float32),
        block_m=st.conv_block_m, block_k=st.conv_block_n,
        block_n=st.conv_block_k, epilogue="none", interpret=st.interpret)
    dx = col2im_patches(dpatches.reshape(bsz, p_pos, kk), kh=kh, kw=kw,
                        stride=st.stride, h=h, w=w_hw)
    return (dx.astype(x.dtype), dw_pc.reshape(w_pc.shape).astype(w_pc.dtype),
            dbias, dw_cc.astype(w_cc.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pr_core(st: _PRStatics, x, w_pc, b_pc, w_cc):
    return _pr_apply(st, x, w_pc, b_pc, w_cc)


def _pr_core_fwd(st: _PRStatics, x, w_pc, b_pc, w_cc):
    # Residuals are the raw operands: u is recomputed from patches in the
    # backward, so the inter-layer activation never exists off-chip in
    # either direction.
    return _pr_apply(st, x, w_pc, b_pc, w_cc), (x, w_pc, b_pc, w_cc)


def _pr_core_bwd(st: _PRStatics, res, g):
    return _pr_grad(st, *res, g)


_pr_core.defvjp(_pr_core_fwd, _pr_core_bwd)


def _primary_caps_routing(x: jax.Array, w_pc: jax.Array, b_pc: jax.Array,
                          w_cc: jax.Array, *, stride: int = 2,
                          iters: int = 3, num_classes: int = 10,
                          mode: str = "resident", block_i: int = 128,
                          block_k: int = 512, bwd_mode: str | None = None,
                          bwd_block_i: int | None = None,
                          bwd_lanes: str = "caps", conv_block_m: int = 128,
                          conv_block_k: int = 128, conv_block_n: int = 128,
                          nota: bool = False,
                          interpret: bool) -> jax.Array:
    """x: [B, H, W, Cin] (Conv1 output), w_pc: [KH, KW, Cin, N] HWIO,
    b_pc: [N], w_cc: [I, J*D, C] -> v: [B, J*D].

    ONE ``pallas_call`` running the PrimaryCaps conv (im2col matmul +
    bias + per-capsule squash) and the full votes+routing consumer with
    the inter-layer activation u resident in VMEM scratch.  Schedule
    parameters come from the ExecutionPlan
    (``plan.op("PrimaryCaps-Routing")``); see ``repro.kernels.ops`` for
    the plan-aware wrapper.  The unfused two-call path
    (``conv2d_im2col`` + ``votes_routing``) remains the fallback and the
    parity oracle.

    Differentiable: the custom VJP replays the producer from patches and
    composes the per-op backward kernels (routing backward per
    ``bwd_mode``/``bwd_block_i``/``bwd_lanes``, conv backward over the
    ``conv_block_*`` tiles).  ``nota`` routes with the none-of-the-above
    category (``capsnet.couplings``), forward and backward.
    """
    i_dim, jd, caps_dim = w_cc.shape
    kh, kw, _, n_ch = w_pc.shape
    if jd % num_classes:
        raise ValueError(
            f"votes dim {jd} not divisible by classes {num_classes}")
    if n_ch % caps_dim:
        raise ValueError(
            f"conv channels {n_ch} not divisible by capsule dim {caps_dim}")
    oh = (x.shape[1] - kh) // stride + 1
    ow = (x.shape[2] - kw) // stride + 1
    if oh * ow * (n_ch // caps_dim) != i_dim:
        raise ValueError(
            f"W_cc expects {i_dim} capsules, producer emits "
            f"{oh * ow * (n_ch // caps_dim)}")
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; choose from {MODES}")
    if bwd_lanes not in LAYOUTS:
        raise ValueError(f"unknown bwd_lanes {bwd_lanes!r}; choose from "
                         f"{LAYOUTS}")
    if iters < 1:
        raise ValueError(f"routing needs iters >= 1, got {iters}")
    bwd_mode = bwd_mode or mode
    st = _PRStatics(stride=stride, iters=iters, num_classes=num_classes,
                    mode=mode, block_i=max(1, min(block_i, i_dim)),
                    block_k=block_k, bwd_mode=bwd_mode,
                    bwd_block_i=max(1, min(bwd_block_i or block_i, i_dim)),
                    conv_block_m=conv_block_m, conv_block_k=conv_block_k,
                    conv_block_n=conv_block_n, interpret=interpret,
                    bwd_lanes=bwd_lanes, nota=nota)
    return _pr_core(st, x, w_pc, b_pc, w_cc)


_STATIC_ARGNAMES = (
    "stride", "iters", "num_classes", "mode", "block_i", "block_k",
    "bwd_mode", "bwd_block_i", "bwd_lanes", "conv_block_m", "conv_block_k",
    "conv_block_n", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES)
def primary_caps_routing(x: jax.Array, w_pc: jax.Array, b_pc: jax.Array,
                         w_cc: jax.Array, **schedule) -> jax.Array:
    """The pipelined PrimaryCaps -> routing kernel with its custom VJP
    (``_primary_caps_routing``: operands, ``schedule`` keywords and
    result), jitted.  A device trace names the call after this jit:
    ``primary_caps_routing.N``; in training ``jvp_jit_
    primary_caps_routing__.N``, and the routing backward ``transpose_jvp_
    jit_primary_caps_routing___.N``."""
    return _primary_caps_routing(x, w_pc, b_pc, w_cc, nota=False, **schedule)


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES)
def primary_caps_routing_nota(x: jax.Array, w_pc: jax.Array,
                              b_pc: jax.Array, w_cc: jax.Array,
                              **schedule) -> jax.Array:
    """``primary_caps_routing`` with the none-of-the-above category in the
    routing, forward and backward, under a jit of its own name so that a
    trace tells it apart (``primary_caps_routing_nota.N``; in training
    ``jvp_jit_primary_caps_routing_nota__.N`` and ``transpose_jvp_jit_
    primary_caps_routing_nota___.N``)."""
    return _primary_caps_routing(x, w_pc, b_pc, w_cc, nota=True, **schedule)
