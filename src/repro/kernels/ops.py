"""Jit'd public wrappers for the Pallas kernels, driven by one ExecutionPlan.

A kernel's schedule has one source: the ``ExecutionPlan``
(``repro.core.execplan.compile_plan``).  The conv and squash wrappers
read their OpPlan; the routing wrappers take the ``RoutingLayer`` they
run (``CapsNetConfig.routing_stack()``) and the plan, and ``_schedule``
resolves the kernel statics from both at trace time.  Every wrapper
takes ``interpret``; the default ``None`` compiles the kernels with
Mosaic on a TPU and runs the Pallas interpreter on any other backend
(``should_interpret``).  The jnp references live in
``repro.kernels.ref`` and ``repro.core.capsnet``.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax

from repro.core import execplan, faults
from repro.kernels import ref
from repro.kernels.conv_im2col import conv2d_im2col as _conv2d
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.primary_routing import \
    primary_caps_routing as _primary_routing
from repro.kernels.primary_routing import \
    primary_caps_routing_nota as _primary_routing_nota
from repro.kernels.squash import squash as _squash
from repro.kernels.votes_routing import \
    res_caps_segment as _res_caps_segment
from repro.kernels.votes_routing import votes_routing as _votes_routing
from repro.kernels.votes_routing import \
    votes_routing_nota as _votes_routing_nota


def should_interpret(interpret: bool | None = None) -> bool:
    """Whether the Pallas kernels run in interpret mode: as the caller
    says, else exactly when the default backend is not a TPU (Mosaic only
    compiles for TPUs; everywhere else the interpreter is the executor)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def conv2d(x, w, b, plan, name: str, *, interpret: bool | None = None):
    """The plan's im2col conv op ``name``: x [B,H,W,Cin], w [KH,KW,Cin,Cout]
    (HWIO) -> [B,OH,OW,Cout].

    ``"Conv1"`` runs at stride 1 with the ReLU epilogue; ``"PrimaryCaps"``
    at ``plan.cfg.pc_stride``, with the per-capsule squash epilogue where
    the plan fuses it (``fuses_squash``).  The tiles are the OpPlan's
    ``block``.  Differentiable: the kernel's custom VJP reuses them for
    the backward matmuls and the col2im scatter.
    """
    op = plan.op(name)
    if name == "Conv1":
        stride, epilogue, squash_dim = 1, "relu", 0
    else:
        stride, squash_dim = plan.cfg.pc_stride, plan.cfg.primary_dim
        epilogue = "squash" if op.fuses_squash else "none"
    out = _conv2d(x, w, b, stride=stride, block_m=op.block.block_m,
                  block_k=op.block.block_k, block_n=op.block.block_n,
                  epilogue=epilogue, squash_dim=squash_dim,
                  interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_CONV2D, out)
    return out


@functools.lru_cache(maxsize=64)            # bounded like the plan caches
def _warn_bwd_fallback_once(msg: str) -> None:
    """Warn once per distinct infeasible-backward schedule (the message
    embeds shapes, budget, and the fallback schedule, so it IS the key);
    repeat calls hit the cache and stay silent."""
    warnings.warn(msg, RuntimeWarning, stacklevel=5)


class _Schedule(NamedTuple):
    """One routing layer's kernel statics, in ``res_caps_segment``'s
    per-half order."""

    iters: int
    num_classes: int
    mode: str
    block_i: int
    bwd_mode: str
    bwd_block_i: int
    lanes: str
    bwd_lanes: str
    nota: bool


def _schedule(lay, batch: int, plan, *, pipe: bool = False) -> _Schedule:
    """Resolve routing layer ``lay``'s kernel statics from ``plan`` for a
    call of ``batch`` rows.

    The forward schedule is ``plan.op(lay.name)`` (``PIPE_NAME`` for the
    pipe kernel).  The backward's is the plan's ``lay.name + "-bwd"`` op
    on a training plan; on a forward-only plan it is planned here at the
    plan's VMEM budget.  Where no backward fits that budget the forward
    runs as planned, and differentiating it reuses the forward schedule
    with a footprint no plan validated -- warned once per schedule.
    ``iters``, the output capsule count and ``nota`` are the layer's.
    """
    if batch > plan.batch:
        # A bigger batch than planned would scale the VMEM scratch past
        # the footprint the plan validated (smaller is safe: the
        # footprint is an upper bound).
        raise ValueError(
            f"{lay.name}: batch {batch} exceeds the plan's batch "
            f"{plan.batch}; recompile the plan for this batch")
    op = plan.op(execplan.PIPE_NAME if pipe else lay.name)
    if plan.train:
        bwd = plan.op(lay.name + execplan.BWD_SUFFIX)
        bwd_mode, bwd_block_i, bwd_lanes = bwd.mode, bwd.block_i, bwd.lanes
    else:
        try:
            bwd = execplan.plan_votes_routing_bwd(
                lay.in_caps, lay.in_dim, lay.jd, lay.num_caps, batch=batch,
                iters=lay.iters, vmem_budget=plan.vmem_budget,
                name=lay.name)
            bwd_mode, bwd_block_i, bwd_lanes = (bwd.mode, bwd.block_i,
                                                bwd.lanes)
        except execplan.PlanError as err:
            _warn_bwd_fallback_once(
                f"{lay.name}: no feasible backward schedule under the "
                f"{plan.vmem_budget} B VMEM budget ({err}); the forward "
                f"runs fine, but differentiating this call will reuse the "
                f"forward schedule (mode={op.mode!r}, block_i="
                f"{op.block_i}) with a backward VMEM footprint the plan "
                f"never validated")
            bwd_mode, bwd_block_i, bwd_lanes = op.mode, op.block_i, op.lanes
    return _Schedule(lay.iters, lay.num_caps, op.mode, op.block_i, bwd_mode,
                     bwd_block_i, op.lanes, bwd_lanes, lay.nota)


def votes_routing(u: jax.Array, w: jax.Array, lay, plan, *,
                  interpret: bool | None = None) -> jax.Array:
    """u: [B, I, C], w: [I, J*D, C] -> v: [B, J*D]: routing layer ``lay``'s
    fused votes + routing (u_hat never leaves the chip), on the schedule
    ``_schedule`` resolves from ``plan``.  Differentiable: the custom
    VJP's backward kernel keeps ``d u_hat`` on-chip too."""
    s = _schedule(lay, u.shape[0], plan)
    kernel = _votes_routing_nota if s.nota else _votes_routing
    out = kernel(u, w, iters=s.iters, num_classes=s.num_classes,
                 mode=s.mode, block_i=s.block_i, bwd_mode=s.bwd_mode,
                 bwd_block_i=s.bwd_block_i, lanes=s.lanes,
                 bwd_lanes=s.bwd_lanes,
                 interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_VOTES_ROUTING, out)
    return out


def primary_routing(x: jax.Array, w_pc: jax.Array, b_pc: jax.Array,
                    w_cc: jax.Array, lay, plan, *,
                    interpret: bool | None = None) -> jax.Array:
    """Pipelined PrimaryCaps conv + votes/routing of the first routing
    layer ``lay`` as ONE kernel: x is the Conv1 output [B, H, W, Cin],
    w_pc/b_pc the PrimaryCaps conv params, w_cc [I, J*D, C] the routing
    weights -> v [B, J*D].  The inter-layer activation u streams from
    VMEM scratch, never HBM.

    The forward schedule is the plan's ``"PrimaryCaps-Routing"`` op (its
    conv tiles also serve the backward's replay); the routing backward's
    resolves as for ``votes_routing``: the pipelined VJP composes the
    per-op backward kernels, so the plan's backward OpPlans apply
    unchanged.
    """
    s = _schedule(lay, x.shape[0], plan, pipe=True)
    op = plan.op(execplan.PIPE_NAME)
    kernel = _primary_routing_nota if s.nota else _primary_routing
    out = kernel(
        x, w_pc, b_pc, w_cc, stride=plan.cfg.pc_stride, iters=s.iters,
        num_classes=s.num_classes, mode=s.mode, block_i=s.block_i,
        block_k=op.block_k, bwd_mode=s.bwd_mode, bwd_block_i=s.bwd_block_i,
        bwd_lanes=s.bwd_lanes, conv_block_m=op.block.block_m,
        conv_block_k=op.block.block_k, conv_block_n=op.block.block_n,
        interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_PRIMARY_ROUTING, out)
    return out


def res_caps_segment(x: jax.Array, ws, pairs, plan, *,
                     interpret: bool | None = None) -> jax.Array:
    """Reversible residual capsule segment: x [B, I, C] through a maximal
    run of ``ResCapsBlock`` coupling pairs -> [B, I, C].

    ``pairs`` is a tuple of ``(f_layer, g_layer)`` ``RoutingLayer`` pairs
    (from ``CapsNetConfig.routing_stack()``); ``ws`` the matching flat
    per-half weights ``[in_caps, jd, in_dim]``.  Each half runs the fused
    votes+routing megakernel with a residual-add epilogue, scheduled by
    its own plan op.  Differentiable with NO saved activations: the
    backward inverts the coupling block-by-block from the segment output
    (see ``kernels.votes_routing._res_segment_bwd``).
    """
    blocks = tuple(
        (lf.num_caps, tuple(_schedule(lf, x.shape[0], plan)),
         tuple(_schedule(lg, x.shape[0], plan))) for lf, lg in pairs)
    out = _res_caps_segment(x, tuple(ws), blocks=blocks,
                            interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_RES_CAPS_SEGMENT, out)
    return out


def squash(x: jax.Array, plan, *, interpret: bool | None = None
           ) -> jax.Array:
    """The primary capsules' standalone squash, blocked over the plan's
    ``PrimaryCaps`` rows (where the plan does not fuse it into the
    conv's epilogue)."""
    out = _squash(x, block_rows=plan.op("PrimaryCaps").block_rows,
                  interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_SQUASH, out)
    return out


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            interpret: bool | None = None) -> jax.Array:
    out = _rmsnorm(x, weight, eps=eps, interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_RMSNORM, out)
    return out


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, interpret=None):
    out = _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                 scale=scale, interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_FLASH_ATTENTION, out)
    return out


__all__ = ["conv2d", "votes_routing", "primary_routing", "res_caps_segment",
           "squash", "rmsnorm", "flash_attention", "ref", "should_interpret"]
