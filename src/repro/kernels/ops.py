"""Jit'd public wrappers for the Pallas kernels, driven by one ExecutionPlan.

Every wrapper takes ``interpret``; the default ``None`` compiles the
kernels with Mosaic on a TPU and runs the Pallas interpreter on any other
backend (``should_interpret``).  Block shapes come from an ``ExecutionPlan``
(``repro.core.execplan.compile_plan``) when one is passed; otherwise the
planner pick is computed once per shape and memoized -- wrappers never
re-run the block-shape DSE per invocation.  The oracles live in
``repro.kernels.ref``.
"""

from __future__ import annotations

import functools
import warnings

import jax

from repro.core import execplan, faults
from repro.core.planner import VMEM_BYTES, MatmulWorkload, plan_matmul
from repro.kernels import ref
from repro.kernels.caps_votes import caps_votes as _caps_votes
from repro.kernels.conv_im2col import conv2d_im2col as _conv2d
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.primary_routing import \
    primary_caps_routing as _primary_routing
from repro.kernels.primary_routing import \
    primary_caps_routing_nota as _primary_routing_nota
from repro.kernels.routing import routing as _routing
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


@functools.lru_cache(maxsize=64)            # m folds in the batch: bounded
def planned_conv_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """CapStore planner pick for a conv's im2col matmul tiles (memoized,
    fp32 elements -- the dtype the conv kernels run in)."""
    plan = plan_matmul(MatmulWorkload(m=m, k=k, n=n, in_bytes=4))
    return plan.block_m, plan.block_k, plan.block_n


def conv2d(x, w, b, *, stride: int = 1, plan_op=None, epilogue: str = "none",
           squash_dim: int = 0, interpret: bool | None = None):
    """Plan-driven im2col conv: x [B,H,W,Cin], w [KH,KW,Cin,Cout] (HWIO).

    ``plan_op`` is the matching ``OpPlan`` (``plan.op("Conv1")`` /
    ``plan.op("PrimaryCaps")``); without one the planner pick is computed
    once per shape and memoized.  A plan op that fuses the squash
    activation (``plan_op.fuses_squash``) forces the squash epilogue --
    callers only supply ``squash_dim``.  Differentiable: the kernel's
    custom VJP reuses the same block tiles for the backward matmuls and
    the col2im scatter.
    """
    if plan_op is not None:
        bm, bk, bn = (plan_op.block.block_m, plan_op.block.block_k,
                      plan_op.block.block_n)
        if plan_op.fuses_squash:
            epilogue = "squash"
    else:
        kh, kw, cin, cout = w.shape
        oh = (x.shape[1] - kh) // stride + 1
        ow = (x.shape[2] - kw) // stride + 1
        bm, bk, bn = planned_conv_blocks(x.shape[0] * oh * ow,
                                         kh * kw * cin, cout)
    out = _conv2d(x, w, b, stride=stride, block_m=bm, block_k=bk,
                  block_n=bn, epilogue=epilogue, squash_dim=squash_dim,
                  interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_CONV2D, out)
    return out


@functools.lru_cache(maxsize=64)                    # bounded: was unbounded
def planned_block_i(num_caps: int, caps_dim: int, out_dim: int,
                    batch: int = 1, vmem_budget: int = VMEM_BYTES) -> int:
    """CapStore planner pick for the split caps-votes i-tile (memoized).

    Shares ``execplan._votes_block_i_raw``: the planner block is shrunk
    until the kernel's footprint at the REAL ``batch`` fits the budget
    (the old pick ignored batch, so a batched call could exceed the
    footprint the planner guarantees), and only clamped to ``num_caps``
    -- never degenerating to 1 for non-power-of-two capsule counts.
    """
    return execplan._votes_block_i_raw(num_caps, caps_dim, out_dim,
                                       batch, vmem_budget)


def caps_votes(u: jax.Array, w: jax.Array, *, plan=None,
               block_i: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """u: [B, I, C], w: [I, N, C] -> [B, I, N] (split-path oracle/fallback;
    the plan executes the fused ``votes_routing`` instead)."""
    if block_i is None:
        if plan is not None:
            block_i = plan.op(execplan.FUSED_NAME).block_i
        else:
            block_i = planned_block_i(u.shape[1], u.shape[2], w.shape[1],
                                      u.shape[0])
    out = _caps_votes(u, w, block_i=block_i,
                      interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_CAPS_VOTES, out)
    return out


def routing(u_hat: jax.Array, *, plan=None, iters: int | None = None,
            num_classes: int | None = None,
            interpret: bool | None = None) -> jax.Array:
    """Split-path routing oracle; it has no none-of-the-above category
    and refuses a plan that asks for one."""
    if iters is None:
        iters = plan.cfg.routing_iters if plan is not None else 3
    if num_classes is None:
        num_classes = plan.cfg.num_classes if plan is not None else 10
    nota = plan is not None and plan.cfg.none_of_the_above
    out = _routing(u_hat, iters=iters, num_classes=num_classes, nota=nota,
                   interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_ROUTING, out)
    return out


@functools.lru_cache(maxsize=64)
def planned_votes_routing(num_caps: int, caps_dim: int, jd: int,
                          num_classes: int, iters: int, batch: int,
                          vmem_budget: int = VMEM_BYTES
                          ) -> tuple[str, int, str]:
    """Memoized (mode, block_i, lanes) decision for the fused
    megakernel."""
    sched = execplan.plan_votes_routing(num_caps, caps_dim, jd, num_classes,
                                        batch=batch, iters=iters,
                                        vmem_budget=vmem_budget)
    return sched.mode, sched.block_i, sched.lanes


@functools.lru_cache(maxsize=64)            # bounded like the plan caches
def _warn_bwd_fallback_once(msg: str) -> None:
    """Warn once per distinct infeasible-backward schedule (the message
    embeds shapes, budget, and the fallback schedule, so it IS the key);
    repeat calls hit the cache and stay silent."""
    warnings.warn(msg, RuntimeWarning, stacklevel=4)


@functools.lru_cache(maxsize=64)
def planned_votes_routing_bwd(num_caps: int, caps_dim: int, jd: int,
                              num_classes: int, iters: int, batch: int,
                              vmem_budget: int = VMEM_BYTES
                              ) -> tuple[str, int, str]:
    """Memoized (mode, block_i, lanes) decision for the fused BACKWARD
    kernel (independent of the forward's: its scratch is larger)."""
    sched = execplan.plan_votes_routing_bwd(
        num_caps, caps_dim, jd, num_classes, batch=batch, iters=iters,
        vmem_budget=vmem_budget)
    return sched.mode, sched.block_i, sched.lanes


def votes_routing(u: jax.Array, w: jax.Array, *, plan=None,
                  op_name: str | None = None,
                  iters: int | None = None, num_classes: int | None = None,
                  mode: str | None = None, block_i: int | None = None,
                  bwd_mode: str | None = None, bwd_block_i: int | None = None,
                  lanes: str | None = None, bwd_lanes: str | None = None,
                  nota: bool | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """u: [B, I, C], w: [I, J*D, C] -> v: [B, J*D]: fused votes + routing
    (u_hat never leaves the chip).  Schedule (``mode``/``block_i``/
    ``lanes``) comes
    from ``plan.op(op_name)`` -- default ``"ClassCaps-Routing"``, the
    final classification layer; deep-stack callers pass the intermediate
    layer's plan-op name (``"ClassCaps-Routing[0]"``, ...) -- or the
    memoized plan decision.

    Differentiable: under ``jax.grad`` the backward schedule
    (``bwd_mode``/``bwd_block_i``) comes from the plan's backward op
    (``compile_plan(train=True)``), falling back to the memoized backward
    plan decision at the plan's VMEM budget -- ``d u_hat`` stays on-chip
    either way.  ``nota`` (the none-of-the-above category) comes from the
    plan op unless given; without a plan it is off.
    """
    if op_name is None:
        op_name = execplan.FUSED_NAME
    if nota is None:
        nota = plan is not None and plan.op(op_name).nota
    if iters is None:
        iters = plan.cfg.routing_iters if plan is not None else 3
    if num_classes is None:
        num_classes = plan.cfg.num_classes if plan is not None else 10
    if mode is None or block_i is None:
        if plan is not None:
            if u.shape[0] > plan.batch:
                # A bigger batch than planned would scale the VMEM scratch
                # past the footprint the plan validated (smaller is safe:
                # the footprint is an upper bound).
                raise ValueError(
                    f"votes_routing: batch {u.shape[0]} exceeds the plan's "
                    f"batch {plan.batch}; recompile the plan for this batch")
            op = plan.op(op_name)
            mode = mode or op.mode
            block_i = block_i or op.block_i
            lanes = lanes or op.lanes
        else:
            pmode, pbi, planes = planned_votes_routing(
                u.shape[1], u.shape[2], w.shape[1], num_classes, iters,
                u.shape[0])
            mode = mode or pmode
            block_i = block_i or pbi
            lanes = lanes or planes
    lanes = lanes or "caps"
    if bwd_mode is None or bwd_block_i is None:
        budget = plan.vmem_budget if plan is not None else VMEM_BYTES
        bwd_op = None
        if plan is not None and plan.train:
            bwd_op = plan.op(op_name + execplan.BWD_SUFFIX)
        if bwd_op is not None:
            bwd_mode = bwd_mode or bwd_op.mode
            bwd_block_i = bwd_block_i or bwd_op.block_i
            bwd_lanes = bwd_lanes or bwd_op.lanes
        else:
            try:
                pbmode, pbbi, pblanes = planned_votes_routing_bwd(
                    u.shape[1], u.shape[2], w.shape[1], num_classes, iters,
                    u.shape[0], budget)
            except execplan.PlanError as err:
                # Forward-only callers must not fail on backward planning;
                # a caller who then differentiates anyway gets the forward
                # schedule (numerically correct, footprint model exceeded)
                # -- warned ONCE per schedule so the silent-footprint case
                # is at least visible.
                _warn_bwd_fallback_once(
                    f"votes_routing: no feasible backward schedule "
                    f"under the {budget} B VMEM budget ({err}); the "
                    f"forward runs fine, but differentiating this call "
                    f"will reuse the forward schedule "
                    f"(mode={mode!r}, block_i={block_i}) with a "
                    f"backward VMEM footprint the plan never validated")
                pbmode, pbbi, pblanes = mode, block_i, lanes
            bwd_mode = bwd_mode or pbmode
            bwd_block_i = bwd_block_i or pbbi
            bwd_lanes = bwd_lanes or pblanes
    kernel = _votes_routing_nota if nota else _votes_routing
    out = kernel(u, w, iters=iters, num_classes=num_classes, mode=mode,
                 block_i=block_i, bwd_mode=bwd_mode, bwd_block_i=bwd_block_i,
                 lanes=lanes, bwd_lanes=bwd_lanes or lanes,
                 interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_VOTES_ROUTING, out)
    return out


@functools.lru_cache(maxsize=64)
def planned_primary_routing(p_pos: int, k_in: int, n_ch: int, num_caps: int,
                            caps_dim: int, jd: int, num_classes: int,
                            iters: int, batch: int,
                            vmem_budget: int = VMEM_BYTES
                            ) -> tuple[str, int, int, tuple[int, int, int]]:
    """Memoized (mode, block_i, block_k, conv tiles) decision for the
    pipelined PrimaryCaps->ClassCaps megakernel."""
    sched = execplan.plan_primary_routing(
        p_pos, k_in, n_ch, num_caps, caps_dim, jd, num_classes,
        batch=batch, iters=iters, vmem_budget=vmem_budget)
    return (sched.mode, sched.block_i, sched.block_k,
            (sched.block.block_m, sched.block.block_k, sched.block.block_n))


def primary_routing(x: jax.Array, w_pc: jax.Array, b_pc: jax.Array,
                    w_cc: jax.Array, *, plan=None, stride: int | None = None,
                    iters: int | None = None, num_classes: int | None = None,
                    routing_op_name: str | None = None,
                    mode: str | None = None, block_i: int | None = None,
                    block_k: int | None = None, bwd_mode: str | None = None,
                    bwd_block_i: int | None = None,
                    bwd_lanes: str | None = None, nota: bool | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Pipelined PrimaryCaps conv + votes/routing as ONE kernel: x is the
    Conv1 output [B, H, W, Cin], w_pc/b_pc the PrimaryCaps conv params,
    w_cc [I, J*D, C] the routing weights -> v [B, J*D].  The inter-layer
    activation u streams from VMEM scratch, never HBM.

    Schedule (``mode``/``block_i``/``block_k`` and the backward-replay
    conv tiles) comes from ``plan.op("PrimaryCaps-Routing")``
    (``compile_plan(pipeline=True)``) or the memoized plan decision.
    Differentiable: the routing-backward schedule resolves exactly like
    ``votes_routing``'s (the pipelined VJP composes the per-op backward
    kernels, so the plan's backward OpPlans apply unchanged).  ``nota``
    (the none-of-the-above category) comes from the plan op unless given;
    without a plan it is off.
    """
    if routing_op_name is None:
        routing_op_name = execplan.FUSED_NAME
    if nota is None:
        nota = plan is not None and plan.op(execplan.PIPE_NAME).nota
    if stride is None:
        stride = plan.cfg.pc_stride if plan is not None else 2
    if iters is None:
        iters = plan.cfg.routing_iters if plan is not None else 3
    if num_classes is None:
        num_classes = plan.cfg.num_classes if plan is not None else 10
    num_caps, jd, caps_dim = w_cc.shape
    kh, kw, cin, n_ch = w_pc.shape
    oh = (x.shape[1] - kh) // stride + 1
    ow = (x.shape[2] - kw) // stride + 1
    if mode is None or block_i is None or block_k is None:
        if plan is not None:
            if x.shape[0] > plan.batch:
                raise ValueError(
                    f"primary_routing: batch {x.shape[0]} exceeds the "
                    f"plan's batch {plan.batch}; recompile the plan for "
                    f"this batch")
            op = plan.op(execplan.PIPE_NAME)
            mode = mode or op.mode
            block_i = block_i or op.block_i
            block_k = block_k or op.block_k
            cb = (op.block.block_m, op.block.block_k, op.block.block_n)
        else:
            pmode, pbi, pbk, cb = planned_primary_routing(
                oh * ow, kh * kw * cin, n_ch, num_caps, caps_dim, jd,
                num_classes, iters, x.shape[0])
            mode = mode or pmode
            block_i = block_i or pbi
            block_k = block_k or pbk
    else:
        cb = planned_conv_blocks(x.shape[0] * oh * ow, kh * kw * cin, n_ch)
    if bwd_mode is None or bwd_block_i is None:
        budget = plan.vmem_budget if plan is not None else VMEM_BYTES
        bwd_op = None
        if plan is not None and plan.train:
            bwd_op = plan.op(routing_op_name + execplan.BWD_SUFFIX)
        if bwd_op is not None:
            bwd_mode = bwd_mode or bwd_op.mode
            bwd_block_i = bwd_block_i or bwd_op.block_i
            bwd_lanes = bwd_lanes or bwd_op.lanes
        else:
            try:
                pbmode, pbbi, pblanes = planned_votes_routing_bwd(
                    num_caps, caps_dim, jd, num_classes, iters, x.shape[0],
                    budget)
            except execplan.PlanError as err:
                _warn_bwd_fallback_once(
                    f"primary_routing: no feasible routing-backward "
                    f"schedule under the {budget} B VMEM budget ({err}); "
                    f"the forward runs fine, but differentiating this "
                    f"call will reuse the forward schedule "
                    f"(mode={mode!r}, block_i={block_i}) with a backward "
                    f"VMEM footprint the plan never validated")
                pbmode, pbbi, pblanes = mode, block_i, "caps"
            bwd_mode = bwd_mode or pbmode
            bwd_block_i = bwd_block_i or pbbi
            bwd_lanes = bwd_lanes or pblanes
    kernel = _primary_routing_nota if nota else _primary_routing
    out = kernel(
        x, w_pc, b_pc, w_cc, stride=stride, iters=iters,
        num_classes=num_classes, mode=mode, block_i=block_i,
        block_k=block_k, bwd_mode=bwd_mode, bwd_block_i=bwd_block_i,
        bwd_lanes=bwd_lanes or "caps", conv_block_m=cb[0], conv_block_k=cb[1], conv_block_n=cb[2],
        interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_PRIMARY_ROUTING, out)
    return out


def _layer_schedule(lay, batch: int, plan) -> tuple[int, int, str, int,
                                                    str, int, str, str,
                                                    bool]:
    """Resolve one routing layer's (iters, j, mode, block_i, bwd_mode,
    bwd_block_i, lanes, bwd_lanes, nota) kernel statics from the plan's
    per-layer OpPlans (or the memoized plan decision and the layer), with
    the same backward-fallback semantics as ``votes_routing``."""
    if plan is not None:
        op = plan.op(lay.name)
        mode, block_i, lanes, nota = op.mode, op.block_i, op.lanes, op.nota
    else:
        mode, block_i, lanes = planned_votes_routing(
            lay.in_caps, lay.in_dim, lay.jd, lay.num_caps, lay.iters, batch)
        nota = lay.nota
    budget = plan.vmem_budget if plan is not None else VMEM_BYTES
    if plan is not None and plan.train:
        bwd_op = plan.op(lay.name + execplan.BWD_SUFFIX)
        bwd_mode, bwd_block_i, bwd_lanes = (bwd_op.mode, bwd_op.block_i,
                                            bwd_op.lanes)
    else:
        try:
            bwd_mode, bwd_block_i, bwd_lanes = planned_votes_routing_bwd(
                lay.in_caps, lay.in_dim, lay.jd, lay.num_caps, lay.iters,
                batch, budget)
        except execplan.PlanError as err:
            _warn_bwd_fallback_once(
                f"res_caps_segment[{lay.name}]: no feasible backward "
                f"schedule under the {budget} B VMEM budget ({err}); "
                f"differentiating this call will reuse the forward "
                f"schedule (mode={mode!r}, block_i={block_i}) with a "
                f"backward VMEM footprint the plan never validated")
            bwd_mode, bwd_block_i, bwd_lanes = mode, block_i, lanes
    return (lay.iters, lay.num_caps, mode, block_i, bwd_mode, bwd_block_i,
            lanes, bwd_lanes, nota)


def res_caps_segment(x: jax.Array, ws, pairs, *, plan=None,
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
    if plan is not None and x.shape[0] > plan.batch:
        raise ValueError(
            f"res_caps_segment: batch {x.shape[0]} exceeds the plan's "
            f"batch {plan.batch}; recompile the plan for this batch")
    blocks = tuple(
        (lf.num_caps, _layer_schedule(lf, x.shape[0], plan),
         _layer_schedule(lg, x.shape[0], plan)) for lf, lg in pairs)
    out = _res_caps_segment(x, tuple(ws), blocks=blocks,
                            interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_RES_CAPS_SEGMENT, out)
    return out


def squash(x: jax.Array, *, plan=None, block_rows: int | None = None,
           interpret: bool | None = None) -> jax.Array:
    if block_rows is None:
        if plan is not None:
            block_rows = plan.op("PrimaryCaps").block_rows
        else:
            block_rows = 1024
    out = _squash(x, block_rows=block_rows,
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
                    scale=None, block_q=128, block_k=128, interpret=None):
    out = _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                 scale=scale, block_q=block_q, block_k=block_k,
                 interpret=should_interpret(interpret))
    if faults.enabled():                 # chaos-test site; zero cost when off
        out = faults.corrupt_array(faults.SITE_FLASH_ATTENTION, out)
    return out


__all__ = ["conv2d", "caps_votes", "routing", "votes_routing",
           "primary_routing", "res_caps_segment", "squash", "rmsnorm",
           "flash_attention",
           "planned_block_i", "planned_conv_blocks",
           "planned_votes_routing", "planned_votes_routing_bwd",
           "planned_primary_routing", "ref", "should_interpret"]
