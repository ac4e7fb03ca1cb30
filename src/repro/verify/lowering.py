"""Plan auditor: derive VMEM/HBM truth from the Pallas lowerings.

``core.execplan`` hand-models every ``OpPlan``'s VMEM footprint
(``vmem_bytes``), HBM traffic (``hbm_bytes``), W-stream pass count
(``n_passes``) and the zero-intermediate claims (``uhat_hbm_bytes=0``,
``intermediate_hbm_bytes=0``).  The DSE, the PMU gating schedule, and
``degrade_plan`` all optimize against those numbers, so a kernel edit
that silently drifts them corrupts every downstream decision.

This module closes the loop **statically**: each op's kernel entry
point is traced with ``jax.make_jaxpr`` over ``ShapeDtypeStruct``
operands (abstract eval -- nothing executes), the ``pallas_call``
equations are pulled out of the jaxpr, and the *derived* numbers are
computed from what the lowering actually says:

* **VMEM**: per ``pallas_call``, sum of operand block tiles
  (double-buffered when the operand's block index varies over the grid,
  single-buffered when it is constant -- the Pallas pipeline only
  prefetches blocks that change) plus output tiles (accumulator
  semantics: one buffer) plus every scratch allocation, each padded to
  the (8, 128) tiling of its two minor dims as Mosaic allocates it.  An
  op lowering to several sequential calls takes the max.
* **HBM traffic**: per operand, ``fetches x block_bytes`` where
  ``fetches`` counts block-index *transitions* over the grid iteration
  order (last grid axis fastest) -- so a streamed W re-fetched every
  pass derives ``n_passes`` from the index map instead of trusting the
  model's assertion.
* **Pass counts**: ``fetches / distinct_blocks`` of the W operand of
  the fused/pipelined kernels, compared exactly against
  ``OpPlan.n_passes``.
* **Zero-intermediate claims**: no equation *outside* a Pallas kernel
  body produces an array of the forbidden u_hat / inter-layer-u shape
  -- i.e. the tensor provably never exists at the HBM level.

Tolerances come from ``execplan.audit_contract`` (per-kernel: the model
counts in-register temporaries the lowering doesn't allocate, and the
lowering pays padding the model rounds away), so the comparison is
tight but honest.  See ``python -m repro.verify``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from repro.core import analysis, execplan
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import (BWD_SUFFIX, PIPE_NAME, ExecutionPlan,
                                 OpPlan)
from repro.core.planner import tile_padded

_SDS = jax.ShapeDtypeStruct


class AuditError(RuntimeError):
    """An audited lowering could not be traced or matched to its plan op."""


# ---------------------------------------------------------------------------
# Jaxpr extraction
# ---------------------------------------------------------------------------

def _walk(jaxpr, calls: list, outer: list) -> None:
    """Collect ``pallas_call`` eqns and every NON-kernel-body eqn."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
            continue                      # never descend into kernel bodies
        outer.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    _walk(sub.jaxpr, calls, outer)
                elif isinstance(sub, jcore.Jaxpr):
                    _walk(sub, calls, outer)


def trace_lowering(fn, *avals):
    """Abstract-trace ``fn`` and return ``(pallas_eqns, outer_eqns)``.

    ``outer_eqns`` is every equation at any nesting level EXCEPT inside
    Pallas kernel bodies -- the HBM-level program the zero-intermediate
    checks scan.
    """
    closed = jax.make_jaxpr(fn)(*avals)
    calls: list = []
    outer: list = []
    _walk(closed.jaxpr, calls, outer)
    if not calls:
        raise AuditError("lowering contains no pallas_call")
    return calls, outer


def _index_walk(block_mapping, grid: tuple[int, ...]) -> tuple[int, int]:
    """(fetches, distinct_blocks) of one operand over the grid.

    Evaluates the BlockSpec index-map jaxpr at every grid point in
    iteration order (row-major, last axis fastest) and counts index
    transitions: the Pallas pipeline refetches a block exactly when its
    index differs from the previous step's.
    """
    if not grid:
        return 1, 1
    steps = np.stack(
        np.meshgrid(*[np.arange(g) for g in grid], indexing="ij"),
        axis=-1).reshape(-1, len(grid))
    cj = block_mapping.index_map_jaxpr

    def f(*idx):
        return jax.core.eval_jaxpr(cj.jaxpr, cj.consts, *idx)

    outs = jax.vmap(f)(*(jnp.asarray(steps[:, k], jnp.int32)
                         for k in range(steps.shape[1])))
    arr = np.stack([np.asarray(o) for o in outs], axis=1)
    changed = (arr[1:] != arr[:-1]).any(axis=1)
    fetches = int(1 + changed.sum())
    distinct = int(len(np.unique(arr, axis=0)))
    return fetches, distinct


def _block_dims(block_mapping) -> tuple[int, ...]:
    """Block shape as ints (``pl.Blocked`` dims carry ``block_size``;
    squeezed dims count 1)."""
    return tuple(1 if d is None else int(getattr(d, "block_size", d))
                 for d in block_mapping.block_shape)


def _block_bytes(block_mapping) -> int:
    dtype = np.dtype(block_mapping.array_aval.dtype)
    return math.prod(_block_dims(block_mapping)) * dtype.itemsize


def _vmem_block_bytes(block_mapping) -> int:
    dtype = np.dtype(block_mapping.array_aval.dtype)
    return tile_padded(_block_dims(block_mapping)) * dtype.itemsize


@dataclasses.dataclass(frozen=True)
class OperandTrace:
    """One pallas_call operand as the lowering declares it."""

    role: str                 # "in" | "out"
    block_shape: tuple[int, ...]
    array_shape: tuple[int, ...]
    dtype: str
    fetches: int              # block-index transitions over the grid
    distinct: int             # distinct block indices touched
    block_bytes: int
    buffers: int              # 2 = double-buffered stream, 1 = resident
    traffic_bytes: int        # fetches * block_bytes


@dataclasses.dataclass(frozen=True)
class CallTrace:
    """One lowered ``pallas_call``: derived footprint and traffic."""

    kernel: str
    grid: tuple[int, ...]
    operands: tuple[OperandTrace, ...]
    scratch_shapes: tuple[tuple[tuple[int, ...], str], ...]
    scratch_bytes: int
    vmem_bytes: int           # derived peak on-chip bytes
    hbm_bytes: int            # derived traffic


def trace_pallas_eqn(eqn) -> CallTrace:
    """Derive one ``pallas_call``'s footprint/traffic from its params."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    bms = gm.block_mappings
    n_in = gm.num_inputs
    operands = []
    vmem = 0
    hbm = 0
    for i, bm in enumerate(bms):
        role = "in" if i < n_in else "out"
        fetches, distinct = _index_walk(bm, grid)
        bb = _block_bytes(bm)
        # Varying input blocks double-buffer (prefetch overlaps compute);
        # constant-index operands are fetched once and stay resident.
        # Outputs live in ONE accumulator buffer (revisited K-steps must
        # accumulate in place).
        buffers = 2 if (role == "in" and distinct > 1) else 1
        vmem += buffers * _vmem_block_bytes(bm)
        hbm += fetches * bb
        operands.append(OperandTrace(
            role=role,
            block_shape=_block_dims(bm),
            array_shape=tuple(bm.array_aval.shape),
            dtype=str(np.dtype(bm.array_aval.dtype)),
            fetches=fetches, distinct=distinct, block_bytes=bb,
            buffers=buffers, traffic_bytes=fetches * bb))
    scratch = []
    scratch_bytes = 0
    for var in eqn.params["jaxpr"].invars[len(bms):]:
        aval = getattr(var.aval, "inner_aval", var.aval)
        nbytes = tile_padded(aval.shape) * np.dtype(aval.dtype).itemsize
        scratch_bytes += nbytes
        scratch.append((tuple(aval.shape), str(np.dtype(aval.dtype))))
    name = getattr(eqn.params["jaxpr"].debug_info, "func_name",
                   None) or "pallas_call"
    return CallTrace(kernel=str(name), grid=grid, operands=tuple(operands),
                     scratch_shapes=tuple(scratch),
                     scratch_bytes=scratch_bytes,
                     vmem_bytes=vmem + scratch_bytes, hbm_bytes=hbm)


# ---------------------------------------------------------------------------
# Per-op entry points: rebuild exactly the call the network makes
# ---------------------------------------------------------------------------

def _conv_shapes(cfg: CapsNetConfig, dims, batch: int, name: str):
    if name == "Conv1":
        x = _SDS((batch, dims.in_hw, dims.in_hw, dims.conv1_cin),
                 jnp.float32)
        w = _SDS((cfg.conv1_kernel, cfg.conv1_kernel, dims.conv1_cin,
                  dims.conv1_cout), jnp.float32)
        b = _SDS((dims.conv1_cout,), jnp.float32)
        return x, w, b, 1, "relu"
    x = _SDS((batch, dims.conv1_out, dims.conv1_out, dims.pc_cin),
             jnp.float32)
    w = _SDS((cfg.pc_kernel, cfg.pc_kernel, dims.pc_cin, dims.pc_cout),
             jnp.float32)
    b = _SDS((dims.pc_cout,), jnp.float32)
    return x, w, b, cfg.pc_stride, "none"


def _layer_for(plan: ExecutionPlan, op_name: str):
    base = op_name[:-len(BWD_SUFFIX)] if op_name.endswith(BWD_SUFFIX) \
        else op_name
    for lay in plan.cfg.routing_stack():
        if lay.name == base:
            return lay
    raise AuditError(f"{op_name}: no routing layer matches this op")


def _trace_conv_fwd(plan: ExecutionPlan, op: OpPlan):
    from repro.kernels import squash as squash_mod
    from repro.kernels.conv_im2col import conv2d_im2col
    dims = analysis.dims_from_config(plan.cfg)
    x, w, b, stride, epilogue = _conv_shapes(plan.cfg, dims, plan.batch,
                                             op.name)
    squash_dim = 0
    if op.name == "PrimaryCaps" and op.fuses_squash:
        epilogue, squash_dim = "squash", dims.primary_dim

    def fn(xv, wv, bv):
        return conv2d_im2col(xv, wv, bv, stride=stride,
                             block_m=op.block.block_m,
                             block_k=op.block.block_k,
                             block_n=op.block.block_n,
                             epilogue=epilogue, squash_dim=squash_dim,
                             interpret=True)

    calls, outer = trace_lowering(fn, x, w, b)
    if op.name == "PrimaryCaps" and not op.fuses_squash:
        # The standalone blocked squash pass rides on this op's plan
        # entry (vmem max'd in); audit its lowering alongside.
        rows = plan.batch * dims.num_primary
        x2 = _SDS((rows, dims.primary_dim), jnp.float32)
        sq_calls, sq_outer = trace_lowering(
            lambda v: squash_mod._squash_core(op.block_rows, True, v), x2)
        calls, outer = calls + sq_calls, outer + sq_outer
    return calls, outer


def _trace_fused_fwd(plan: ExecutionPlan, op: OpPlan):
    from repro.kernels import votes_routing as vr
    lay = _layer_for(plan, op.name)
    st = vr._VRStatics(iters=lay.iters, num_classes=lay.num_caps,
                       mode=op.mode, block_i=op.block_i,
                       bwd_mode=op.mode, bwd_block_i=op.block_i,
                       interpret=True, lanes=op.lanes, bwd_lanes=op.lanes,
                       nota=op.nota)
    u = _SDS((plan.batch, lay.in_caps, lay.in_dim), jnp.float32)
    w = _SDS((lay.in_caps, lay.jd, lay.in_dim), jnp.float32)
    if lay.residual:
        r = _SDS((plan.batch, lay.jd), jnp.float32)
        return trace_lowering(lambda uv, wv, rv: vr._vr_apply(st, uv, wv, rv),
                              u, w, r)
    return trace_lowering(lambda uv, wv: vr._vr_apply(st, uv, wv), u, w)


def _trace_fused_bwd(plan: ExecutionPlan, op: OpPlan):
    from repro.kernels import votes_routing as vr
    lay = _layer_for(plan, op.name)
    st = vr._VRStatics(iters=lay.iters, num_classes=lay.num_caps,
                       mode=op.mode, block_i=op.block_i,
                       bwd_mode=op.mode, bwd_block_i=op.block_i,
                       interpret=True, lanes=op.lanes, bwd_lanes=op.lanes,
                       nota=op.nota)
    u = _SDS((plan.batch, lay.in_caps, lay.in_dim), jnp.float32)
    w = _SDS((lay.in_caps, lay.jd, lay.in_dim), jnp.float32)
    g = _SDS((plan.batch, lay.jd), jnp.float32)
    calls, outer = trace_lowering(
        lambda uv, wv, gv: vr._vr_grad(st, uv, wv, gv), u, w, g)
    if lay.residual:
        # Reversible inversion replays this coupling half FORWARD (no
        # residual epilogue) with the forward op's schedule before the
        # VJP proper; the plan's backward entry models max(vmem) /
        # summed traffic over both.
        fwd_op = plan.op(lay.name)
        fst = vr._VRStatics(iters=lay.iters, num_classes=lay.num_caps,
                            mode=fwd_op.mode, block_i=fwd_op.block_i,
                            bwd_mode=fwd_op.mode, bwd_block_i=fwd_op.block_i,
                            interpret=True, lanes=fwd_op.lanes,
                            bwd_lanes=fwd_op.lanes, nota=fwd_op.nota)
        fcalls, fouter = trace_lowering(
            lambda uv, wv: vr._vr_apply(fst, uv, wv), u, w)
        calls, outer = calls + fcalls, outer + fouter
    return calls, outer


def _trace_pipe_fwd(plan: ExecutionPlan, op: OpPlan):
    from repro.kernels import primary_routing as pr
    dims = analysis.dims_from_config(plan.cfg)
    lay = plan.cfg.routing_stack()[0]
    st = pr._PRStatics(stride=plan.cfg.pc_stride, iters=lay.iters,
                       num_classes=lay.num_caps, mode=op.mode,
                       block_i=op.block_i, block_k=op.block_k,
                       bwd_mode=op.mode, bwd_block_i=op.block_i,
                       conv_block_m=op.block.block_m,
                       conv_block_k=op.block.block_k,
                       conv_block_n=op.block.block_n, interpret=True,
                       nota=op.nota)
    x = _SDS((plan.batch, dims.conv1_out, dims.conv1_out, dims.pc_cin),
             jnp.float32)
    w_pc = _SDS((plan.cfg.pc_kernel, plan.cfg.pc_kernel, dims.pc_cin,
                 dims.pc_cout), jnp.float32)
    b_pc = _SDS((dims.pc_cout,), jnp.float32)
    w_cc = _SDS((lay.in_caps, lay.jd, lay.in_dim), jnp.float32)
    return trace_lowering(
        lambda xv, wp, bp, wc: pr._pr_apply(st, xv, wp, bp, wc),
        x, w_pc, b_pc, w_cc)


def _trace_conv_bwd(plan: ExecutionPlan, op: OpPlan):
    from repro.kernels import conv_im2col as conv
    dims = analysis.dims_from_config(plan.cfg)
    base = op.name[:-len(BWD_SUFFIX)]
    x, w, b, stride, epilogue = _conv_shapes(plan.cfg, dims, plan.batch,
                                             base)
    squash_dim = 0
    pipelined_pc = base == "PrimaryCaps" and any(
        o.name == PIPE_NAME for o in plan.ops)
    if base == "PrimaryCaps" and (op.fuses_squash or pipelined_pc):
        # The backward recomputes the pre-activation from patches (the
        # third matmul the plan's `matmuls=3` accounts for).
        epilogue, squash_dim = "squash", dims.primary_dim
    st = conv._ConvStatics(stride=stride, block_m=op.block.block_m,
                           block_k=op.block.block_k,
                           block_n=op.block.block_n, epilogue=epilogue,
                           squash_dim=squash_dim, interpret=True)
    kh, kw = w.shape[0], w.shape[1]
    oh = (x.shape[1] - kh) // stride + 1
    ow = (x.shape[2] - kw) // stride + 1
    dy = _SDS((plan.batch, oh, ow, w.shape[3]), jnp.float32)
    if epilogue == "relu":
        return trace_lowering(
            lambda xv, wv, bv, ov, gv: conv._conv_core_bwd(
                st, (xv, wv, bv, ov), gv), x, w, b, dy, dy)
    return trace_lowering(
        lambda xv, wv, bv, gv: conv._conv_core_bwd(
            st, (xv, wv, bv, None), gv), x, w, b, dy)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    ok: bool
    detail: str


@dataclasses.dataclass(frozen=True)
class OpAudit:
    op: str
    kernel: str
    calls: tuple[CallTrace, ...]
    checks: tuple[Check, ...]

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.checks)

    def failures(self) -> list[Check]:
        return [c for c in self.checks if not c.ok]


@dataclasses.dataclass(frozen=True)
class PlanAudit:
    label: str
    ops: tuple[OpAudit, ...]

    @property
    def ok(self) -> bool:
        return all(o.ok for o in self.ops)

    def failures(self) -> list[tuple[str, Check]]:
        return [(o.op, c) for o in self.ops for c in o.failures()]


# Fused/pipelined kernel bodies and the grid position of their streamed
# W operand (the one whose derived fetch count IS the pass count).
_W_OPERAND = {"_routing_kernel": 1, "_routing_bwd_kernel": 1,
              "_pipe_kernel": 3}


def _main_call(calls: tuple[CallTrace, ...], op: OpPlan) -> CallTrace | None:
    """The fused/pipelined megakernel call carrying the W stream."""
    want_bwd = op.name.endswith(BWD_SUFFIX)
    for c in calls:
        base = c.kernel.split(" ")[0]
        if base in _W_OPERAND and ("bwd" in base) == want_bwd:
            return c
    return None


def _derived_passes(call: CallTrace) -> float:
    w = call.operands[_W_OPERAND[call.kernel.split(" ")[0]]]
    return w.fetches / max(w.distinct, 1)


def _shape_check(outer, forbidden: set, allowed: set, claim: str) -> Check:
    hits = sorted({tuple(v.aval.shape) for eqn in outer for v in eqn.outvars
                   if hasattr(v.aval, "shape")
                   and tuple(v.aval.shape) in forbidden
                   and tuple(v.aval.shape) not in allowed})
    return Check(
        name=claim, ok=not hits,
        detail=("no HBM-level array of a forbidden shape" if not hits else
                f"HBM-level intermediate(s) of forbidden shape {hits} "
                f"contradict the zero-intermediate claim"))


def _i_pad(i_dim: int, block_i: int) -> int:
    return math.ceil(i_dim / max(block_i, 1)) * max(block_i, 1)


def _xla_extraction_bytes(plan: ExecutionPlan, op: OpPlan, outer) -> int:
    """HBM bytes of a forward patch extraction that XLA runs outside any
    kernel (channel axes narrower than the lane tiling): the image read
    and the patch matrix written, counted when the traced program
    materializes that matrix."""
    dims = analysis.dims_from_config(plan.cfg)
    if op.name == "Conv1":
        hw, cin, k, out = (dims.in_hw, dims.conv1_cin, dims.conv1_k,
                           dims.conv1_out)
    elif op.name in ("PrimaryCaps", PIPE_NAME):
        hw, cin, k, out = (dims.conv1_out, dims.pc_cin, dims.pc_k,
                           dims.pc_out)
    else:
        return 0
    patch = (plan.batch, out * out, k * k * cin)
    if not any(tuple(getattr(v.aval, "shape", ())) == patch
               for eqn in outer for v in eqn.outvars):
        return 0
    return plan.batch * (hw * hw * cin + out * out * k * k * cin) * 4


def _relayout_bytes(outer) -> int:
    """HBM bytes of the transposes the wrappers run outside any kernel
    (the routing kernels' lane layouts, transposed weights): each reads
    its operand and writes its result once."""
    def nbytes(v):
        return math.prod(v.aval.shape) * np.dtype(v.aval.dtype).itemsize
    return sum(nbytes(eqn.invars[0]) + nbytes(eqn.outvars[0])
               for eqn in outer if eqn.primitive.name == "transpose")


def audit_op(plan: ExecutionPlan, op: OpPlan) -> OpAudit:
    """Trace one op's lowering and diff it against its plan entry."""
    tracers = {
        "conv_im2col": _trace_conv_fwd,
        "conv_im2col+squash": _trace_conv_fwd,
        "votes_routing": _trace_fused_fwd,
        "votes_routing_bwd": _trace_fused_bwd,
        "primary_routing": _trace_pipe_fwd,
        "conv_im2col_bwd": _trace_conv_bwd,
    }
    if op.kernel not in tracers:
        raise AuditError(f"{op.name}: no audit tracer for kernel "
                         f"{op.kernel!r} -- teach verify.lowering about it")
    eqns, outer = tracers[op.kernel](plan, op)
    calls = tuple(trace_pallas_eqn(e) for e in eqns)
    contract = execplan.audit_contract(op)
    checks: list[Check] = []

    derived_vmem = max(c.vmem_bytes for c in calls)
    limit = op.vmem_bytes * (1 + contract.vmem_rtol)
    checks.append(Check(
        name="vmem-under-model", ok=derived_vmem <= limit,
        detail=(f"derived {derived_vmem} B vs modeled {op.vmem_bytes} B "
                f"(+{contract.vmem_rtol:.0%} tolerance)")))
    checks.append(Check(
        name="vmem-over-model",
        ok=op.vmem_bytes <= derived_vmem * contract.vmem_over_factor,
        detail=(f"modeled {op.vmem_bytes} B vs derived {derived_vmem} B "
                f"(x{contract.vmem_over_factor} slack)")))

    if op.hbm_bytes is not None:
        derived_hbm = (sum(c.hbm_bytes for c in calls)
                       + _xla_extraction_bytes(plan, op, outer)
                       + _relayout_bytes(outer))
        rel = abs(derived_hbm - op.hbm_bytes) / max(op.hbm_bytes, 1.0)
        checks.append(Check(
            name="hbm-traffic", ok=rel <= contract.hbm_rtol,
            detail=(f"derived {derived_hbm} B vs modeled "
                    f"{op.hbm_bytes:.0f} B ({rel:.1%} off, tolerance "
                    f"{contract.hbm_rtol:.0%})")))

    if op.n_passes is not None:
        main = _main_call(calls, op)
        if main is None:
            checks.append(Check(
                name="w-pass-count", ok=False,
                detail=f"no fused kernel call found among "
                       f"{[c.kernel for c in calls]}"))
        else:
            got = _derived_passes(main)
            # One block covering the whole i-axis never changes its block
            # index, so W crosses HBM once however many passes the grid
            # makes (the traffic models count the same way).
            w_op = main.operands[_W_OPERAND[main.kernel]]
            want = 1 if w_op.distinct <= 1 else op.n_passes
            checks.append(Check(
                name="w-pass-count", ok=got == want,
                detail=(f"W operand fetched {got:g} passes, plan models "
                        f"{want} ({op.mode}"
                        f"{', single i-block' if want != op.n_passes else ''})"
                        )))

    batch = plan.batch

    def _uhat_shapes(lay, pad):
        # [B, I, J*D] as the model sees it, [B, J, D, I] / [B, D, I, J]
        # as the kernel layouts hold it, unpadded and padded.
        return {shape for i in (lay.in_caps, pad)
                for shape in ((batch, i, lay.jd),
                              (batch, lay.num_caps, lay.caps_dim, i),
                              (batch, lay.caps_dim, i, lay.num_caps))}

    if op.uhat_hbm_bytes == 0.0 and op.kernel != "primary_routing":
        lay = _layer_for(plan, op.name)
        pad = _i_pad(lay.in_caps, op.block_i or lay.in_caps)
        allowed = {(batch, lay.in_caps, lay.in_dim),
                   (batch, pad, lay.in_dim)}
        checks.append(_shape_check(outer, _uhat_shapes(lay, pad), allowed,
                                   "uhat-never-in-hbm"))
    if op.kernel == "primary_routing":
        lay = plan.cfg.routing_stack()[0]
        pad = _i_pad(lay.in_caps, op.block_i or lay.in_caps)
        checks.append(_shape_check(outer, _uhat_shapes(lay, pad), set(),
                                   "uhat-never-in-hbm"))
        if op.intermediate_hbm_bytes == 0.0:
            forb_u = {shape for i in (lay.in_caps, pad)
                      for shape in ((batch, i, lay.in_dim),
                                    (batch, lay.in_dim, i))}
            checks.append(_shape_check(outer, forb_u, set(),
                                       "u-never-in-hbm"))

    return OpAudit(op=op.name, kernel=op.kernel, calls=calls,
                   checks=tuple(checks))


def audit_plan(plan: ExecutionPlan, label: str = "") -> PlanAudit:
    """Audit every op of a compiled plan (no execution)."""
    return PlanAudit(label=label or f"batch={plan.batch} "
                                    f"train={plan.train}",
                     ops=tuple(audit_op(plan, op) for op in plan.ops))


def audit_config(cfg: CapsNetConfig, *, batch: int = 1,
                 vmem_budget: int | None = None, train: bool = False,
                 pipeline: bool = False, label: str = "") -> PlanAudit:
    """Compile ``cfg`` and audit the resulting plan."""
    kw = dict(batch=batch, train=train, pipeline=pipeline)
    if vmem_budget is not None:
        kw["vmem_budget"] = vmem_budget
    plan = execplan.compile_plan(cfg, **kw)
    return audit_plan(plan, label=label)
