"""ExecutionPlan: ONE compiled schedule shared by kernels, PMU, and serving.

CapStore's core contribution is a single per-operation schedule that sizes
each on-chip memory and drives power-gating from it (paper Secs. 4.1-4.3).
Before this module the repo had three parallel models of that schedule:
``kernels/ops.py`` re-ran the block-shape DSE per call, ``core/dse.py``
derived PMU phases from the analysis profiles, and ``core/capsnet.py``
ignored both.  ``compile_plan`` unifies them: it compiles a
``CapsNetConfig`` into per-operation

  * Pallas block shapes (``planner.plan_matmul`` energy-argmin DSE),
  * VMEM footprints (checked against the budget -- the TPU analogue of
    the paper's sized-to-fit SRAMs),
  * estimated cycles, and
  * auto-derived ``PhaseRequirement``s (analysis.py dataflow model)

so the schedule the kernels *execute* is the same schedule the PMU/energy
model *scores* (``pmu.schedule_from_plan``, ``dse.explore(plan=...)``) and
the serving engine *amortizes* (``serve/capsule.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

from repro.core import analysis
from repro.core.analysis import OperationProfile
from repro.core.capsnet import CapsNetConfig
from repro.core.planner import (LANES, MXU, SUBLANES, VMEM_BYTES,
                                BlockPlan, MatmulWorkload,
                                plan_matmul, tile_padded)
from repro.core.pmu import PhaseRequirement

# Kernels run in fp32 (interpret-mode validated; fp32 accumulation on TPU).
ELEM_BYTES = 4
SQUASH_BLOCK_ROWS = 1024

# The fused ClassCaps megakernel: ONE plan op / PMU phase covering the
# dataflow model's ClassCaps-FC + Sum+Squash + Update+Sum operations.
FUSED_NAME = "ClassCaps-Routing"
# Routing kernel layouts in plan preference order: input capsules on the
# lanes first (dense for narrow heads), output capsules on the lanes for
# layers whose W tile or class vectors do not fit that way.
LAYOUTS = ("caps", "classes")
FUSED_COVERS = ("ClassCaps-FC", "Sum+Squash", "Update+Sum")

# The pipelined producer->consumer pair: PrimaryCaps' squash-epilogue
# output feeds the votes/routing megakernel straight from VMEM scratch,
# so the inter-layer activation u never round-trips HBM (the paper's
# inter-layer on-chip residency -- DESCNet's scratchpad, CapsAcc's
# cross-layer reuse).  ONE plan op / PMU phase covering four dataflow
# operations.
PIPE_NAME = "PrimaryCaps-Routing"
PIPE_COVERS = ("PrimaryCaps",) + FUSED_COVERS

# Training plans append one backward OpPlan per executed kernel, named
# "<op>-bwd" and listed in reverse network order (the order the backward
# actually runs), so dse/pmu gate the backward phases like the forward's.
BWD_SUFFIX = "-bwd"


class PlanError(ValueError):
    """An ExecutionPlan violates one of its invariants."""


@dataclasses.dataclass(frozen=True)
class OpPlan:
    """The compiled schedule entry for one CapsuleNet operation.

    ``kernel`` names the executor -- all Pallas: ``conv_im2col``
    (optionally ``+squash`` when the primary-capsule activation fuses into
    the epilogue) and the fused ``votes_routing`` megakernel.  Matmul-view
    operations carry the planner's energy-argmin ``block``; its
    ``block_m/k/n`` (conv) and ``block_i`` / ``block_rows`` are the
    concrete grid tiles the kernel wrappers consume.  ``requirement`` is
    the PMU phase (ASIC dataflow-model bytes/cycles) the gating schedule
    is built from; a fused op covers several dataflow-model operations
    (``profiles``) with ONE phase -- the schedule it actually executes.

    ``mode`` is the fused kernel's plan-chosen schedule (``resident`` /
    ``streamed``); ``hbm_bytes`` is the op's modeled HBM traffic per
    forward at the plan batch and ``uhat_hbm_bytes`` the share of it spent
    on the votes intermediate (0 for the fused kernel -- the point).
    ``intermediate_hbm_bytes`` is the traffic this op's OUTPUT pays to
    reach its consumer: the write+read round-trip on a per-op plan, 0 on
    a pipelined pair (the consumer reads the producer's VMEM scratch --
    the inter-layer analogue of ``uhat_hbm_bytes``).
    """

    name: str
    kernel: str
    workload: MatmulWorkload | None
    block: BlockPlan | None
    vmem_bytes: int
    est_cycles: float
    requirement: PhaseRequirement
    profiles: tuple[OperationProfile, ...]
    block_i: int | None = None
    block_rows: int | None = None
    mode: str | None = None
    hbm_bytes: float | None = None
    uhat_hbm_bytes: float | None = None
    intermediate_hbm_bytes: float | None = None
    block_k: int | None = None   # pipelined produce-phase K tile
    # Routing kernel layout: which capsule axis lies on the 128 lanes --
    # "caps" (input capsules I) or "classes" (output capsules J); see
    # ``kernels.votes_routing``.  None for ops without a routing kernel.
    lanes: str | None = None
    # Modeled W-stream pass count of the fused/pipelined kernels (1
    # resident / iters+1 streamed forward, 2 / iters+4 backward; None
    # for ops without a W stream).  A first-class plan claim so the
    # static auditor (``repro.verify.lowering``) can diff it against
    # the pass count DERIVED from the lowering's index maps.
    n_passes: int | None = None
    # The routing kernels' none-of-the-above category
    # (``CapsNetConfig.none_of_the_above``), carried to the wrappers.  It
    # adds no scratch and no bytes: every footprint and traffic model is
    # the same with it on or off.
    nota: bool = False

    @property
    def profile(self) -> OperationProfile:
        """The primary dataflow profile (first of ``profiles``)."""
        return self.profiles[0]

    @property
    def fuses_squash(self) -> bool:
        """Whether this op's epilogue absorbs the squash activation."""
        return self.kernel.endswith("+squash")


@dataclasses.dataclass(frozen=True)
class AuditContract:
    """Tolerances the static auditor (``repro.verify.lowering``) holds an
    op's DERIVED footprint/traffic to.

    ``vmem_rtol`` bounds how far the derived peak VMEM may exceed the
    modeled ``vmem_bytes`` (the hard direction: an under-modeling plan
    would let ``validate()`` pass a schedule that busts real VMEM).
    ``vmem_over_factor`` bounds the other direction -- the model may
    legitimately count in-register temporaries (the ``uh_block`` votes
    tile, s/v candidates) that the lowering never allocates as scratch,
    but a model more than this factor above the lowering is stale.
    ``hbm_rtol`` is symmetric: derived traffic pays i/K zero-padding and
    side kernels (patch extraction, bias slabs) the byte model rounds
    away, so it is per-kernel calibrated rather than zero.
    """

    vmem_rtol: float
    vmem_over_factor: float
    hbm_rtol: float


# Per-kernel calibrated contracts.  The conv entries absorb the patch-
# extraction call (reads the image, writes the patches tensor) that
# ``BlockPlan.hbm_bytes`` -- a pure matmul model -- does not count; the
# fused entries absorb i-axis zero-padding of u/W at ragged block_i.
_AUDIT_CONTRACTS = {
    # Calibrated against the worst derived-vs-modeled margin over every
    # registered CapsNet arch x {per-op, pipelined} x {fwd, train} (see
    # tests/test_verify_lowering.py): the fused/pipelined models are
    # near-exact; the conv margins absorb the im2col patch-extraction
    # call and the coarse matmul-count backward estimate.
    "conv_im2col": AuditContract(0.15, 1.75, 0.20),
    "conv_im2col+squash": AuditContract(0.10, 1.50, 0.30),
    "conv_im2col_bwd": AuditContract(0.10, 1.50, 0.50),
    "votes_routing": AuditContract(0.05, 1.40, 0.05),
    "votes_routing_bwd": AuditContract(0.05, 1.60, 0.05),
    "primary_routing": AuditContract(0.25, 1.25, 0.15),
}


def audit_contract(op: OpPlan) -> AuditContract:
    """The audit tolerance contract for one plan op (keyed by kernel)."""
    try:
        return _AUDIT_CONTRACTS[op.kernel]
    except KeyError:
        raise PlanError(f"{op.name}: no audit contract for kernel "
                        f"{op.kernel!r}") from None


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    cfg: CapsNetConfig
    batch: int
    dataflow: str
    vmem_budget: int
    ops: tuple[OpPlan, ...]
    train: bool = False          # backward OpPlans appended (reverse order)

    def op(self, name: str) -> OpPlan:
        for op in self.ops:
            if op.name == name:
                return op
        raise KeyError(f"no operation {name!r} in plan "
                       f"({[o.name for o in self.ops]})")

    @property
    def profiles(self) -> tuple[OperationProfile, ...]:
        """The dataflow profiles this plan was compiled from (feeds dse).

        Fused ops contribute every profile they cover, so this is always
        the full five-operation paper model regardless of fusion.
        """
        return tuple(p for op in self.ops for p in op.profiles)

    def phase_requirements(self) -> tuple[PhaseRequirement, ...]:
        """Per-operation PMU phases, in execution order.

        One phase per EXECUTED op: the fused ClassCaps megakernel is a
        single phase, so the gating schedule scores what actually runs.
        """
        return tuple(op.requirement for op in self.ops)

    def phase_groups(self) -> tuple[tuple[str, tuple[str, ...]], ...]:
        """(phase_name, covered profile names) per executed op -- lets the
        organization DSE (``dse.evaluate_plan``) gate over the fused
        phases the kernels execute instead of the raw five-op model."""
        return tuple((op.name, tuple(p.name for p in op.profiles))
                     for op in self.ops)

    def phase_durations(self) -> dict[str, float]:
        """Per-phase cycle estimate keyed by executed-op name.  Pass-count
        aware: a STREAMED fused phase re-streams W (``iters + 1`` forward
        / ``iters + 4`` backward passes recomputing the votes), so its
        leakage window is longer than the one-pass profile sum a
        ``phase_groups()`` consumer would otherwise derive."""
        return {op.name: op.requirement.duration_cycles for op in self.ops}

    @property
    def peak_vmem_bytes(self) -> int:
        return max(op.vmem_bytes for op in self.ops)

    def forward_hbm_bytes(self) -> float:
        """Total modeled HBM traffic of one forward pass (forward ops'
        ``hbm_bytes`` summed) -- the whole-network number the paper
        optimizes.  Each op's ``intermediate_hbm_bytes`` is the share of
        this total spent round-tripping that op's output to its consumer
        (already inside the per-op ``hbm_bytes``: the producer's store and
        the consumer's load), so a pipelined plan beats the per-op plan
        here by at least the eliminated intermediate."""
        return sum(op.hbm_bytes or 0.0 for op in self.ops
                   if not op.name.endswith(BWD_SUFFIX))

    def activation_residency_bytes(self, *, reversible: bool = True) -> int:
        """Routing-stack activation bytes a training step keeps live (see
        the module-level ``activation_residency_bytes``) at this plan's
        batch."""
        return activation_residency_bytes(self.cfg, batch=self.batch,
                                          reversible=reversible)

    def validate(self) -> None:
        """Check the plan invariants; raises ``PlanError`` on violation."""
        if self.batch < 1:
            raise PlanError(f"batch must be >= 1, got {self.batch}")
        names = [op.name for op in self.ops]
        if len(set(names)) != len(names):
            raise PlanError(f"duplicate operation names: {names}")
        stack = self.cfg.routing_stack()
        covered = [p.name for op in self.ops for p in op.profiles]
        expected = [p.name for p in
                    analysis.capsnet_stack_profiles(
                        self.dataflow, analysis.dims_from_config(self.cfg),
                        _layer_descs(stack))]
        if self.train:
            # Backward phases mirror the forward coverage in reverse
            # execution order (the order the backward actually runs).
            expected = expected + [n + BWD_SUFFIX for n in reversed(expected)]
        if covered != expected:
            raise PlanError(
                f"phases {names} cover {covered}, not operations {expected}")
        for op in self.ops:
            if op.mode is not None and op.mode not in ("resident", "streamed"):
                raise PlanError(f"{op.name}: unknown mode {op.mode!r}")
            if op.lanes is not None and op.lanes not in LAYOUTS:
                raise PlanError(f"{op.name}: unknown lanes {op.lanes!r}")
            if op.vmem_bytes > self.vmem_budget:
                raise PlanError(
                    f"{op.name}: VMEM footprint {op.vmem_bytes} exceeds "
                    f"budget {self.vmem_budget}")
            if op.requirement.name != op.name:
                raise PlanError(f"{op.name}: phase named {op.requirement.name!r}")
            if op.requirement.duration_cycles <= 0:
                raise PlanError(f"{op.name}: non-positive phase duration")
            if op.block is not None and op.block.vmem_total > self.vmem_budget:
                raise PlanError(f"{op.name}: block tiles exceed VMEM budget")
            if op.block_i is not None and not (
                    1 <= op.block_i <= max(max(s.in_caps for s in stack),
                                           1)):
                raise PlanError(f"{op.name}: block_i {op.block_i} out of range")

    def summary(self) -> list[dict]:
        rows = []
        for op in self.ops:
            rows.append(dict(
                name=op.name,
                kernel=op.kernel,
                block=((op.block.block_m, op.block.block_k, op.block.block_n)
                       if op.block else None),
                block_i=op.block_i,
                block_rows=op.block_rows,
                mode=op.mode,
                lanes=op.lanes,
                n_passes=op.n_passes,
                vmem_kib=op.vmem_bytes / 1024,
                est_cycles=op.est_cycles,
                hbm_bytes=op.hbm_bytes,
                uhat_hbm_bytes=op.uhat_hbm_bytes,
                intermediate_hbm_bytes=op.intermediate_hbm_bytes,
                req_kib=op.requirement.required_bytes / 1024,
                duration_cycles=op.requirement.duration_cycles,
            ))
        return rows


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------

def _requirement(profile: OperationProfile) -> PhaseRequirement:
    return PhaseRequirement(name=profile.name,
                            required_bytes=profile.total_mem,
                            duration_cycles=profile.total_cycles)


def _layer_descs(stack) -> tuple:
    """``analysis.capsnet_stack_profiles`` layer descriptors for a
    resolved routing stack (the per-layer profile-name suffix is the
    instance name minus the shared ``FUSED_NAME`` base)."""
    return tuple((lay.name[len(FUSED_NAME):], lay.in_caps, lay.in_dim,
                  lay.num_caps, lay.caps_dim, lay.iters) for lay in stack)


def activation_residency_bytes(cfg: CapsNetConfig, *, batch: int = 1,
                               reversible: bool = True) -> int:
    """Modeled bytes of ROUTING-STACK activations a training step must
    keep live for the backward pass.

    ``reversible=False`` is the conventional autodiff accounting: every
    routing-layer instance saves its input capsule tensor
    ``[B, in_caps, in_dim]``, so the total grows linearly in depth.
    ``reversible=True`` is what the plan actually executes: a maximal run
    of residual coupling halves forms ONE reversible segment that saves
    only its OUTPUT (the backward re-derives every interior state by
    inverting the additive couplings), so an all-residual stack costs one
    segment tensor no matter how many blocks are stacked -- activation
    memory flat in depth.  Plain (non-residual) layers still save their
    input either way.
    """
    stack = cfg.routing_stack()
    total, k = 0, 0
    while k < len(stack):
        lay = stack[k]
        if reversible and lay.residual:
            # x = [x1 | x2]: the F half consumes x2 and emits x1's width,
            # so the segment tensor is (in_caps + num_caps) capsules.
            seg_caps = lay.in_caps + lay.num_caps
            total += batch * seg_caps * lay.in_dim * ELEM_BYTES
            while k < len(stack) and stack[k].residual:
                k += 1
        else:
            total += batch * lay.in_caps * lay.in_dim * ELEM_BYTES
            k += 1
    return total


def _votes_vmem(batch: int, block_i: int, caps_dim: int, out_dim: int) -> int:
    """caps_votes footprint per grid step (double-buffered streams)."""
    data = batch * block_i * caps_dim * ELEM_BYTES
    weight = block_i * out_dim * caps_dim * ELEM_BYTES
    accum = batch * block_i * out_dim * ELEM_BYTES
    return 2 * (data + weight) + accum


def _votes_max_batch(caps_dim: int, out_dim: int, vmem_budget: int) -> int:
    """Largest batch whose block_i=1 caps-votes footprint fits the budget."""
    fixed = 2 * out_dim * caps_dim * ELEM_BYTES          # weight tile
    per_batch = (2 * caps_dim + out_dim) * ELEM_BYTES    # data + accum rows
    return max((vmem_budget - fixed) // per_batch, 0)


def _votes_block_i_raw(num_caps: int, caps_dim: int, out_dim: int,
                       batch: int, vmem_budget: int) -> int:
    """Split-path caps-votes i-tile: planner pick shrunk to the budget at
    the REAL batch (the memoized plan-less wrapper in ``kernels/ops.py``
    shares this, so a batched call can no longer exceed the footprint the
    planner guarantees).  Raises ``PlanError`` when even ``block_i=1``
    exceeds the budget (instead of letting ``validate()`` fail later with
    a generic footprint message)."""
    wl = MatmulWorkload(m=num_caps, k=caps_dim, n=out_dim)
    block = plan_matmul(wl, vmem_budget)
    bi = max(min(block.block_m, num_caps), 1)
    while bi > 1 and _votes_vmem(batch, bi, caps_dim, out_dim) > vmem_budget:
        bi //= 2
    need = _votes_vmem(batch, bi, caps_dim, out_dim)
    if need > vmem_budget:
        raise PlanError(
            f"ClassCaps-FC: no feasible schedule at batch={batch}: even "
            f"block_i=1 needs {need} B of VMEM, over the {vmem_budget} B "
            f"budget; largest feasible batch is "
            f"{_votes_max_batch(caps_dim, out_dim, vmem_budget)}")
    return bi


# ---------------------------------------------------------------------------
# Fused votes+routing schedule (the megakernel's resident-vs-streamed DSE)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VotesRoutingSchedule:
    """Plan decision for the fused ``votes_routing`` megakernel."""

    mode: str                # "resident" | "streamed"
    block_i: int
    vmem_bytes: int          # footprint of the CHOSEN schedule
    n_passes: int            # W streams: 1 resident, iters+1 streamed
    workload: MatmulWorkload
    lanes: str = "caps"      # kernel layout (see ``LAYOUTS``)


def _i_padded(num_caps: int, block_i: int) -> int:
    return math.ceil(num_caps / block_i) * block_i


def _lane_block_i(num_caps: int, bi0: int) -> int:
    """The lane-legal i-tile nearest a generic matmul ``block_m`` pick.

    The caps-on-lanes routing layout keeps the capsule axis on the
    128-lane axis, so an i-tile is either the whole axis (one block) or a
    multiple of 128.  Among the multiples of 128 up to ``bi0`` the pick
    minimizes the zero-padded ``ceil(I/block_i) * block_i`` rows the
    kernels allocate and stream (ties keep the largest): block_i=1024
    over MNIST's I=1152 would pad to 2048 rows -- 78% phantom W traffic
    on every stream -- where 384 or 128 divide 1152 exactly.  The static
    auditor (repro.verify.lowering) found exactly this drift between the
    modeled traffic and the lowering's index maps."""
    if num_caps <= LANES or bi0 >= num_caps:
        return num_caps
    return min(range(max(bi0 // LANES, 1) * LANES, 0, -LANES),
               key=lambda bi: (_i_padded(num_caps, bi), -bi))


def _shrink_ladder(start: int) -> list[int]:
    """Lane-legal tiles of a lane axis from ``start`` down:
    ``start`` (the whole axis or a multiple of 128), then the largest
    multiple of 128 below a ragged ``start``, then successive halvings
    rounded down to multiples of 128.  A tile below one lane tile frees
    no VMEM under the (8, 128) tiling, so the ladder stops at 128 -- or
    at ``start`` itself when the axis is shorter."""
    out = [start]
    t = (start // LANES * LANES if start % LANES
         else start // 2 // LANES * LANES)
    while t >= LANES and t < out[-1]:
        out.append(t)
        t = t // 2 // LANES * LANES
    return out


def _sublane_ladder(num_caps: int) -> list[int]:
    """i-tiles of the classes-on-lanes layout, where I lies on sublanes:
    the whole axis when it is at most one 128-row tile, else 128, then
    halvings rounded down to multiples of 8, down to 8."""
    out = [min(num_caps, LANES)]
    t = out[0] // 2 // SUBLANES * SUBLANES
    while t >= SUBLANES:
        out.append(t)
        t = t // 2 // SUBLANES * SUBLANES
    return out


def _i_ladder(lanes: str, num_caps: int, caps_dim: int, jd: int
              ) -> list[int]:
    """Candidate i-tiles of one layout, largest first."""
    if lanes == "classes":
        return _sublane_ladder(num_caps)
    wl = MatmulWorkload(m=num_caps, k=caps_dim, n=jd, in_bytes=ELEM_BYTES)
    # Tile-shape pick only (the per-mode footprint model is what is held
    # to the budget, not the generic double-buffered matmul model),
    # refined to the lane-legal, i-padding-minimal candidate.
    return _shrink_ladder(_lane_block_i(
        num_caps, max(min(plan_matmul(wl).block_m, num_caps), 1)))


def _min_block_i(num_caps: int, lanes: str = "caps") -> int:
    return min(num_caps, SUBLANES if lanes == "classes" else LANES)


def _i_buf(num_caps: int, block_i: int) -> int:
    """Tile buffer count: 2 (double-buffered) when the i-axis spans more
    than one block, 1 when a single block covers it -- a block whose
    index never changes is fetched once and never swapped, so the
    lowering holds exactly one copy (the static auditor measured the
    2x model against single-block lowerings at twice the real tiles)."""
    return 2 if _i_padded(num_caps, block_i) > block_i else 1


def _caps_vec(batch: int, j: int, d: int, lanes: str = "caps") -> int:
    """One class-capsule vector buffer: [B, J, D, 1] (D on sublanes, one
    lane) with caps on lanes, [B, D, 1, J] with classes on lanes."""
    if lanes == "classes":
        return tile_padded((batch, d, 1, j))
    return tile_padded((batch, j, d, 1))


def _u_buf(batch: int, num_caps: int, block_i: int, caps_dim: int,
           lanes: str) -> int:
    """u as the kernels hold it: resident [B, C, I_pad] (fetched once)
    with caps on lanes; one streamed [B, TI, C] i-block with classes on
    lanes."""
    if lanes == "classes":
        return (_i_buf(num_caps, block_i)
                * tile_padded((batch, block_i, caps_dim)))
    return tile_padded((batch, caps_dim, _i_padded(num_caps, block_i)))


def _logits(batch: int, num_caps: int, block_i: int, j: int,
            lanes: str) -> int:
    """The routing logits slab over all of I_pad."""
    i_pad = _i_padded(num_caps, block_i)
    if lanes == "classes":
        return tile_padded((batch, i_pad, j))
    return tile_padded((batch, j, i_pad))


def _votes(batch: int, rows: int, j: int, d: int, lanes: str) -> int:
    """A votes buffer over ``rows`` capsules: [B, J, D, rows] with caps on
    lanes, [B, D, rows, J] with classes on lanes."""
    if lanes == "classes":
        return tile_padded((batch, d, rows, j))
    return tile_padded((batch, j, d, rows))


def _routing_state(batch: int, num_caps: int, block_i: int, caps_dim: int,
                   jd: int, j: int, lanes: str = "caps") -> int:
    """Buffers every fused forward schedule holds (elements, tile-padded):
    u, the routing logits, the s / v candidates and the output vector,
    and one votes block in flight per step."""
    d = jd // j
    return (_u_buf(batch, num_caps, block_i, caps_dim, lanes)
            + _logits(batch, num_caps, block_i, j, lanes)
            + 3 * _caps_vec(batch, j, d, lanes)
            + _votes(batch, block_i, j, d, lanes))


def _w_tile(num_caps: int, block_i: int, caps_dim: int, jd: int,
            j: int, lanes: str = "caps") -> int:
    """The streamed W tile ([C, J, D, block_i] with caps on lanes,
    [C, D, block_i, J] with classes on lanes) and its buffers."""
    d = jd // j
    shape = ((caps_dim, d, block_i, j) if lanes == "classes"
             else (caps_dim, j, d, block_i))
    return _i_buf(num_caps, block_i) * tile_padded(shape)


def _fused_resident_vmem(batch: int, num_caps: int, block_i: int,
                         caps_dim: int, jd: int, j: int,
                         lanes: str = "caps") -> int:
    """Resident schedule: the full votes tensor lives in VMEM scratch next
    to the routing state while W i-tiles stream past once (every buffer
    tile-padded: the footprint Mosaic allocates)."""
    votes = _votes(batch, _i_padded(num_caps, block_i), j, jd // j, lanes)
    return (votes
            + _routing_state(batch, num_caps, block_i, caps_dim, jd, j, lanes)
            + _w_tile(num_caps, block_i, caps_dim, jd, j, lanes)
            ) * ELEM_BYTES


def _fused_streamed_vmem(batch: int, num_caps: int, block_i: int,
                         caps_dim: int, jd: int, j: int,
                         lanes: str = "caps") -> int:
    """Streamed schedule: only the routing state stays resident; W tiles
    stream (double-buffered) each pass and every step recomputes one
    votes block."""
    return (_routing_state(batch, num_caps, block_i, caps_dim, jd, j, lanes)
            + _w_tile(num_caps, block_i, caps_dim, jd, j, lanes)
            ) * ELEM_BYTES


def _residual_vmem(batch: int, j: int, d: int, lanes: str) -> int:
    """The [B, J*D] skip operand a coupling half's epilogue holds."""
    return _caps_vec(batch, j, d, lanes) * ELEM_BYTES


def _streamed_floor(batch: int, num_caps: int, caps_dim: int, jd: int,
                    j: int, residual: bool = False) -> tuple[int, int, str]:
    """(bytes, block_i, lanes) of the cheapest streamed schedule at the
    smallest legal i-tile of either layout -- the point below which no
    schedule can keep the routing state on-chip."""
    return min((_fused_streamed_vmem(batch, num_caps,
                                     _min_block_i(num_caps, lanes),
                                     caps_dim, jd, j, lanes)
                + (_residual_vmem(batch, j, jd // j, lanes)
                   if residual else 0),
                _min_block_i(num_caps, lanes), lanes)
               for lanes in LAYOUTS)


def _fused_max_batch(num_caps: int, caps_dim: int, jd: int, j: int,
                     vmem_budget: int, residual: bool = False) -> int:
    """Largest batch whose cheapest streamed footprint (either layout, at
    its smallest legal i-tile) fits; ``residual`` counts a coupling
    half's skip operand."""
    b = 0
    while _streamed_floor(b + 1, num_caps, caps_dim, jd, j,
                          residual)[0] <= vmem_budget:
        b += 1
    return b


def plan_votes_routing(num_caps: int, caps_dim: int, jd: int, j: int, *,
                       batch: int = 1, iters: int = 3,
                       vmem_budget: int = VMEM_BYTES,
                       name: str = FUSED_NAME,
                       residual: bool = False) -> VotesRoutingSchedule:
    """Layout, resident-vs-streamed and i-tile decision for the fused
    megakernel.

    Prefer the caps-on-lanes layout, then **resident** (votes computed
    once into scratch, routing iterates on-chip -- the split path's
    behavior minus the u_hat HBM round-trip); fall back to **streamed**
    (votes recomputed from re-streamed W tiles each pass) when the votes
    tensor cannot fit the budget at any i-tile.  The streamed schedule
    fuses each iteration's s-accumulation with its logits update into
    ONE W stream (the b-update runs against the previous pass's ``v``
    held in scratch), so ``W`` moves ``iters + 1`` times per forward.
    When no caps-on-lanes schedule fits (a wide layer: one 128-capsule W
    tile, or the lane-padded class vectors, exceed the budget) the same
    search runs with the output capsules on the lanes.  Raises
    ``PlanError`` only when even the cheapest streamed floor exceeds the
    budget -- the point where no schedule can keep the routing state
    on-chip at this batch.

    ``name`` labels the layer instance in the error (deep stacks plan one
    schedule per routing layer); ``residual`` adds the [B, J*D] residual
    operand a coupling half's epilogue holds alongside the output.
    """
    wl = MatmulWorkload(m=num_caps, k=caps_dim, n=jd, in_bytes=ELEM_BYTES)
    for lanes in LAYOUTS:
        extra = (_residual_vmem(batch, j, jd // j, lanes) if residual
                 else 0)
        ladder = _i_ladder(lanes, num_caps, caps_dim, jd)
        for mode, vmem_of, n_passes in (
                ("resident", _fused_resident_vmem, 1),
                ("streamed", _fused_streamed_vmem, iters + 1)):
            for bi in ladder:
                need = vmem_of(batch, num_caps, bi, caps_dim, jd, j,
                               lanes) + extra
                if need <= vmem_budget:
                    return VotesRoutingSchedule(
                        mode=mode, block_i=bi, vmem_bytes=need,
                        n_passes=n_passes, workload=wl, lanes=lanes)
    need, bi, lanes = _streamed_floor(batch, num_caps, caps_dim, jd, j,
                                      residual)
    raise PlanError(
        f"{name}: no feasible schedule at batch={batch}: even "
        f"streamed block_i={bi} ({lanes} on lanes) needs {need} B of "
        f"VMEM, over the {vmem_budget} B budget; largest feasible batch "
        f"is {_fused_max_batch(num_caps, caps_dim, jd, j, vmem_budget, residual)}")


def votes_routing_hbm_bytes(batch: int, num_caps: int, caps_dim: int,
                            jd: int, n_passes: int,
                            block_i: int | None = None,
                            lanes: str = "caps") -> float:
    """Modeled HBM traffic of the fused megakernel per forward: u read
    once (caps on lanes: resident) or alongside every W stream (classes
    on lanes: u streams by i-block with W), W streamed ``n_passes``
    times, v written once -- and NO u_hat term (the tensor never exists
    off-chip).

    With ``block_i`` the model counts the i-rows the lowering actually
    moves: the wrapper zero-pads u/W to ``ceil(I/block_i) * block_i``
    rows, so padded rows cross HBM like real ones -- and when ONE block
    covers the whole i-axis the W block index never changes, so W is
    fetched once no matter how many passes the grid makes (Pallas keeps
    the unchanged block in VMEM).  ``None`` keeps the unpadded
    idealization (what a perfectly divisible tile achieves)."""
    i_eff = _i_padded(num_caps, block_i) if block_i else num_caps
    w_sweeps = 1 if block_i is not None and i_eff <= block_i else n_passes
    u = batch * i_eff * caps_dim * (w_sweeps if lanes == "classes" else 1)
    w = i_eff * jd * caps_dim * w_sweeps
    v = batch * jd
    return float((u + w + v) * ELEM_BYTES)


def lane_relayout_hbm_bytes(batch: int, num_caps: int, caps_dim: int,
                            jd: int, *, lanes: str = "caps",
                            vectors: int = 1) -> float:
    """HBM bytes the routing wrappers spend laying the operands out for
    the kernel (``kernels.votes_routing`` ``to_kernel`` / ``to_vec``), one
    read and one write per transposed array (the i-axis zero-padding
    fuses into the same pass).  Caps on lanes transposes ``u [B, I, C]``
    and ``W [I, J*D, C]``; ``batch=0`` counts W alone (the pipelined
    pair never holds u in HBM).  Classes on lanes transposes W and each
    of the ``vectors`` [B, J*D] class vectors crossing the kernel
    boundary (output, residual) -- u keeps its layout."""
    if lanes == "classes":
        return float(2 * (jd * num_caps * caps_dim + vectors * batch * jd)
                     * ELEM_BYTES)
    return float(2 * (batch + jd) * num_caps * caps_dim * ELEM_BYTES)


def lane_relayout_bwd_hbm_bytes(batch: int, num_caps: int, caps_dim: int,
                                jd: int, *, lanes: str = "caps") -> float:
    """The backward's relayouts: the inputs in and du / dW back out --
    with caps on lanes the forward's bytes twice; with classes on lanes
    W and dW, the cotangent vector in and du out."""
    if lanes == "classes":
        return float(2 * (2 * jd * num_caps * caps_dim + batch * jd
                          + batch * num_caps * caps_dim) * ELEM_BYTES)
    return 2 * lane_relayout_hbm_bytes(batch, num_caps, caps_dim, jd)


def split_votes_routing_hbm_bytes(batch: int, num_caps: int, caps_dim: int,
                                  jd: int) -> tuple[float, float]:
    """(total, u_hat share) of the split ``caps_votes`` -> ``routing``
    path: the votes tensor is written by one kernel and read back by the
    next -- the produce-once/consume-once round-trip the fusion kills."""
    u = batch * num_caps * caps_dim
    w = num_caps * jd * caps_dim
    v = batch * jd
    uhat = 2 * batch * num_caps * jd                 # write + read back
    return float((u + w + v + uhat) * ELEM_BYTES), float(uhat * ELEM_BYTES)


# ---------------------------------------------------------------------------
# Pipelined PrimaryCaps->ClassCaps pair (inter-op residency DSE)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PrimaryRoutingSchedule:
    """Plan decision for the pipelined producer->consumer megakernel.

    The producer output u ([B, I, C] -- the inter-layer activation) is
    SMALL, so the whole tensor lives in VMEM scratch: a K-blocked produce
    phase accumulates the im2col matmul into it and applies the
    bias+squash epilogue in place, then the votes/routing phases read its
    i-blocks exactly the way the fused megakernel reads u from HBM.
    Patches and the conv weight are fetched ONCE (vs once per re-stream
    on a per-i-block recompute), and u itself never exists off-chip.
    """

    mode: str                # votes/routing schedule: "resident"|"streamed"
    block_i: int             # votes/routing i-tile
    block_k: int             # produce-phase K tile (im2col reduction)
    k_steps: int             # ceil(K / block_k) produce grid steps
    vmem_bytes: int          # footprint of the CHOSEN schedule
    n_passes: int            # ClassCaps W streams: 1 resident, iters+1 str.
    workload: MatmulWorkload # the producer's im2col matmul
    block: BlockPlan         # producer tiling (VJP replay matmuls)


def _pipe_produce_vmem(batch: int, p_pos: int, n_ch: int,
                       block_k: int) -> int:
    """Produce-phase residency shared by both pipelined schedules: the
    pre-activation scratch [B*P, N] (the conv channels on lanes), the
    patch-row scratch [B*P, block_k] the step's one dot reads,
    double-buffered patch [B, P, block_k] / conv-weight [block_k, N] K
    tiles and the bias row (u's [B, C, I_pad] scratch is part of the
    routing state)."""
    rows = batch * p_pos
    return (tile_padded((rows, n_ch)) + tile_padded((rows, block_k))
            + 2 * (tile_padded((batch, p_pos, block_k))
                   + tile_padded((block_k, n_ch)))
            + tile_padded((1, n_ch)))


def _pipe_resident_vmem(batch: int, p_pos: int, n_ch: int, block_k: int,
                        num_caps: int, block_i: int, caps_dim: int,
                        jd: int, j: int) -> int:
    """Resident consumer on top of the produce-phase residency: the full
    votes tensor in scratch, W_cc i-tiles, the routing state."""
    i_pad = _i_padded(num_caps, block_i)
    return (_pipe_produce_vmem(batch, p_pos, n_ch, block_k)
            + tile_padded((batch, j, jd // j, i_pad))
            + _routing_state(batch, num_caps, block_i, caps_dim, jd, j)
            + _w_tile(num_caps, block_i, caps_dim, jd, j)) * ELEM_BYTES


def _pipe_streamed_vmem(batch: int, p_pos: int, n_ch: int, block_k: int,
                        num_caps: int, block_i: int, caps_dim: int,
                        jd: int, j: int) -> int:
    """Streamed consumer on top of the produce-phase residency: W_cc
    tiles re-streamed each pass, one votes block recomputed per step."""
    return (_pipe_produce_vmem(batch, p_pos, n_ch, block_k)
            + _routing_state(batch, num_caps, block_i, caps_dim, jd, j)
            + _w_tile(num_caps, block_i, caps_dim, jd, j)) * ELEM_BYTES


def plan_primary_routing(p_pos: int, k_in: int, n_ch: int, num_caps: int,
                         caps_dim: int, jd: int, j: int, *,
                         batch: int = 1, iters: int = 3,
                         vmem_budget: int = VMEM_BYTES
                         ) -> PrimaryRoutingSchedule:
    """Schedule DSE for the pipelined PrimaryCaps->ClassCaps pair.

    Prefer the resident consumer (votes computed once into scratch);
    fall back to streamed (votes recomputed from re-streamed W, the
    fused s+b pass -- ``iters + 1`` W streams).  Both shrink the votes
    i-tile first, then the produce K tile (lane-legal tiles only), before
    giving up.  Raises ``PlanError`` when even the smallest streamed
    tiles exceed the budget -- ``compile_plan`` then falls back to the
    per-op pair (which may itself still fit: its phases never coexist).
    """
    wl = MatmulWorkload(m=batch * p_pos, k=k_in, n=n_ch,
                        in_bytes=ELEM_BYTES)
    try:
        blk = plan_matmul(wl, vmem_budget)
    except ValueError as err:
        raise PlanError(f"{PIPE_NAME}: no feasible producer tiling at "
                        f"batch={batch}: {err}")
    k_ladder = _shrink_ladder(max(min(blk.block_k, k_in), 1))
    vr_wl = MatmulWorkload(m=num_caps, k=caps_dim, n=jd,
                           in_bytes=ELEM_BYTES)
    i_ladder = _shrink_ladder(_lane_block_i(
        num_caps, max(min(plan_matmul(vr_wl).block_m, num_caps), 1)))

    def _fit(vmem_of):
        for bk in k_ladder:
            for bi in i_ladder:
                need = vmem_of(bi, bk)
                if need <= vmem_budget:
                    return bi, bk, need
        return None

    for mode, vmem_of, n_passes in (
            ("resident", _pipe_resident_vmem, 1),
            ("streamed", _pipe_streamed_vmem, iters + 1)):
        fit = _fit(lambda bi, bk: vmem_of(batch, p_pos, n_ch, bk, num_caps,
                                          bi, caps_dim, jd, j))
        if fit is not None:
            bi, bk, need = fit
            return PrimaryRoutingSchedule(
                mode=mode, block_i=bi, block_k=bk,
                k_steps=math.ceil(k_in / bk), vmem_bytes=need,
                n_passes=n_passes, workload=wl, block=blk)
    bi, bk = i_ladder[-1], k_ladder[-1]
    need = _pipe_streamed_vmem(batch, p_pos, n_ch, bk, num_caps, bi,
                               caps_dim, jd, j)
    raise PlanError(
        f"{PIPE_NAME}: no feasible pipelined schedule at batch={batch}: "
        f"even streamed block_i={bi}, block_k={bk} needs {need} B of VMEM, "
        f"over the {vmem_budget} B budget")


def primary_routing_hbm_bytes(batch: int, p_pos: int, k_in: int, n_ch: int,
                              num_caps: int, caps_dim: int, jd: int,
                              n_passes: int,
                              block_i: int | None = None,
                              block_k: int | None = None) -> float:
    """Modeled HBM traffic of the pipelined pair per forward: patches and
    the conv weight+bias each read ONCE (the produce phase streams K
    tiles past the resident output scratch), the routing W streamed
    ``n_passes`` times, v written once -- and NO u term at all (the
    inter-layer activation never exists off-chip).

    ``block_i`` pads the routing W rows to the i-tile grid, ``block_k``
    pads the im2col reduction (patch columns / conv-weight rows) to the
    K-tile grid -- the rows/columns the lowering actually streams;
    ``None`` keeps the unpadded idealization."""
    i_eff = _i_padded(num_caps, block_i) if block_i else num_caps
    k_eff = _i_padded(k_in, block_k) if block_k else k_in
    w_sweeps = 1 if block_i is not None and i_eff <= block_i else n_passes
    patches = batch * p_pos * k_eff
    wpc = k_eff * n_ch + n_ch
    w_cc = i_eff * jd * caps_dim * w_sweeps
    v = batch * jd
    return float((patches + wpc + w_cc + v) * ELEM_BYTES)


def primary_intermediate_hbm_bytes(batch: int, num_caps: int,
                                   caps_dim: int) -> float:
    """The u round-trip a per-op plan pays between PrimaryCaps and the
    votes/routing megakernel: written by the conv epilogue, read back by
    the u-load -- the traffic the pipelined pair eliminates."""
    return float(2 * batch * num_caps * caps_dim * ELEM_BYTES)


def _pipe_requirement(in_caps: int, j: int, jd: int,
                      profs: Sequence[OperationProfile],
                      sched: PrimaryRoutingSchedule) -> PhaseRequirement:
    """ONE PMU phase for the pipelined pair, honest per mode: the produce
    phase's demand is the PrimaryCaps profile's; the consumer phases match
    ``_fused_requirement`` (with u's residency already counted -- it IS
    the produce scratch).  Duration is the four covered operations' sum
    with the votes computation scaled by the W-pass count.
    ``in_caps``/``j``/``jd`` are the consumed routing layer's dimensions
    (the FIRST layer of a deep stack)."""
    pc, cc, ss, us = profs
    duration = (pc.total_cycles + cc.total_cycles * sched.n_passes
                + ss.total_cycles + us.total_cycles)
    if sched.mode == "resident":
        req = max(p.total_mem for p in profs)
    else:
        bij = in_caps * j
        req = max(pc.total_mem,
                  cc.data_mem
                  + bij * (analysis.ACC_BYTES + analysis.ACT_BYTES)
                  + cc.weight_mem
                  + 4 * jd * analysis.ACC_BYTES)
    return PhaseRequirement(name=PIPE_NAME, required_bytes=req,
                            duration_cycles=duration)


# ---------------------------------------------------------------------------
# Fused votes+routing BACKWARD schedule (the custom-VJP kernels' DSE)
# ---------------------------------------------------------------------------

def _bwd_state(batch: int, num_caps: int, block_i: int, caps_dim: int,
               jd: int, j: int, lanes: str = "caps") -> int:
    """Buffers both backward schedules hold (elements, tile-padded): u,
    the W tile, the output cotangent, one du / dW block each, a ROLLING
    PAIR of logits slabs (only ``b_{T-1}``/``b_T`` are ever consumed
    again under the stop-gradient convention; ``db_T`` is rebuilt per
    block, never held), the s pair (overwritten by the ds pair),
    accumulator and v vectors, and one votes block in flight.
    Independent of ``iters``: the replay reuses the two slots."""
    d = jd // j
    if lanes == "classes":
        du_blk = tile_padded((caps_dim, batch, block_i, 1))
        dw_blk = tile_padded((caps_dim, d, block_i, j))
    else:
        du_blk = tile_padded((batch, caps_dim, block_i))
        dw_blk = tile_padded((caps_dim, j, d, block_i))
    return (_u_buf(batch, num_caps, block_i, caps_dim, lanes)
            + _w_tile(num_caps, block_i, caps_dim, jd, j, lanes)
            + _caps_vec(batch, j, d, lanes)                    # cotangent
            + du_blk + dw_blk
            + 2 * _logits(batch, num_caps, block_i, j, lanes)  # b pair
            + 4 * _caps_vec(batch, j, d, lanes)                # s/acc/v
            + _votes(batch, block_i, j, d, lanes))             # votes block


def _fused_resident_bwd_vmem(batch: int, num_caps: int, block_i: int,
                             caps_dim: int, jd: int, j: int,
                             iters: int, lanes: str = "caps") -> int:
    """Resident backward: the rebuilt votes scratch on top of the shared
    backward state (``W`` streams twice: rebuild + emit)."""
    del iters
    votes = _votes(batch, _i_padded(num_caps, block_i), j, jd // j, lanes)
    return (votes + _bwd_state(batch, num_caps, block_i, caps_dim, jd, j,
                               lanes)) * ELEM_BYTES


def _fused_streamed_bwd_vmem(batch: int, num_caps: int, block_i: int,
                             caps_dim: int, jd: int, j: int,
                             iters: int, lanes: str = "caps") -> int:
    """Streamed backward: W tiles stream on every pass and each step
    recomputes one votes block -- ``d u_hat`` exists only one i-block at
    a time."""
    del iters
    return _bwd_state(batch, num_caps, block_i, caps_dim, jd, j,
                      lanes) * ELEM_BYTES


def _streamed_bwd_floor(batch: int, num_caps: int, caps_dim: int, jd: int,
                        j: int, iters: int) -> tuple[int, int, str]:
    """(bytes, block_i, lanes) of the cheapest streamed backward at the
    smallest legal i-tile of either layout."""
    return min((_fused_streamed_bwd_vmem(batch, num_caps,
                                         _min_block_i(num_caps, lanes),
                                         caps_dim, jd, j, iters, lanes),
                _min_block_i(num_caps, lanes), lanes)
               for lanes in LAYOUTS)


def _fused_bwd_max_batch(num_caps: int, caps_dim: int, jd: int, j: int,
                         iters: int, vmem_budget: int) -> int:
    """Largest batch whose cheapest streamed-backward footprint fits."""
    b = 0
    while _streamed_bwd_floor(b + 1, num_caps, caps_dim, jd, j,
                              iters)[0] <= vmem_budget:
        b += 1
    return b


def plan_votes_routing_bwd(num_caps: int, caps_dim: int, jd: int, j: int, *,
                           batch: int = 1, iters: int = 3,
                           vmem_budget: int = VMEM_BYTES,
                           name: str = FUSED_NAME) -> VotesRoutingSchedule:
    """Layout, resident-vs-streamed and i-tile decision for the fused
    megakernel's BACKWARD, in the forward's preference order.

    Chosen independently of the forward: the backward's scratch is larger
    (the logits pair rides along, and resident additionally
    holds the rebuilt votes), so a budget can plan the forward resident
    -- or plan the forward at all -- and still be unable to run the
    backward.  That boundary raises a ``PlanError`` naming the backward
    op and the largest feasible batch, instead of failing opaquely in
    ``validate()``.

    ``n_passes`` counts W streams: 2 resident (votes rebuild + du/dW
    emit), ``iters + 4`` streamed (forward replay ``T+1`` -- one W stream
    per replayed iteration -- then the ds seed, ONE dv/ds reverse pass,
    emit; the stop-gradient convention means ``d u_hat`` only ever needs
    ``ds_T`` and ``ds_{T-1}``, so there is no deep reverse recurrence to
    stream W for).
    """
    wl = MatmulWorkload(m=num_caps, k=caps_dim, n=jd, in_bytes=ELEM_BYTES)
    for lanes in LAYOUTS:
        ladder = _i_ladder(lanes, num_caps, caps_dim, jd)
        for mode, vmem_of, n_passes in (
                ("resident", _fused_resident_bwd_vmem, 2),
                ("streamed", _fused_streamed_bwd_vmem, iters + 4)):
            for bi in ladder:
                need = vmem_of(batch, num_caps, bi, caps_dim, jd, j, iters,
                               lanes)
                if need <= vmem_budget:
                    return VotesRoutingSchedule(
                        mode=mode, block_i=bi, vmem_bytes=need,
                        n_passes=n_passes, workload=wl, lanes=lanes)
    need, bi, lanes = _streamed_bwd_floor(batch, num_caps, caps_dim, jd, j,
                                          iters)
    raise PlanError(
        f"{name}{BWD_SUFFIX}: no feasible backward schedule at "
        f"batch={batch}: even streamed block_i={bi} ({lanes} on lanes) "
        f"needs {need} B of VMEM, over the {vmem_budget} B budget; "
        f"largest feasible batch is "
        f"{_fused_bwd_max_batch(num_caps, caps_dim, jd, j, iters, vmem_budget)}")


def votes_routing_bwd_hbm_bytes(batch: int, num_caps: int, caps_dim: int,
                                jd: int, *, mode: str, iters: int,
                                block_i: int | None = None,
                                lanes: str = "caps") -> float:
    """Modeled HBM traffic of the fused backward per step: W streamed once
    per pass (2 resident: rebuild + emit; ``iters + 4`` streamed), u read
    once (constant index map), the output cotangent read once, du/dW
    written once -- and NO ``u_hat`` or ``d u_hat`` term (neither ever
    exists off-chip).

    ``block_i`` makes the i-terms padding-aware (u/W/du/dW are all padded
    to the i-tile grid by the wrapper; the kernel emits padded du/dW that
    the wrapper slices) -- and when one block covers the i-axis, W is
    fetched once however many passes the grid makes (the block index
    never changes, so Pallas keeps it in VMEM).  ``None`` is the
    unpadded idealization."""
    i_eff = _i_padded(num_caps, block_i) if block_i else num_caps
    single = block_i is not None and i_eff <= block_i
    w_passes = (2 if mode == "resident" else iters + 4) if not single else 1
    # u rides every W stream when it streams by i-block (classes on lanes)
    u = batch * i_eff * caps_dim * (w_passes if lanes == "classes" else 1)
    w = i_eff * jd * caps_dim * w_passes
    cot = batch * jd
    du = batch * i_eff * caps_dim
    dw = i_eff * jd * caps_dim
    return float((u + w + cot + du + dw) * ELEM_BYTES)


def spilled_votes_routing_bwd_hbm_bytes(batch: int, num_caps: int,
                                        caps_dim: int, jd: int
                                        ) -> tuple[float, float]:
    """(total, u_hat share) of a recompute-from-HBM backward: the forward
    spills ``u_hat``, the backward reads it back, writes ``d u_hat`` and
    reads it again for the du/dW contractions -- four votes-sized HBM
    trips the fused backward never makes."""
    uhat = 4 * batch * num_caps * jd
    u = batch * num_caps * caps_dim
    w = num_caps * jd * caps_dim
    cot = batch * jd
    du = batch * num_caps * caps_dim
    dw = num_caps * jd * caps_dim
    return (float((uhat + u + w + cot + du + dw) * ELEM_BYTES),
            float(uhat * ELEM_BYTES))


def conv_extract_hbm_bytes(in_hw: int, cin: int, k: int, out_hw: int, *,
                           batch: int = 1) -> float:
    """HBM traffic of the im2col extraction call per forward: the input
    feature map read once, the patch matrix written once.  The matmul
    model (``BlockPlan.hbm_bytes``) then counts the patch read-back; the
    static auditor measured the extraction side missing from both the
    per-op and the pipelined conv models (34.8% under at batch=4)."""
    return float(batch * (in_hw * in_hw * cin
                          + out_hw * out_hw * k * k * cin) * ELEM_BYTES)


def _conv_bwd_matmul_vmem(block, m: int, kcol: int, n: int) -> int:
    """Peak VMEM of the conv backward's blocked matmuls, which reuse the
    FORWARD tile choice (``kernels.conv_im2col._conv_core_bwd`` passes
    ``st.block_*`` through):

    * dW = patches^T @ dy (``matmul_at_b``): A tiled (bm, bk<=kcol),
      B tiled (bm, bn<=n), both double-buffered once their block index
      varies over the grid, plus the (bk, bn) accumulator;
    * dpatches = dy @ W^T (``matmul_bias_act`` with block_k/block_n
      SWAPPED): A (bm, bk<=n), W (bk, bn<=kcol), bias row, (bm, bn) out.

    The forward peak does not bound these -- at_b streams TWO bm-tall
    operands, so a multi-step m grid exceeds the forward model (the
    auditor caught Conv1-bwd 11.5% over at batch=2)."""
    def steps(total, blk):
        return math.ceil(total / blk)

    def dbuf(distinct):
        return 2 if distinct > 1 else 1

    bm = max(1, min(block.block_m, m))
    bk = max(1, min(block.block_k, kcol))
    bn = max(1, min(block.block_n, n))
    m_steps = steps(m, bm)
    at_b = (dbuf(m_steps * steps(kcol, bk)) * tile_padded((bm, bk))
            + dbuf(m_steps * steps(n, bn)) * tile_padded((bm, bn))
            + tile_padded((bk, bn))) * ELEM_BYTES
    bm2 = max(1, min(block.block_m, m))
    bk2 = max(1, min(block.block_n, n))
    bn2 = max(1, min(block.block_k, kcol))
    m2, k2, n2 = steps(m, bm2), steps(n, bk2), steps(kcol, bn2)
    dpatches = (dbuf(m2 * k2) * tile_padded((bm2, bk2))
                + dbuf(k2 * n2) * tile_padded((bk2, bn2))
                + dbuf(n2) * tile_padded((1, bn2))
                + tile_padded((bm2, bn2))) * ELEM_BYTES
    return max(at_b, dpatches)


def _fused_requirement(in_caps: int, j: int, jd: int,
                       profs: Sequence[OperationProfile],
                       sched: VotesRoutingSchedule,
                       name: str = FUSED_NAME) -> PhaseRequirement:
    """ONE PMU phase for one fused megakernel instance, honest per mode.

    Resident keeps the layer's votes in the accumulator memory across
    routing, so the phase demand is the peak of the three covered
    dataflow operations.  Streamed never materializes the votes: the
    demand is u + logits/couplings + the W prefetch buffer + the s/v
    candidates (dataflow-model byte widths).  The streamed duration
    scales the votes computation by the schedule's W-pass count
    (``iters + 1`` fused passes recompute the votes each stream); the
    resident duration is the plain three-operation sum (one pass).
    ``in_caps``/``j``/``jd`` are THIS layer instance's dimensions (a deep
    stack plans one phase per layer), ``name`` its plan-op name.
    """
    cc, ss, us = profs
    duration = (cc.total_cycles * sched.n_passes
                + ss.total_cycles + us.total_cycles)
    if sched.mode == "resident":
        req = max(cc.total_mem, ss.total_mem, us.total_mem)
    else:
        bij = in_caps * j
        req = (cc.data_mem                                    # u resident
               + bij * (analysis.ACC_BYTES + analysis.ACT_BYTES)  # b + c
               + cc.weight_mem                                # W prefetch
               + 4 * jd * analysis.ACC_BYTES)                 # s/v temps
    return PhaseRequirement(name=name, required_bytes=req,
                            duration_cycles=duration)


def _backward_profile(p: OperationProfile) -> OperationProfile:
    """Dataflow profile of one operation's backward pass.

    Reverse-mode doubles the MAC work (the d-input and d-weight products
    are each a forward-sized contraction) and the on-chip access counts
    with it; the per-component footprints stay the forward's -- the
    backward kernels reuse the same residencies, swapping ``u_hat`` /
    activations for their cotangents.
    """
    return dataclasses.replace(
        p, name=p.name + BWD_SUFFIX, macs=2 * p.macs, cycles=2 * p.cycles,
        data_reads=2 * p.data_reads, data_writes=2 * p.data_writes,
        weight_reads=2 * p.weight_reads,
        accum_reads=2 * p.accum_reads, accum_writes=2 * p.accum_writes,
        offchip_reads=2 * p.offchip_reads,
        offchip_writes=2 * p.offchip_writes)


def _fused_bwd_requirement(in_caps: int, j: int, jd: int, iters: int,
                           profs_bwd: Sequence[OperationProfile],
                           sched: VotesRoutingSchedule,
                           name: str = FUSED_NAME) -> PhaseRequirement:
    """ONE PMU phase for one fused backward instance, honest per mode
    (mirrors ``_fused_requirement``: resident holds votes-sized state
    across the replay, streamed holds u + the logits trajectory + small
    temps).  The votes-recompute cycles (the ClassCaps-FC-bwd profile,
    whose 2x-forward work matches resident's 2 W streams) scale with the
    schedule's W-pass count: ``iters + 4`` streamed passes each rebuild
    one votes block.  Dimensions are per layer instance, like
    ``_fused_requirement``'s."""
    duration = (sum(p.total_cycles for p in profs_bwd[:-1])
                + profs_bwd[-1].total_cycles * sched.n_passes / 2)
    if sched.mode == "resident":
        req = max(p.total_mem for p in profs_bwd)
    else:
        cc = profs_bwd[-1]                       # ClassCaps-FC-bwd
        bij = in_caps * j
        req = (cc.data_mem                                   # u resident
               + (iters + 2) * bij * analysis.ACC_BYTES      # b_t, db
               + cc.weight_mem                               # W prefetch
               + 8 * jd * analysis.ACC_BYTES)                # s/ds/dv temps
    return PhaseRequirement(name=name + BWD_SUFFIX,
                            required_bytes=req, duration_cycles=duration)


@functools.lru_cache(maxsize=64)
def compile_plan(cfg: CapsNetConfig = CapsNetConfig(), *, batch: int = 1,
                 vmem_budget: int = VMEM_BYTES,
                 dataflow: str = "resident",
                 train: bool = False,
                 pipeline: bool = False) -> ExecutionPlan:
    """Compile ``cfg`` into the per-operation ExecutionPlan (memoized:
    plans are immutable and the block-shape DSE runs once per shape).

    The five analysis operations map onto executors as follows:

      Conv1, PrimaryCaps -> ``conv_im2col`` kernels (strided Pallas patch
                            extraction + blocked matmul over the planner's
                            block_m/k/n tiles; PrimaryCaps fuses the squash
                            activation into the epilogue when its n-tile is
                            capsule-aligned)
      ClassCaps-FC,
      Sum+Squash,
      Update+Sum         -> ONE fused ``votes_routing`` megakernel (votes
                            from streamed W i-blocks + every routing
                            iteration in VMEM scratch -- u_hat never
                            touches HBM; ``plan_votes_routing`` picks the
                            resident or streamed schedule per config)

    ``requirement``s (PMU phases) keep the paper's per-inference dataflow
    model -- one phase per EXECUTED op, so the fused megakernel is scored
    as the single phase it runs; ``vmem_bytes`` scale with ``batch``
    where the kernel batches.

    ``pipeline=True`` additionally tries the producer->consumer PAIR:
    PrimaryCaps and the megakernel collapse into ONE ``primary_routing``
    OpPlan (combined VMEM footprint, combined PMU phase,
    ``intermediate_hbm_bytes=0`` -- the inter-layer u never off-chip)
    whenever the combined footprint fits the budget, silently keeping the
    per-op pair otherwise.  The backward OpPlans are unchanged: the
    pipelined VJP replays the producer from patches and runs exactly the
    per-op backward kernels.

    ``train=True`` appends one backward OpPlan per executed kernel, in
    reverse network order (the order the backward runs): the fused
    backward gets its own resident/streamed schedule
    (``plan_votes_routing_bwd`` -- its scratch is larger than the
    forward's, so the mode can differ), and each conv backward reuses the
    forward block tiles for its dW / dpatches matmuls and col2im scatter.
    Backward phases join ``phase_groups()`` so dse/pmu gate them too.
    """
    dims = analysis.dims_from_config(cfg)
    stack = cfg.routing_stack()
    profiles = analysis.capsnet_stack_profiles(dataflow, dims,
                                               _layer_descs(stack))
    by_name = {p.name: p for p in profiles}
    ops: list[OpPlan] = []

    # Conv stack: im2col matmuls the kernels EXECUTE with the planned
    # tiles.  Workloads carry the real batched row count and fp32 element
    # size so ``block.vmem_total`` is the honest double-buffered footprint
    # (patch tile + weight tile + accumulator) of the running kernel.
    conv_wls = {
        "Conv1": MatmulWorkload(m=batch * dims.conv1_out ** 2,
                                k=dims.conv1_k ** 2 * dims.conv1_cin,
                                n=dims.conv1_cout, in_bytes=ELEM_BYTES),
        "PrimaryCaps": MatmulWorkload(m=batch * dims.pc_out ** 2,
                                      k=dims.pc_k ** 2 * dims.pc_cin,
                                      n=dims.pc_cout, in_bytes=ELEM_BYTES),
    }
    conv_geom = {
        "Conv1": (dims.in_hw, dims.conv1_cin, dims.conv1_k, dims.conv1_out),
        "PrimaryCaps": (dims.conv1_out, dims.pc_cin, dims.pc_k, dims.pc_out),
    }
    squash_rows = batch * dims.num_primary
    block_rows = max(min(SQUASH_BLOCK_ROWS, squash_rows), 1)
    for name, wl in conv_wls.items():
        prof = by_name[name]
        block = plan_matmul(wl, vmem_budget)
        if train:
            # The backward's three matmuls reuse this tile choice, and
            # matmul_at_b streams TWO bm-tall operands -- shrink the
            # forward pick until the backward peak also honors the
            # budget (plan_matmul raises when nothing fits).
            eff = vmem_budget
            while (_conv_bwd_matmul_vmem(block, wl.m, wl.k, wl.n)
                   > vmem_budget and eff > 1):
                eff = eff * 3 // 4
                block = plan_matmul(wl, eff)
        bias_tile = 2 * tile_padded((1, block.block_n)) * ELEM_BYTES
        op = OpPlan(name=name, kernel="conv_im2col", workload=wl, block=block,
                    vmem_bytes=block.vmem_total + bias_tile,
                    est_cycles=block.est_cycles,
                    requirement=_requirement(prof), profiles=(prof,),
                    hbm_bytes=(block.hbm_bytes
                               + conv_extract_hbm_bytes(*conv_geom[name],
                                                        batch=batch)))
        if name == "PrimaryCaps":
            # The primary-capsule squash activation rides on this op: fused
            # into the matmul epilogue when every n-tile holds whole
            # capsules (the kernel clamps the tile to N), otherwise a
            # standalone blocked squash pass.
            if min(block.block_n, wl.n) % dims.primary_dim == 0:
                op = dataclasses.replace(op, kernel="conv_im2col+squash",
                                         block_rows=block_rows)
            else:
                op = dataclasses.replace(
                    op, block_rows=block_rows,
                    vmem_bytes=max(op.vmem_bytes,
                                   2 * block_rows * dims.primary_dim
                                   * ELEM_BYTES))
            # On a per-op plan this op's output u round-trips HBM to
            # reach the votes/routing megakernel (share of the plan's
            # forward_hbm_bytes; the pipelined pair reports 0 here).
            op = dataclasses.replace(
                op, intermediate_hbm_bytes=primary_intermediate_hbm_bytes(
                    batch, dims.num_primary, dims.primary_dim))
        ops.append(op)

    # Routing stack: ONE fused votes+routing megakernel per layer
    # instance (the historical single-op ClassCaps head is the one-layer
    # case).  Each layer runs its own resident-vs-streamed DSE at ITS
    # dimensions -- a PlanError names the offending layer -- and residual
    # coupling halves carry the [B, J*D] skip operand in their footprint
    # and an extra skip read in their traffic.
    layer_plans: list[tuple] = []
    for pos, lay in enumerate(stack):
        suffix = lay.name[len(FUSED_NAME):]
        lay_profs = tuple(by_name[n + suffix] for n in FUSED_COVERS)
        sched = plan_votes_routing(lay.in_caps, lay.in_dim, lay.jd,
                                   lay.num_caps, batch=batch,
                                   iters=lay.iters,
                                   vmem_budget=vmem_budget,
                                   name=lay.name, residual=lay.residual)
        votes_cycles = sched.workload.flops / (2 * MXU * MXU)
        routing_cycles = sum(p.total_cycles for p in lay_profs[1:])
        hbm = (votes_routing_hbm_bytes(batch, lay.in_caps, lay.in_dim,
                                       lay.jd, sched.n_passes,
                                       block_i=sched.block_i,
                                       lanes=sched.lanes)
               + lane_relayout_hbm_bytes(batch, lay.in_caps, lay.in_dim,
                                         lay.jd, lanes=sched.lanes,
                                         vectors=1 + lay.residual))
        if lay.residual:
            hbm += batch * lay.jd * ELEM_BYTES     # skip operand read
        # An intermediate layer's output round-trips HBM to the next
        # layer's kernel call; the FINAL layer's v is the network output.
        inter = (primary_intermediate_hbm_bytes(batch, lay.num_caps,
                                                lay.caps_dim)
                 if pos + 1 < len(stack) else None)
        ops.append(OpPlan(
            name=lay.name, kernel="votes_routing", workload=sched.workload,
            block=None, block_i=sched.block_i, mode=sched.mode,
            lanes=sched.lanes, n_passes=sched.n_passes, nota=lay.nota,
            vmem_bytes=sched.vmem_bytes,
            est_cycles=votes_cycles * sched.n_passes + routing_cycles,
            hbm_bytes=hbm,
            uhat_hbm_bytes=0.0,
            intermediate_hbm_bytes=inter,
            requirement=_fused_requirement(lay.in_caps, lay.num_caps,
                                           lay.jd, lay_profs, sched,
                                           name=lay.name),
            profiles=lay_profs))
        layer_plans.append((lay, lay_profs, sched, votes_cycles,
                            routing_cycles))

    # Pipelined producer->consumer pair: replace [PrimaryCaps, fused
    # megakernel] with ONE OpPlan whose kernel streams the conv's
    # squash-epilogue output straight from VMEM scratch into the
    # votes/routing accumulation.  Falls back to the per-op pair above
    # when the combined footprint exceeds the budget (PlanError only
    # when neither fits -- the per-op planning already raised then).
    conv1_op, pc_op = ops[0], ops[1]
    first, first_profs, _, first_votes, first_routing = layer_plans[0]
    pipe_sched = None
    if pipeline and not first.residual:
        # The pipelined pair fuses PrimaryCaps with the FIRST routing
        # layer (whatever its width); a residual first half cannot
        # pipeline -- its kernel consumes a skip operand that does not
        # exist until the producer has run.
        try:
            pipe_sched = plan_primary_routing(
                dims.pc_out ** 2, dims.pc_k ** 2 * dims.pc_cin,
                dims.pc_cout, first.in_caps, first.in_dim, first.jd,
                first.num_caps, batch=batch, iters=first.iters,
                vmem_budget=vmem_budget)
        except PlanError:
            pipe_sched = None            # per-op pair is the fallback
    if pipe_sched is not None:
        pipe_profs = (by_name["PrimaryCaps"],) + first_profs
        prod_cycles = pipe_sched.workload.flops / (2 * MXU * MXU)
        ops = [conv1_op, OpPlan(
            name=PIPE_NAME, kernel="primary_routing",
            workload=pipe_sched.workload, block=pipe_sched.block,
            block_i=pipe_sched.block_i, block_k=pipe_sched.block_k,
            mode=pipe_sched.mode, lanes="caps", n_passes=pipe_sched.n_passes,
            nota=first.nota,
            vmem_bytes=pipe_sched.vmem_bytes,
            est_cycles=(prod_cycles + first_votes * pipe_sched.n_passes
                        + first_routing),
            hbm_bytes=(primary_routing_hbm_bytes(
                batch, dims.pc_out ** 2, dims.pc_k ** 2 * dims.pc_cin,
                dims.pc_cout, first.in_caps, first.in_dim, first.jd,
                pipe_sched.n_passes, block_i=pipe_sched.block_i,
                block_k=pipe_sched.block_k)
                # ...plus the im2col extraction feeding the produce
                # phase (image read + patch store) and the wrapper's
                # W_cc relayout onto lanes, which the routing model
                # deliberately excludes.
                + conv_extract_hbm_bytes(*conv_geom["PrimaryCaps"],
                                         batch=batch)
                + lane_relayout_hbm_bytes(0, first.in_caps, first.in_dim,
                                          first.jd)),
            uhat_hbm_bytes=0.0,
            intermediate_hbm_bytes=(
                0.0 if len(stack) == 1 else
                primary_intermediate_hbm_bytes(batch, first.num_caps,
                                               first.caps_dim)),
            requirement=_pipe_requirement(first.in_caps, first.num_caps,
                                          first.jd, pipe_profs, pipe_sched),
            profiles=pipe_profs)] + ops[3:]

    if train:
        # Backward OpPlans, reverse network order.  The fused backward
        # gets its own schedule DSE (larger scratch than the forward:
        # a budget can plan forward-only); the conv backwards reuse the
        # forward tiles for their two (three with the squash recompute)
        # blocked matmuls plus the col2im scatter, whose peak footprint
        # matches the forward's (the stages run sequentially).
        for lay, lay_profs, fwd_sched, votes_cycles, routing_cycles \
                in reversed(layer_plans):
            bwd_sched = plan_votes_routing_bwd(
                lay.in_caps, lay.in_dim, lay.jd, lay.num_caps,
                batch=batch, iters=lay.iters, vmem_budget=vmem_budget,
                name=lay.name)
            bwd_profs = tuple(_backward_profile(p)
                              for p in reversed(lay_profs))
            est = votes_cycles * bwd_sched.n_passes + 2 * routing_cycles
            hbm = (votes_routing_bwd_hbm_bytes(
                batch, lay.in_caps, lay.in_dim, lay.jd,
                mode=bwd_sched.mode, iters=lay.iters,
                block_i=bwd_sched.block_i, lanes=bwd_sched.lanes)
                + lane_relayout_bwd_hbm_bytes(batch, lay.in_caps,
                                              lay.in_dim, lay.jd,
                                              lanes=bwd_sched.lanes))
            vmem = bwd_sched.vmem_bytes
            if lay.residual:
                # Reversible inversion (MoCapsNet-style): the backward
                # first replays this coupling half FORWARD from the
                # reconstructed segment state to invert the residual add,
                # then runs the ordinary fused VJP -- the recompute cost
                # of never saving the stack's activations.
                est += votes_cycles * fwd_sched.n_passes + routing_cycles
                hbm += (votes_routing_hbm_bytes(
                    batch, lay.in_caps, lay.in_dim, lay.jd,
                    fwd_sched.n_passes, block_i=fwd_sched.block_i,
                    lanes=fwd_sched.lanes)
                    + lane_relayout_hbm_bytes(batch, lay.in_caps,
                                              lay.in_dim, lay.jd,
                                              lanes=fwd_sched.lanes))
                vmem = max(vmem, fwd_sched.vmem_bytes)
            ops.append(OpPlan(
                name=lay.name + BWD_SUFFIX, kernel="votes_routing_bwd",
                workload=bwd_sched.workload, block=None,
                block_i=bwd_sched.block_i, mode=bwd_sched.mode,
                lanes=bwd_sched.lanes, n_passes=bwd_sched.n_passes,
                nota=lay.nota,
                vmem_bytes=vmem,
                est_cycles=est,
                hbm_bytes=hbm,
                uhat_hbm_bytes=0.0,
                requirement=_fused_bwd_requirement(
                    lay.in_caps, lay.num_caps, lay.jd, lay.iters,
                    bwd_profs, bwd_sched, name=lay.name),
                profiles=bwd_profs))
        for fwd in (pc_op, conv1_op):           # PrimaryCaps, then Conv1
            wl = fwd.workload
            # + pre-act recompute: the squash backward replays the conv
            # output (always, on a pipelined plan -- its VJP recomputes
            # pre-activation from patches regardless of n-tile alignment).
            matmuls = 3 if (fwd.fuses_squash
                            or (pipe_sched is not None
                                and fwd is pc_op)) else 2
            patches = wl.m * wl.k * ELEM_BYTES       # dpatches write + read
            # The weight transpose feeding the dpatches matmul.
            relayout = 2 * wl.k * wl.n * ELEM_BYTES
            prof = _backward_profile(fwd.profile)
            ops.append(OpPlan(
                name=fwd.name + BWD_SUFFIX, kernel="conv_im2col_bwd",
                workload=wl, block=fwd.block, block_rows=fwd.block_rows,
                # The at_b/dpatches matmuls' two bm-tall streams can
                # exceed the forward tiles' peak (measured by the static
                # auditor); the forward's own peak counts only where the
                # backward replays the forward matmul.
                vmem_bytes=max(fwd.vmem_bytes if matmuls == 3 else 0,
                               _conv_bwd_matmul_vmem(fwd.block, wl.m,
                                                     wl.k, wl.n)),
                est_cycles=matmuls * fwd.est_cycles,
                hbm_bytes=(matmuls * fwd.block.hbm_bytes + 2 * patches
                           + relayout),
                requirement=_requirement(prof), profiles=(prof,)))

    plan = ExecutionPlan(cfg=cfg, batch=batch, dataflow=dataflow,
                         vmem_budget=vmem_budget, ops=tuple(ops),
                         train=train)
    plan.validate()
    return plan


# ---------------------------------------------------------------------------
# Graceful degradation: replanning under a REDUCED VMEM budget
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DegradeReport:
    """What ``degrade_plan`` gave up to fit the reduced budget.

    ``concessions`` is human-readable, one entry per fallback rung taken
    relative to the full-budget plan: the pipelined pair dissolving to
    per-op, a layer flipping resident -> streamed, a shrunk ``block_i`` /
    ``block_k`` / conv tile, and finally a reduced batch.  Empty means
    the degraded budget still admits the exact full-budget schedule.
    """

    vmem_budget: int
    requested_batch: int
    batch: int
    concessions: tuple[str, ...]

    @property
    def degraded(self) -> bool:
        return bool(self.concessions)


def _feasible_batch(cfg: CapsNetConfig, vmem_budget: int,
                    train: bool) -> int:
    """Largest batch the fused-schedule footprint models admit under
    ``vmem_budget`` (the binding constraint in practice; the conv ops'
    tiles shrink independently).  Train plans also bound by the backward
    footprint -- it is larger, so it usually decides."""
    best = None
    for lay in cfg.routing_stack():
        b = _fused_max_batch(lay.in_caps, lay.in_dim, lay.jd, lay.num_caps,
                             vmem_budget, lay.residual)
        if train:
            b = min(b, _fused_bwd_max_batch(lay.in_caps, lay.in_dim, lay.jd,
                                            lay.num_caps, lay.iters,
                                            vmem_budget))
        best = b if best is None else min(best, b)
    return best or 0


def _plan_concessions(baseline: ExecutionPlan,
                      plan: ExecutionPlan) -> tuple[str, ...]:
    """Human-readable diff of what ``plan`` gave up vs ``baseline``."""
    notes: list[str] = []
    if plan.batch < baseline.batch:
        notes.append(f"batch {baseline.batch} -> {plan.batch}")
    base_names = {op.name for op in baseline.ops}
    plan_names = {op.name for op in plan.ops}
    if PIPE_NAME in base_names and PIPE_NAME not in plan_names:
        notes.append(f"pipelined {PIPE_NAME} pair -> per-op "
                     f"(inter-layer u round-trips HBM again)")
    base_ops = {op.name: op for op in baseline.ops}
    for op in plan.ops:
        base = base_ops.get(op.name)
        if base is None:
            continue
        if base.lanes != op.lanes and op.lanes is not None:
            notes.append(f"{op.name}: lanes {base.lanes} -> {op.lanes}")
        if base.mode != op.mode and op.mode is not None:
            notes.append(f"{op.name}: {base.mode} -> {op.mode}")
        if (base.block_i is not None and op.block_i is not None
                and base.lanes == op.lanes and op.block_i < base.block_i):
            notes.append(f"{op.name}: block_i {base.block_i} "
                         f"-> {op.block_i}")
        if (base.block_k is not None and op.block_k is not None
                and op.block_k < base.block_k):
            notes.append(f"{op.name}: block_k {base.block_k} "
                         f"-> {op.block_k}")
        if (base.block is not None and op.block is not None
                and (op.block.block_m, op.block.block_k, op.block.block_n)
                != (base.block.block_m, base.block.block_k,
                    base.block.block_n)):
            notes.append(
                f"{op.name}: conv tiles "
                f"({base.block.block_m},{base.block.block_k},"
                f"{base.block.block_n}) -> ({op.block.block_m},"
                f"{op.block.block_k},{op.block.block_n})")
    return tuple(notes)


def degrade_plan(cfg: CapsNetConfig = CapsNetConfig(),
                 vmem_budget: int = VMEM_BYTES, *, batch: int = 1,
                 train: bool = False, pipeline: bool = False,
                 min_batch: int = 1
                 ) -> tuple[ExecutionPlan, DegradeReport]:
    """Replan ``cfg`` under a (possibly reduced) ``vmem_budget``,
    reporting what was given up relative to the full-budget plan.

    This is the runtime's graceful-degradation chain -- the DESCNet-style
    degraded-scratchpad operating points taken online.  ``compile_plan``
    already embodies most of the ladder (pipelined pair -> per-op pair,
    resident -> streamed, shrinking ``block_i``/``block_k``/conv tiles),
    so the walk here is: recompile at the reduced budget, and when even
    the smallest streamed i-tile cannot fit the batch, drop to the largest
    feasible batch (``_fused_max_batch`` bound, halving as a safety net
    when a non-routing constraint binds instead) down to ``min_batch``.

    At the FULL budget the returned plan is bit-identical to
    ``compile_plan(cfg, batch=batch, ...)`` -- the memoized plan object
    itself -- and the report carries zero concessions: with no fault
    there is no behavior change.  Raises ``PlanError`` when no batch
    ``>= min_batch`` fits (callers with a fixed slot batch pass
    ``min_batch=slots`` and treat the raise as "fall back to the
    reference backend").
    """
    if min_batch < 1 or min_batch > batch:
        raise PlanError(f"min_batch must be in [1, batch={batch}], "
                        f"got {min_batch}")
    baseline = compile_plan(cfg, batch=batch, train=train,
                            pipeline=pipeline)
    b, last_err = batch, None
    while b >= min_batch:
        try:
            plan = compile_plan(cfg, batch=b, vmem_budget=vmem_budget,
                                train=train, pipeline=pipeline)
            return plan, DegradeReport(
                vmem_budget=vmem_budget, requested_batch=batch, batch=b,
                concessions=_plan_concessions(baseline, plan))
        except ValueError as err:        # PlanError, or the conv planner's
            last_err = err               # bare no-block-fits ValueError
            feas = _feasible_batch(cfg, vmem_budget, train)
            # Jump straight to the model's feasible batch when it is the
            # binding constraint; halve as the safety net when it is not
            # (a conv tiling bound, say).  Always strictly decrease.
            nxt = max(min(feas, b - 1), b // 2)
            b = nxt if nxt < b else b - 1
    raise PlanError(
        f"degrade_plan: no feasible plan for batch >= {min_batch} under "
        f"the degraded {vmem_budget} B VMEM budget "
        f"(requested batch {batch}): {last_err}")


def plan_table(plans: Sequence[tuple[str, ExecutionPlan]]) -> list[dict]:
    """Flat summary rows for benchmarks/examples."""
    rows = []
    for tag, plan in plans:
        for r in plan.summary():
            rows.append(dict(plan=tag, **r))
    return rows
