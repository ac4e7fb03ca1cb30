"""CapStore planner: the TPU adaptation of the paper's DSE (DESIGN.md Sec. 2).

The ASIC paper sizes three on-chip memories (data / weight / accumulator)
to per-operation working sets and gates unused sectors.  On TPU the same
decision is *which Pallas block shape to use*: a kernel's VMEM footprint is

    data tile   : block_m x block_k          (input operand)
    weight tile : block_k x block_n          (stationary operand)
    accum tile  : block_m x block_n @ fp32   (partial sums)

and its HBM traffic (the off-chip accesses of the paper) follows from how
often each operand is re-streamed.  This module runs the paper's
energy-objective DSE over block shapes:

    E = e_hbm * HBM_bytes + e_vmem * VMEM_accesses
        + leak * VMEM_resident_bytes * est_cycles

subject to the footprint fitting the VMEM budget and MXU alignment
(multiples of 128 lanes / 8 sublanes).  ``kernels/ops.py`` uses it to pick
default BlockSpecs; `benchmarks/bench_planner.py` reports the explored
space.  The *unallocated* VMEM is the TPU analogue of a gated-OFF sector.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

# TPU v5e-ish constants (per core).
VMEM_BYTES = 128 * 1024 * 1024 // 8          # 16 MiB VMEM plan budget
LANES = 128
SUBLANES = 8
MXU = 128
# Scoped-VMEM limit every kernel hands Mosaic: the plan budget plus as
# much again for the compiler's own internal scratch (spilled in-register
# temporaries), which no plan models.  v5e has 128 MiB of VMEM per core;
# its default scoped limit (16 MiB) equals the budget and leaves none.
VMEM_LIMIT_BYTES = 2 * VMEM_BYTES


def tile_padded(shape: Sequence[int]) -> int:
    """Elements a VMEM buffer of ``shape`` occupies under the TPU's
    (8, 128) tiling of its two minor dims (32-bit elements): the second
    minor dim pads to a multiple of 8 sublanes, the minor dim to a
    multiple of 128 lanes, leading dims multiply."""
    if not shape:
        return 1
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    return (math.prod(lead) * (-(-sub // SUBLANES) * SUBLANES)
            * (-(-lane // LANES) * LANES))

# Relative energy weights (pJ/byte-ish; only ratios matter for the argmin).
E_HBM = 1.0
E_VMEM = 0.02
E_LEAK = 1e-9      # per resident byte-cycle


@dataclasses.dataclass(frozen=True)
class MatmulWorkload:
    """[M, K] x [K, N] with element sizes in bytes."""

    m: int
    k: int
    n: int
    in_bytes: int = 2        # bf16
    acc_bytes: int = 4       # fp32 accumulation

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    block_m: int
    block_k: int
    block_n: int
    vmem_data: int           # bytes: input tile (the paper's data memory)
    vmem_weight: int         # bytes: stationary tile (weight memory)
    vmem_accum: int          # bytes: partials (accumulator memory)
    hbm_bytes: float
    vmem_accesses: float
    energy: float
    est_cycles: float

    @property
    def vmem_total(self) -> int:
        return self.vmem_data + self.vmem_weight + self.vmem_accum

    @property
    def gated_fraction(self) -> float:
        """VMEM left unallocated -- the power-gated-sector analogue."""
        return 1.0 - self.vmem_total / VMEM_BYTES


def _round_up(x: int, to: int) -> int:
    return max(to, math.ceil(x / to) * to)


def _candidates(dim: int, align: int, cap: int = 4096) -> list[int]:
    out = []
    b = align
    while b <= min(_round_up(dim, align), cap):
        out.append(b)
        b *= 2
    return out or [align]


def plan_matmul(w: MatmulWorkload,
                vmem_budget: int = VMEM_BYTES,
                double_buffer: bool = True) -> BlockPlan:
    """Paper-style DSE over block shapes; returns the energy-argmin plan."""
    best: BlockPlan | None = None
    buf = 2 if double_buffer else 1
    for bm in _candidates(w.m, SUBLANES):
        for bk in _candidates(w.k, LANES):
            for bn in _candidates(w.n, LANES):
                tiles_m = math.ceil(w.m / bm)
                tiles_k = math.ceil(w.k / bk)
                tiles_n = math.ceil(w.n / bn)
                data = bm * bk * w.in_bytes * buf
                weight = bk * bn * w.in_bytes * buf
                accum = bm * bn * w.acc_bytes
                total = data + weight + accum
                if total > vmem_budget:
                    continue
                # HBM traffic: LHS streamed once per N-tile column, RHS once
                # per M-tile row, output written once (fp32->bf16 on store).
                # PADDED dims: the lowering zero-pads every operand to the
                # tile grid, and padded rows cross HBM like real ones -- an
                # unpadded model let the DSE pick e.g. block_m=512 over
                # M=576 (1024 padded rows, 78% phantom LHS traffic), drift
                # the static auditor (repro.verify.lowering) flagged.
                # The kernels clamp each block to its axis before padding
                # (bm = min(block_m, m)), so a candidate larger than the
                # whole axis pads to the axis itself, not the candidate.
                m_pad = tiles_m * min(bm, w.m)
                k_pad = tiles_k * min(bk, w.k)
                n_pad = tiles_n * min(bn, w.n)
                hbm = (m_pad * k_pad * w.in_bytes * tiles_n
                       + k_pad * n_pad * w.in_bytes * tiles_m
                       + m_pad * n_pad * w.in_bytes)
                vmem_acc = 2.0 * w.m * w.k * tiles_n + w.m * w.n * tiles_k
                cycles = w.flops / (2 * MXU * MXU)   # MXU-bound estimate
                e = (E_HBM * hbm + E_VMEM * vmem_acc
                     + E_LEAK * total * cycles)
                plan = BlockPlan(bm, bk, bn, data, weight, accum,
                                 hbm, vmem_acc, e, cycles)
                if best is None or plan.energy < best.energy:
                    best = plan
    if best is None:
        raise ValueError(f"no block plan fits VMEM budget for {w}")
    return best


def arithmetic_intensity(plan: BlockPlan, w: MatmulWorkload) -> float:
    return w.flops / max(plan.hbm_bytes, 1.0)


def plan_table(workloads: Sequence[tuple[str, MatmulWorkload]]) -> list[dict]:
    rows = []
    for name, w in workloads:
        p = plan_matmul(w)
        rows.append(dict(
            name=name, m=w.m, k=w.k, n=w.n,
            block=(p.block_m, p.block_k, p.block_n),
            vmem_kib=p.vmem_total / 1024,
            gated_frac=round(p.gated_fraction, 4),
            hbm_mib=p.hbm_bytes / 2**20,
            intensity=round(arithmetic_intensity(p, w), 2),
        ))
    return rows


# Workloads the paper profiles, as TPU matmuls (see analysis.py).
CAPSNET_WORKLOADS: list[tuple[str, MatmulWorkload]] = [
    ("Conv1(im2col)", MatmulWorkload(m=400, k=81, n=256)),
    ("PrimaryCaps(im2col)", MatmulWorkload(m=36, k=20736, n=256)),
    ("ClassCaps-votes", MatmulWorkload(m=1152, k=8, n=160)),
    ("Routing-SumSquash", MatmulWorkload(m=160, k=1152, n=1)),
]
