"""Golden concession-ordering tests for ``degrade_plan``.

For every registered CapsNet arch we pin the EXACT concession sequence
at a ladder of reduced VMEM budgets.  The ordering is part of the
serving contract: batch reduction is always reported first, then the
pipelined pair dissolving, then per-op mode/tile concessions in plan
order -- a degraded replica's log line must stay stable and readable
across planner refactors.  Budgets are fractions of the full
``VMEM_BYTES`` (16 MiB), matching the ``python -m repro.verify``
degrade ladder.
"""

import pytest

from repro.configs.registry import CAPSNET_ARCHS, get_config
from repro.core.execplan import (PlanError, VMEM_BYTES, compile_plan,
                                 degrade_plan)

# (arch, requested batch, budget fraction) -> exact concession tuple, or
# None where no batch fits: capsnet-cifar10's ResCaps halves need 9.7 MB
# at batch 1 (a 4 MiB logits slab and two 2 MiB 8-capsule W tiles), over
# half the budget.
GOLDEN = {
    ("capsnet-mnist", 4, 1.0): (),
    ("capsnet-mnist", 4, 0.5): (),
    ("capsnet-mnist", 4, 0.25): (
        "PrimaryCaps-Routing: resident -> streamed",
        "PrimaryCaps-Routing: block_k 256 -> 128",
    ),
    ("capsnet-mnist", 4, 0.125): (
        "batch 4 -> 1",
        "pipelined PrimaryCaps-Routing pair -> per-op (inter-layer u "
        "round-trips HBM again)",
        "Conv1: conv tiles (1024,128,256) -> (256,128,256)",
    ),
    ("capsnet-cifar10", 2, 1.0): (),
    ("capsnet-cifar10", 2, 0.625): (
        "batch 2 -> 1",
        "PrimaryCaps: conv tiles (128,256,256) -> (64,256,256)",
        "ClassCaps-Routing: block_i 2048 -> 512",
    ),
    ("capsnet-cifar10", 2, 0.5): None,
    ("capsnet-svhn", 4, 1.0): (),
    ("capsnet-svhn", 4, 0.5): (
        "pipelined PrimaryCaps-Routing pair -> per-op (inter-layer u "
        "round-trips HBM again)",
    ),
    ("capsnet-svhn", 4, 0.25): (
        "batch 4 -> 3",
        "pipelined PrimaryCaps-Routing pair -> per-op (inter-layer u "
        "round-trips HBM again)",
        "Conv1: conv tiles (512,256,256) -> (1024,256,256)",
    ),
    ("capsnet-svhn", 4, 0.125): (
        "batch 4 -> 1",
        "pipelined PrimaryCaps-Routing pair -> per-op (inter-layer u "
        "round-trips HBM again)",
        "Conv1: conv tiles (512,256,256) -> (256,256,256)",
    ),
}


@pytest.mark.parametrize(("arch", "batch", "frac"), sorted(GOLDEN),
                         ids=lambda v: str(v))
def test_concession_sequence_golden(arch, batch, frac):
    cfg = get_config(arch)
    if GOLDEN[(arch, batch, frac)] is None:
        with pytest.raises(PlanError, match="batch >= 1"):
            degrade_plan(cfg, int(VMEM_BYTES * frac), batch=batch,
                         pipeline=True)
        return
    plan, rep = degrade_plan(cfg, int(VMEM_BYTES * frac), batch=batch,
                             pipeline=True)
    assert rep.concessions == GOLDEN[(arch, batch, frac)]
    assert rep.requested_batch == batch
    assert rep.degraded == bool(rep.concessions)
    # The returned plan honors whatever batch the report claims.
    assert plan.batch == rep.batch


@pytest.mark.parametrize("arch", CAPSNET_ARCHS)
def test_full_budget_is_concession_free_and_memoized(arch):
    batch = 2 if arch == "capsnet-cifar10" else 4
    cfg = get_config(arch)
    plan, rep = degrade_plan(cfg, VMEM_BYTES, batch=batch, pipeline=True)
    assert rep.concessions == ()
    # Bit-identical to the full-budget plan: a no-fault replica has zero
    # behavior change.
    assert plan == compile_plan(cfg, batch=batch, pipeline=True)


def test_batch_concession_is_reported_first():
    # Whenever batch is conceded it must lead the sequence -- operators
    # grep degradation logs for the throughput hit first.
    for (arch, batch, frac), gold in GOLDEN.items():
        if gold is None:
            continue
        batch_notes = [c for c in gold if c.startswith("batch ")]
        if batch_notes:
            assert gold[0] == batch_notes[0], (arch, frac)
            assert len(batch_notes) == 1


def test_exhausted_ladder_raises_named_planerror():
    with pytest.raises(PlanError, match="batch >= 1"):
        degrade_plan(get_config("capsnet-cifar10"), VMEM_BYTES // 4,
                     batch=2, pipeline=True)
