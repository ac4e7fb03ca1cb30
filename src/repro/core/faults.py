"""Deterministic, seeded fault injection for chaos-testing the runtime.

The paper's energy story power-gates on-chip memory sectors per
operation, so a production engine must keep serving when the machine
degrades underneath it: VMEM budgets shrink (sectors gated off,
co-tenancy, a conservative PMU policy), kernels emit non-finite outputs,
plans stop compiling, slots corrupt, ticks stall.  This module is the
ONE switchboard those failures are injected through, so the chaos tests
(`tests/test_faults.py`) drive the real recovery paths in
``serve/capsule.py`` (retry/quarantine/circuit-breaker/degraded
replanning), ``train/harness.py`` (NaN rollback, straggler, preemption),
and ``kernels/ops.py`` (poisoned kernel outputs).

Design rules:

* **Zero overhead when disabled.**  Every site guards with
  ``if faults.enabled():`` -- a module-global ``is None`` check -- so
  production code pays one attribute load per site when no injection is
  active, and the fast path allocates nothing.
* **Deterministic.**  A ``FaultSpec`` fires on an index *window*
  (``at <= index < at + times``) against an explicit site index (the
  engine's tick, the training step) or the site's own poll counter --
  never wall clock, never un-seeded randomness.  Choices that need
  randomness (which slot to corrupt) derive from ``spec.seed`` and the
  firing index, so a chaos run replays bit-identically.
* **Scoped.**  ``inject(*specs)`` is a context manager; the registry is
  installed for the ``with`` body and ALWAYS torn down, so a failing
  chaos test cannot leak faults into the rest of the suite.  Nesting is
  refused -- overlapping registries would make ``fired`` logs ambiguous.

Sites currently wired (the string is the ``FaultSpec.site`` key):

=======================  ==================================================
``ops.votes_routing``    fused megakernel wrapper output (eager calls)
``ops.primary_routing``  pipelined pair wrapper output (eager calls)
``ops.conv2d``           conv wrapper output (eager calls)
``ops.res_caps_segment`` reversible segment wrapper output (eager calls)
``ops.squash``           squash wrapper output (eager calls)
``ops.rmsnorm``          rmsnorm wrapper output (eager calls)
``ops.flash_attention``  flash-attention wrapper output (eager calls)
``engine.tick``          ``CapsuleEngine`` tick boundary (index = tick)
``engine.forward``       the engine's forward dispatch (index = tick)
``train.step``           ``FaultTolerantLoop`` step boundary (index = step)
=======================  ==================================================

Every public eager kernel wrapper in ``kernels/ops.py`` carries a site:
``repro.verify.lint`` fails the build on a wrapper the chaos suite
cannot reach.

Kinds: ``nan_output`` / ``inf_output`` (poison an output), ``vmem_shrink``
(scale the VMEM budget by ``factor``; the engine replans degraded),
``plan_error`` (raise ``PlanError`` at the site), ``slot_corrupt``
(NaN-fill one seeded active slot's device row), ``stall`` (a tick/step
makes no progress; ``seconds`` inflates the step's measured duration so
straggler detection fires deterministically).

NOTE: the ``ops.*`` sites poison at Python call time.  Inside ``jax.jit``
that means trace time -- the poison would be baked into the compiled
executable -- so chaos tests drive the ops sites eagerly and drive jitted
paths (the engine) through the ``engine.*`` sites instead.
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import defaultdict
from typing import Iterator

KINDS = ("nan_output", "inf_output", "vmem_shrink", "plan_error",
         "slot_corrupt", "stall")

SITE_VOTES_ROUTING = "ops.votes_routing"
SITE_PRIMARY_ROUTING = "ops.primary_routing"
SITE_CONV2D = "ops.conv2d"
SITE_RES_CAPS_SEGMENT = "ops.res_caps_segment"
SITE_SQUASH = "ops.squash"
SITE_RMSNORM = "ops.rmsnorm"
SITE_FLASH_ATTENTION = "ops.flash_attention"
SITE_ENGINE_TICK = "engine.tick"
SITE_ENGINE_FORWARD = "engine.forward"
SITE_TRAIN_STEP = "train.step"


class InjectionError(RuntimeError):
    """Misuse of the fault-injection machinery itself (nested ``inject``,
    unknown kind) -- never raised by a *fired* fault."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One deterministic fault: fire ``kind`` at ``site`` for the index
    window ``[at, at + times)``.

    ``at`` / ``times`` index whatever the site polls with -- the engine's
    tick, the training loop's step, or the site's own call counter (for
    the ``ops.*`` kernel-wrapper sites).  ``times=0`` never fires (a
    convenient way to parameterize a storm down to nothing).  ``factor``
    scales the original VMEM budget for ``vmem_shrink``; ``seconds`` is
    the virtual duration a ``stall`` adds to a training step; ``seed``
    drives any random choice the firing makes (e.g. which active slot
    ``slot_corrupt`` poisons).
    """

    site: str
    kind: str
    at: int = 0
    times: int = 1
    factor: float = 0.5
    seconds: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise InjectionError(
                f"unknown fault kind {self.kind!r} (kinds: {KINDS})")
        if self.times < 0:
            raise InjectionError(f"times must be >= 0, got {self.times}")
        if self.kind == "vmem_shrink" and not 0.0 < self.factor <= 1.0:
            raise InjectionError(
                f"vmem_shrink factor must be in (0, 1], got {self.factor}")

    def fires_at(self, index: int) -> bool:
        return self.times > 0 and self.at <= index < self.at + self.times


class FaultRegistry:
    """The active fault set plus a log of every firing.

    ``poll(site)`` is the one read path: returns the specs firing at the
    given index (or the site's own monotonically-advancing poll counter
    when no index is passed) and records each firing in ``fired`` as
    ``(site, kind, index)`` so tests can assert exactly what was
    injected where.
    """

    def __init__(self, specs: tuple[FaultSpec, ...]):
        self.specs = tuple(specs)
        self.fired: list[tuple[str, str, int]] = []
        self._counters: defaultdict[str, int] = defaultdict(int)

    def poll(self, site: str, *, index: int | None = None,
             kinds: tuple[str, ...] | None = None) -> tuple[FaultSpec, ...]:
        if index is None:
            index = self._counters[site]
            self._counters[site] += 1
        hits = tuple(s for s in self.specs
                     if s.site == site and s.fires_at(index)
                     and (kinds is None or s.kind in kinds))
        self.fired.extend((site, s.kind, index) for s in hits)
        return hits

    def count(self, site: str | None = None,
              kind: str | None = None) -> int:
        """Number of recorded firings, optionally filtered."""
        return sum(1 for (s, k, _) in self.fired
                   if (site is None or s == site)
                   and (kind is None or k == kind))


_ACTIVE: FaultRegistry | None = None


def enabled() -> bool:
    """True iff an ``inject`` context is active (the sites' fast-path
    guard: one global load, nothing else)."""
    return _ACTIVE is not None


def registry() -> FaultRegistry | None:
    return _ACTIVE


@contextlib.contextmanager
def inject(*specs: FaultSpec) -> Iterator[FaultRegistry]:
    """Activate ``specs`` for the ``with`` body; always tears down."""
    global _ACTIVE
    if _ACTIVE is not None:
        raise InjectionError(
            "fault injection is already active; nested inject() would make "
            "the fired log ambiguous -- compose specs into one registry")
    reg = FaultRegistry(specs)
    _ACTIVE = reg
    try:
        yield reg
    finally:
        _ACTIVE = None


def poll(site: str, *, index: int | None = None,
         kinds: tuple[str, ...] | None = None) -> tuple[FaultSpec, ...]:
    """Site-level poll: () when injection is disabled."""
    reg = _ACTIVE
    if reg is None:
        return ()
    return reg.poll(site, index=index, kinds=kinds)


def corrupt_array(site: str, x):
    """Kernel-wrapper site: return ``x`` poisoned (all-NaN / all-Inf) when
    a matching output fault fires, raise ``PlanError`` on ``plan_error``,
    and return ``x`` UNTOUCHED (the same object) otherwise.  Advances the
    site's poll counter once per call."""
    reg = _ACTIVE
    if reg is None:
        return x
    hits = reg.poll(site, kinds=("nan_output", "inf_output", "plan_error"))
    for spec in hits:
        if spec.kind == "plan_error":
            from repro.core.execplan import PlanError
            raise PlanError(f"injected plan_error at {site}")
    for spec in hits:
        import jax.numpy as jnp
        fill = jnp.nan if spec.kind == "nan_output" else jnp.inf
        return jnp.full_like(x, fill)
    return x
