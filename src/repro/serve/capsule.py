"""Slot-based batched CapsuleNet inference engine.

Mirrors ``serve/engine.py``'s admission/refill loop for the paper's own
(non-autoregressive) workload: a fixed number of batch slots share ONE
jit-compiled, plan-driven forward pass.  New requests fill free slots from
the queue each tick; every tick runs the whole batch through the compiled
forward once, so the ExecutionPlan (block shapes, VMEM schedule) is
compiled once and amortized across the request stream.  Inactive slots
carry zero images -- the capsule head is per-sample, so padding never
perturbs active requests.

On the pallas backend the engine compiles the FUSED plan: every routing
layer of the config's graph (the single ClassCaps head, or a deep
ResCaps stack's per-layer instances) is one ``votes_routing`` megakernel
(resident or streamed schedule per the plan's VMEM decision), so no slot
tick ever round-trips a votes tensor through HBM.  The engine is
graph-agnostic -- it serves whatever stack ``compile_plan`` scheduled
for the config.  A caller-supplied plan must be compiled for
``batch >= slots_per_shard``: the jitted forward always runs all slot
rows (of its shard), so a smaller plan batch would blow the plan's
validated VMEM footprint (or raise the opaque kernel-level batch error
on the first tick) -- ``__init__`` rejects it up front, naming both
numbers.

**Sharded serving.**  ``n_shards=k`` lays the slot batch out over a
k-device mesh (``slots = n_shards * slots_per_shard``, slot ``s`` lives
on shard ``s // slots_per_shard``) and runs the SAME jitted forward
under ``parallel/compat.shard_map`` with the specs from
``parallel/sharding.py`` (params replicated, batch row-sharded).  ONE
``compile_plan`` call produces the per-shard plan
(``plan.batch == slots_per_shard``), so the resident / streamed /
pipelined machinery is untouched inside a shard, and the body still
traces exactly once -- the single-trace invariant holds across shard
counts, and degrade/breaker swaps re-trace ONCE across the whole mesh.
The capsule head is per-sample (no cross-batch reductions), so sharded
outputs are bit-identical to the single-device engine's.

Host<->device traffic is tick-size, not batch-size: the slot batch lives
ON DEVICE and only slots dirtied since the last tick (new admissions,
freed slots zeroing out) are uploaded (dirty set padded to the next
power of two so the scatter compiles O(log slots) times, not once per
occupancy); classification finishes on device and the active slots' rows
are gathered INSIDE the jit through a fixed-size padded index, so the
forward traces exactly once no matter how occupancy varies tick to tick
(the old eager ``jnp.take`` compiled a fresh gather per distinct
occupancy count).

**Graceful degradation.**  The engine is hardened against the failure
modes the chaos suite (``tests/test_faults.py``, ``core/faults.py``)
injects; with injection disabled none of these paths add a trace or
change a result:

* Every request reaches exactly ONE terminal ``status``: ``ok`` /
  ``timeout`` (its ``deadline_s`` expired in queue or in a slot) /
  ``error`` (non-finite output survived ``max_retries``) / ``shed``
  (bounded-queue admission or an unservable drain).  ``stats()``
  counters satisfy ``ok + timeout + error + shed == submitted``.
* **Bounded queue**: ``max_queue`` caps the backlog; ``admission``
  picks who pays -- ``"reject"`` sheds the NEW request, ``"shed-oldest"``
  sheds the head of the queue.  Shedding is a terminal status, never a
  raise: the caller reads it off the request.
* **Non-finite guard**: a slot row whose lengths come back NaN/Inf is
  retried with per-retry tick backoff (the clean host-side image is
  re-uploaded, healing device-side corruption); a request whose
  ``deadline_s`` has already expired is terminated ``timeout`` instead
  of being re-dispatched.  After ``max_retries`` the request errors
  out, and ``quarantine_after`` consecutive poisoned results quarantine
  the SLOT -- a storm cannot grind the engine through one bad lane
  forever.  Quarantine is PROBATIONARY, not permanent:
  ``probation_ticks`` consecutive clean ticks (or a breaker trip /
  degrade-replan swap, both of which change the serving path) lift it,
  so capacity returns once a transient fault window closes.  When every
  slot is quarantined the remaining queue is shed rather than hung.
* **Circuit breaker**: ``breaker_after`` consecutive forward-dispatch
  exceptions re-trace the forward on the jnp reference backend and keep
  serving with ``degraded=True`` -- one failing Pallas lowering does not
  take the service down.
* **Degraded-VMEM replanning**: a ``vmem_shrink(factor)`` fault (sector
  power-gating, co-tenancy) makes the engine call
  ``execplan.degrade_plan`` at the next tick boundary and swap in the
  reduced-budget plan -- ONE new trace, the device slot batch preserved
  -- walking compile_plan's own fallback ladder (pipelined pair ->
  per-op, resident -> streamed, shrunk tiles); if not even a degraded
  plan fits the slot batch, the breaker path serves on the reference
  backend instead.
* **Stall detection**: ``run(max_ticks=...)`` bounds the host loop, and
  ``stall_ticks`` consecutive ticks without a single terminal event
  while work is pending raise ``EngineStalled`` instead of spinning
  forever.

Per-request latency (submit -> classified) and engine throughput
(requests/s) are reported by ``stats()``; tests validate slot-batched
outputs against the direct single-request forward.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import capsnet, execplan, faults, spans
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import ExecutionPlan, PlanError, compile_plan
from repro.core.planner import VMEM_BYTES
from repro.parallel import compat
from repro.parallel.sharding import slot_batch_spec, slot_mesh, slot_param_spec

TERMINAL_STATUSES = ("ok", "timeout", "error", "shed")


class EngineStalled(RuntimeError):
    """``CapsuleEngine.run`` detected zero progress (or exhausted
    ``max_ticks``) with work still pending -- raised instead of hanging
    the host loop."""


@dataclasses.dataclass
class CapsRequest:
    rid: int
    image: np.ndarray                  # [H, W, C] float in [0, 1]
    deadline_s: float | None = None    # submit-relative expiry (None: never)
    submitted_s: float | None = None
    finished_s: float | None = None
    admitted_s: float | None = None    # when it left the queue for a slot
    retries: int = 0                   # non-finite-output retries consumed
    status: str = "pending"            # -> ok | timeout | error | shed
    lengths: np.ndarray | None = None  # [num_classes] capsule lengths
    pred: int | None = None

    @property
    def latency_s(self) -> float | None:
        if self.submitted_s is None or self.finished_s is None:
            return None
        return self.finished_s - self.submitted_s


class CapsuleEngine:
    """Continuous-batching CapsNet classifier over a request queue."""

    def __init__(self, params, cfg: CapsNetConfig = CapsNetConfig(), *,
                 slots: int = 8, backend: str = "jnp",
                 interpret: bool | None = None,
                 plan: ExecutionPlan | None = None,
                 n_shards: int | None = None,
                 max_queue: int | None = None, admission: str = "reject",
                 max_retries: int = 2, retry_backoff_ticks: int = 1,
                 quarantine_after: int = 3, breaker_after: int = 3,
                 probation_ticks: int | None = 8, stall_ticks: int = 32):
        if admission not in ("reject", "shed-oldest"):
            raise ValueError(f"unknown admission policy {admission!r} "
                             f"(choices: 'reject', 'shed-oldest')")
        self.params = params
        self.cfg = cfg
        self.slots = slots
        # n_shards=None is the legacy single-device layout (no mesh in
        # play at all); n_shards=k >= 1 shards the slot batch row-wise
        # over the first k local devices (k=1 exercises the mesh path on
        # a single device, so parity is testable without a real mesh).
        self.n_shards = n_shards if n_shards is not None else 1
        if slots % self.n_shards:
            raise ValueError(
                f"slots={slots} does not divide over n_shards="
                f"{self.n_shards}: the slot batch is laid out "
                f"[n_shards, slots_per_shard, ...]")
        self.mesh = slot_mesh(n_shards) if n_shards is not None else None
        self.slots_per_shard = slots // self.n_shards
        if plan is None and backend == "pallas":
            # ONE compile_plan produces the per-shard plan: under
            # shard_map each shard's forward sees slots_per_shard rows,
            # so the PIPELINED plan (Conv1 -> one primary_routing
            # megakernel when the combined footprint fits, per-op
            # fallback otherwise) is compiled for that local batch and
            # replicated across the mesh unchanged.
            plan = compile_plan(cfg, batch=self.slots_per_shard,
                                pipeline=True)
        elif plan is not None and plan.batch < self.slots_per_shard:
            # The jitted forward runs ALL slot rows every tick; a plan
            # compiled for fewer would either raise the kernel-level
            # votes_routing batch error on the first step() or (jnp path)
            # silently exceed the VMEM footprint the plan validated.
            shard_note = (
                f" per shard (slots = n_shards * plan.batch: {slots} slots "
                f"over {self.n_shards} shards)" if self.mesh is not None
                else "")
            raise PlanError(
                f"plan compiled for batch {plan.batch} cannot serve "
                f"{self.slots_per_shard} slots{shard_note}: every tick runs "
                f"the full {self.slots_per_shard}-row slot batch; compile "
                f"the plan with batch >= slots")
        self.plan = plan          # None on the jnp path unless caller-supplied
        self.max_queue = max_queue
        self.admission = admission
        self.max_retries = max_retries
        self.retry_backoff_ticks = retry_backoff_ticks
        self.quarantine_after = quarantine_after
        self.breaker_after = breaker_after
        self.probation_ticks = probation_ticks
        self.stall_ticks = stall_ticks
        self.degraded = False            # breaker tripped or plan degraded
        self.degrade_report = None       # execplan.DegradeReport after replan
        self.quarantined: set[int] = set()
        self.active: list[CapsRequest | None] = [None] * slots
        self.queue: deque[CapsRequest] = deque()
        self.finished: list[CapsRequest] = []
        self.ticks = 0
        self._backend = backend
        self._interpret = interpret
        self._occupancy = 0
        self._now = time.perf_counter    # injectable clock (deadline tests)
        self._started_s: float | None = None
        self._stopped_s: float | None = None
        self._vmem_budget = (plan.vmem_budget if plan is not None
                             else VMEM_BYTES)
        self._orig_budget = self._vmem_budget
        self._counters = {s: 0 for s in TERMINAL_STATUSES}
        self._counters.update(submitted=0, retries=0, replans=0,
                              breaker_trips=0, forward_failures=0,
                              poisoned=0, unquarantined=0)
        # Terminal events attributed per shard (slot-resident terminals)
        # plus a "queue" bucket for requests that never reached a slot;
        # stats() asserts their sum equals the aggregate counters.
        self._shard_counters = [{s: 0 for s in TERMINAL_STATUSES}
                                for _ in range(self.n_shards)]
        self._queue_counters = {s: 0 for s in TERMINAL_STATUSES}
        self._poison_streak = [0] * slots   # consecutive bad results / slot
        self._backoff_until = [0] * slots   # tick a retrying slot resumes at
        self._breaker_fails = 0             # consecutive dispatch exceptions
        self._clean_streak = 0              # ticks since the last poison
        self._stall_pending = False         # injected stall: skip one tick
        self._batch = np.zeros(
            (slots, cfg.image_hw, cfg.image_hw, cfg.in_channels), np.float32)
        self._batch_dev = jnp.asarray(self._batch)   # device-resident slots
        if self.mesh is not None:
            self._batch_dev = jax.device_put(
                self._batch_dev,
                NamedSharding(self.mesh, slot_batch_spec()))
        self._dirty: set[int] = set()                # slots to re-upload
        self._forward_traces = 0                     # (re)compilations seen
        self._forward = self._make_forward(backend, plan)
        self._scatter = jax.jit(lambda b, i, x: b.at[i].set(x))

    def _make_forward(self, backend: str, plan: ExecutionPlan | None):
        """One jitted forward over the full slot batch.  Rebuilt (ONE new
        trace) only when the engine degrades: a vmem_shrink replan swaps
        in the reduced-budget plan, a tripped breaker swaps in the jnp
        reference backend.  Under a mesh the body runs per shard through
        ``compat.shard_map`` (params replicated, batch and index
        row-sharded) -- still ONE trace for the whole mesh, and a
        degrade/breaker rebuild is likewise ONE re-trace mesh-wide."""
        def body(p, images, idx):
            out = capsnet.forward(p, images, self.cfg, backend=backend,
                                  plan=plan, interpret=self._interpret)
            # Gather the active slots ON DEVICE through the fixed-size
            # padded index and classify there: one trace for any
            # occupancy, and only slot-count-many result rows ever cross.
            # Under shard_map the index is shard-local ([slots_per_shard]
            # values in [0, slots_per_shard)), so the gather never
            # crosses shards.
            lengths = jnp.take(out["lengths"], idx, axis=0)
            return lengths, jnp.argmax(lengths, axis=-1)

        if self.mesh is not None:
            batch_spec = slot_batch_spec()
            body = compat.shard_map(
                body, mesh=self.mesh,
                in_specs=(slot_param_spec(), batch_spec, batch_spec),
                out_specs=(batch_spec, batch_spec))

        def fwd(p, images, idx):
            self._forward_traces += 1                # counts traces, not calls
            return body(p, images, idx)

        return jax.jit(fwd)

    # -- admission -------------------------------------------------------
    def _shard_of(self, s: int) -> int:
        return s // self.slots_per_shard

    def _finish(self, req: CapsRequest, status: str,
                shard: int | None = None) -> None:
        """Assign the terminal ``status`` and retire the request; every
        submitted request passes through here exactly once.  ``shard``
        attributes slot-resident terminals to their shard's counters;
        queue-side terminals (admission sheds, queued timeouts) land in
        the "queue" bucket, so per-shard + queue always sums to the
        aggregate."""
        req.status = status
        req.finished_s = self._now()
        self.finished.append(req)
        self._counters[status] += 1
        if shard is None:
            self._queue_counters[status] += 1
        else:
            self._shard_counters[shard][status] += 1

    def submit(self, req: CapsRequest) -> None:
        """Queue ``req``; rejects images whose layout does not match the
        engine input (a same-size [C, H, W] array would otherwise be
        silently reinterpreted as [H, W, C] garbage).  A full bounded
        queue sheds per the admission policy -- a terminal ``"shed"``
        status on the victim, never a raise."""
        img = np.asarray(req.image, np.float32)
        want = self._batch.shape[1:]
        if img.shape != want:
            raise ValueError(
                f"request {req.rid}: image shape {img.shape} does not match "
                f"the engine input shape {want} (H, W, C for "
                f"image_hw={self.cfg.image_hw}, "
                f"in_channels={self.cfg.in_channels}); refusing to reshape")
        req.image = img
        req.submitted_s = self._now()
        self._counters["submitted"] += 1
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.admission == "reject":
                self._finish(req, "shed")            # the newcomer pays
                return
            self._finish(self.queue.popleft(), "shed")   # the oldest pays
        self.queue.append(req)

    def _admit_order(self):
        """Slot fill order: shard-interleaved under a mesh, so a
        part-full queue spreads over all shards instead of saturating
        shard 0 while the rest idle.  Placement never changes a result
        (the head is per-sample), only balance."""
        if self.n_shards == 1:
            return range(self.slots)
        return (shard * self.slots_per_shard + k
                for k in range(self.slots_per_shard)
                for shard in range(self.n_shards))

    def _admit(self) -> None:
        for s in self._admit_order():
            if s in self.quarantined:
                continue
            if self.active[s] is None and self.queue:
                req = self.queue.popleft()
                req.admitted_s = self._now()
                spans.mark("caps.request.queue", req.submitted_s,
                           req.admitted_s, rid=req.rid)
                self._batch[s] = req.image        # shape-checked in submit()
                self._dirty.add(s)
                self.active[s] = req

    def _clear_slot(self, s: int) -> None:
        self.active[s] = None
        self._batch[s] = 0.0
        self._dirty.add(s)          # freed slot returns to zero images
        self._backoff_until[s] = 0

    def _upload_dirty(self) -> None:
        """Scatter only the slots dirtied since the last tick into the
        device-resident batch.  The dirty set is padded to the next power
        of two by repeating its last entry (duplicate indices write the
        same row), so the scatter compiles O(log slots) distinct shapes
        instead of one per occupancy delta."""
        dirty = sorted(self._dirty)
        self._dirty.clear()
        n = min(1 << (len(dirty) - 1).bit_length(), self.slots)
        dirty.extend(dirty[-1:] * (n - len(dirty)))
        idx = np.asarray(dirty, np.int32)
        self._batch_dev = self._scatter(self._batch_dev, jnp.asarray(idx),
                                        jnp.asarray(self._batch[idx]))

    # -- fault reactions -------------------------------------------------
    def _apply_tick_faults(self, tick: int) -> None:
        for spec in faults.poll(faults.SITE_ENGINE_TICK, index=tick):
            if spec.kind == "vmem_shrink":
                self._replan(spec.factor)
            elif spec.kind == "slot_corrupt":
                self._corrupt_slot(spec, tick)
            elif spec.kind == "stall":
                self._stall_pending = True

    def _replan(self, factor: float) -> None:
        """React to a shrunk VMEM budget at a tick boundary: swap in the
        degraded plan (ONE new trace, device slot batch preserved); fall
        back to the reference backend when not even a degraded plan fits
        the slot batch.  Idempotent across a multi-tick fault window --
        the factor applies to the ORIGINAL budget."""
        new_budget = max(int(self._orig_budget * factor), 1)
        if new_budget == self._vmem_budget:
            return
        self._vmem_budget = new_budget
        if self._backend != "pallas":
            return                       # the jnp path plans nothing
        try:
            plan, report = execplan.degrade_plan(
                self.cfg, new_budget, batch=self.slots_per_shard,
                pipeline=True, min_batch=self.slots_per_shard)
        except PlanError:
            self._trip_breaker()         # not even degraded fits: reference
            return
        if plan == self.plan:
            return                       # shrunk budget still fits as-is
        self.plan = plan
        self.degrade_report = report
        self.degraded = self.degraded or report.degraded
        self._counters["replans"] += 1
        self._forward = self._make_forward("pallas", plan)
        self._lift_quarantine()          # new plan: lanes get a fresh chance

    def _corrupt_slot(self, spec: faults.FaultSpec, tick: int) -> None:
        """NaN-fill one seeded ACTIVE slot's device row (the host copy
        stays clean, so the retry path's re-upload heals it -- exactly
        the transient-device-corruption scenario)."""
        act = [s for s in range(self.slots) if self.active[s] is not None]
        if not act:
            return
        rng = np.random.default_rng(spec.seed + tick)
        s = act[int(rng.integers(len(act)))]
        if self._dirty:
            self._upload_dirty()    # land pending admissions first, or the
        bad = np.full((1,)          # dispatch upload would erase the NaN row
                      + self._batch.shape[1:], np.nan, np.float32)
        self._batch_dev = self._scatter(
            self._batch_dev, jnp.asarray([s], np.int32), jnp.asarray(bad))

    def _trip_breaker(self) -> None:
        if self._backend == "jnp":
            return                       # already on the reference path
        self._backend = "jnp"
        self.plan = None
        self.degraded = True
        self._counters["breaker_trips"] += 1
        self._breaker_fails = 0
        self._forward = self._make_forward("jnp", None)
        self._lift_quarantine()          # new backend: lanes get a fresh chance

    def _lift_quarantine(self) -> None:
        """Return quarantined slots to the admission pool with their
        poison streaks reset.  Called after ``probation_ticks``
        consecutive clean ticks, and on breaker trips / plan swaps (the
        serving path changed, so the old lanes' verdicts are stale)."""
        if not self.quarantined:
            return
        for s in self.quarantined:
            self._poison_streak[s] = 0
        self._counters["unquarantined"] += len(self.quarantined)
        self.quarantined.clear()
        self._clean_streak = 0

    def _maybe_lift_quarantine(self) -> None:
        if (self.probation_ticks is not None and self.quarantined
                and self._clean_streak >= self.probation_ticks):
            self._lift_quarantine()

    def _expired(self, req: CapsRequest) -> bool:
        return (req.deadline_s is not None
                and self._now() - req.submitted_s > req.deadline_s)

    def _sweep_deadlines(self, now: float) -> None:
        for req in [r for r in self.queue
                    if r.deadline_s is not None
                    and now - r.submitted_s > r.deadline_s]:
            self.queue.remove(req)
            self._finish(req, "timeout")
        for s in range(self.slots):
            req = self.active[s]
            if (req is not None and req.deadline_s is not None
                    and now - req.submitted_s > req.deadline_s):
                self._finish(req, "timeout", self._shard_of(s))
                self._clear_slot(s)

    # -- main loop -------------------------------------------------------
    def _end_tick(self, act_count: int, poisoned: bool = False) -> None:
        self.ticks += 1
        self._occupancy += act_count
        self._clean_streak = 0 if poisoned else self._clean_streak + 1
        self._stopped_s = self._now()

    def step(self) -> int:
        """One engine tick: fault reactions, deadline sweep, admit, then
        classify all dispatchable slots.  Returns the number of requests
        that reached ``ok`` this tick.  Under a profiler trace the tick
        and its phases are ``caps.tick*`` spans (``repro.core.spans``)."""
        with spans.span("caps.tick"):
            if self._started_s is None:
                self._started_s = self._now()
            with spans.span("caps.tick.admit"):
                self._sweep_deadlines(self._now())
                self._maybe_lift_quarantine()
                self._admit()
            # Tick faults land AFTER admission (slot_corrupt must see the
            # rows resident this tick) and BEFORE dispatch (a vmem_shrink
            # replan swaps the plan at the tick boundary, never mid-forward).
            if faults.enabled():
                self._apply_tick_faults(self.ticks)
            if self._stall_pending:
                # Injected stall: the tick passes with no dispatch (run()'s
                # zero-progress detection is the guardrail).
                self._stall_pending = False
                self._end_tick(0)
                return 0
            if self.queue and len(self.quarantined) == self.slots:
                # Every lane is quarantined: the backlog can never be served.
                # Shed it (terminal status) instead of spinning until the
                # stall detector fires.
                while self.queue:
                    self._finish(self.queue.popleft(), "shed")
            act = [s for s in range(self.slots)
                   if self.active[s] is not None
                   and self._backoff_until[s] <= self.ticks]
            if not act:
                if any(a is not None for a in self.active) or self.queue:
                    self._end_tick(0)        # backed-off slots need time to pass
                return 0
            with spans.span("caps.tick.upload"):
                if self._dirty:
                    self._upload_dirty()
            # Fixed-size index: the active slots, padded by repeating the
            # first (result rows not named in ``pos`` are ignored).  Under a
            # mesh the index is built PER SHARD in shard-local coordinates
            # (shard_map hands each device its own [slots_per_shard] block),
            # and ``pos`` maps slot -> global result row either way.
            pos: dict[int, int] = {}
            if self.mesh is None:
                idx = np.full(self.slots, act[0], np.int32)
                idx[:len(act)] = act
                pos = {s: i for i, s in enumerate(act)}
            else:
                sps = self.slots_per_shard
                idx = np.zeros(self.slots, np.int32)
                for shard in range(self.n_shards):
                    base = shard * sps
                    local = [s for s in act if base <= s < base + sps]
                    idx[base:base + sps] = (local[0] - base) if local else 0
                    for k, s in enumerate(local):
                        idx[base + k] = s - base
                        pos[s] = base + k
            try:
                if faults.enabled() and faults.poll(
                        faults.SITE_ENGINE_FORWARD, index=self.ticks,
                        kinds=("plan_error",)):
                    raise PlanError(
                        f"injected plan_error at {faults.SITE_ENGINE_FORWARD} "
                        f"(tick {self.ticks})")
                with spans.span("caps.tick.dispatch"):
                    out = self._forward(self.params, self._batch_dev,
                                        jnp.asarray(idx))
                with spans.span("caps.tick.fetch"):
                    lengths, preds = jax.device_get(out)
                self._breaker_fails = 0
            except Exception:
                # One forward failure loses one tick, never the engine:
                # consecutive failures trip the breaker onto the reference
                # backend (re-traced once) and the engine keeps serving.
                self._counters["forward_failures"] += 1
                self._breaker_fails += 1
                if self._breaker_fails >= self.breaker_after:
                    self._trip_breaker()
                self._end_tick(0)
                return 0
            if faults.enabled():
                for spec in faults.poll(faults.SITE_ENGINE_FORWARD,
                                        index=self.ticks,
                                        kinds=("nan_output", "inf_output")):
                    fill = np.nan if spec.kind == "nan_output" else np.inf
                    lengths = np.full_like(lengths, fill)
            with spans.span("caps.tick.finish"):
                done = 0
                poisoned_tick = False
                for s in act:
                    req = self.active[s]
                    row = lengths[pos[s]]
                    shard = self._shard_of(s)
                    if not np.all(np.isfinite(row)):
                        poisoned_tick = True
                        self._counters["poisoned"] += 1
                        self._poison_streak[s] += 1
                        if self._poison_streak[s] >= self.quarantine_after:
                            # K consecutive poisoned results through one lane:
                            # the slot is quarantined (probation may lift it
                            # later), the request errors out.
                            self.quarantined.add(s)
                            self._finish(req, "error", shard)
                            self._clear_slot(s)
                        elif self._expired(req):
                            # The deadline passed while the slot sat in retry
                            # backoff: terminate as timeout instead of burning
                            # another dispatch on a dead request.
                            self._finish(req, "timeout", shard)
                            self._clear_slot(s)
                        elif req.retries < self.max_retries:
                            req.retries += 1
                            self._counters["retries"] += 1
                            # Backoff grows with the retry count; the clean host
                            # image is re-uploaded (heals device corruption).
                            self._backoff_until[s] = (self.ticks + 1
                                                      + self.retry_backoff_ticks
                                                      * req.retries)
                            self._batch[s] = req.image
                            self._dirty.add(s)
                        else:
                            self._finish(req, "error", shard)
                            self._clear_slot(s)
                        continue
                    self._poison_streak[s] = 0
                    req.lengths = row
                    req.pred = int(preds[pos[s]])
                    self._finish(req, "ok", shard)
                    self._clear_slot(s)
                    done += 1
                self._end_tick(len(act), poisoned=poisoned_tick)
            return done

    def run(self, max_ticks: int | None = None) -> list[CapsRequest]:
        """Drive ticks until every request is terminal.  ``max_ticks``
        bounds the loop; ``stall_ticks`` consecutive ticks with no
        terminal event while work is pending raise ``EngineStalled``
        (named, with the pending counts) instead of hanging the host."""
        no_progress = 0
        while self.queue or any(a is not None for a in self.active):
            before = len(self.finished)
            self.step()
            no_progress = (0 if len(self.finished) > before
                           else no_progress + 1)
            pending = (len(self.queue)
                       + sum(a is not None for a in self.active))
            if pending and no_progress >= self.stall_ticks:
                raise EngineStalled(
                    f"no request reached a terminal status in "
                    f"{no_progress} consecutive ticks with {pending} "
                    f"pending (tick {self.ticks}); the engine is stalled")
            if max_ticks is not None and self.ticks >= max_ticks and pending:
                raise EngineStalled(
                    f"max_ticks={max_ticks} exhausted with {pending} "
                    f"requests still pending")
        return self.finished

    # -- reporting -------------------------------------------------------
    def stats(self) -> dict:
        n = len(self.finished)
        elapsed = ((self._stopped_s - self._started_s)
                   if self._started_s is not None and self._stopped_s is not None
                   else 0.0)
        lats = [r.latency_s for r in self.finished if r.latency_s is not None]
        sps = self.slots_per_shard
        per_shard = [
            dict(shard=i, slots=sps,
                 occupied=sum(1 for s in range(i * sps, (i + 1) * sps)
                              if self.active[s] is not None),
                 quarantined=sum(1 for s in self.quarantined
                                 if self._shard_of(s) == i),
                 **self._shard_counters[i])
            for i in range(self.n_shards)
        ]
        return dict(
            requests=n,
            ticks=self.ticks,
            elapsed_s=elapsed,
            requests_per_s=n / elapsed if elapsed > 0 else 0.0,
            mean_latency_ms=1e3 * float(np.mean(lats)) if lats else 0.0,
            max_latency_ms=1e3 * float(np.max(lats)) if lats else 0.0,
            occupancy=(self._occupancy / (self.ticks * self.slots)
                       if self.ticks else 0.0),
            degraded=self.degraded,
            quarantined=len(self.quarantined),
            vmem_budget=self._vmem_budget,
            n_shards=self.n_shards,
            slots_per_shard=sps,
            # Slot-resident terminals per shard + the queue bucket sum to
            # the aggregate counters (asserted by the chaos suite).
            per_shard=per_shard,
            queue_bucket=dict(self._queue_counters),
            **self._counters,
        )


class AsyncCapsuleServer:
    """Asyncio host loop over a ``CapsuleEngine``: continuous slot
    recycling with per-request futures.

    ``submit()`` enqueues through the engine (so the bounded-queue
    admission policy applies unchanged -- a shed request's future
    resolves immediately with ``status == "shed"``) and awaits the
    request's terminal status.  A single driver task ticks the engine
    whenever work is pending and yields to the event loop between
    ticks, so freed slots are refilled from whatever has been submitted
    since the last tick -- callers never wait for a "batch" to form.
    The engine is stepped from the event-loop thread only, so no
    engine state needs locking.  Works over sharded and unsharded
    engines alike; ``EngineStalled`` (or any driver failure) is
    propagated to every in-flight future instead of hanging them.
    """

    def __init__(self, engine: CapsuleEngine, *,
                 idle_sleep_s: float = 1e-3):
        self.engine = engine
        self._idle_sleep_s = idle_sleep_s
        self._waiters: dict[int, asyncio.Future] = {}   # id(req) -> future
        self._task: asyncio.Task | None = None
        self._stopping = False
        self._next_rid = 0
        self._seen = len(engine.finished)

    async def __aenter__(self) -> "AsyncCapsuleServer":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def start(self) -> None:
        if self._task is None:
            self._stopping = False
            self._task = asyncio.get_running_loop().create_task(
                self._drive())

    async def stop(self) -> None:
        """Drain: the driver keeps ticking until no work is pending,
        then exits."""
        self._stopping = True
        if self._task is not None:
            await self._task
            self._task = None

    async def submit(self, image, *,
                     deadline_s: float | None = None) -> CapsRequest:
        """Submit one image and await its terminal request."""
        rid = self._next_rid
        self._next_rid += 1
        req = CapsRequest(rid=rid, image=image, deadline_s=deadline_s)
        fut = asyncio.get_running_loop().create_future()
        self._waiters[id(req)] = fut
        self.engine.submit(req)      # may shed synchronously (admission)
        self._resolve_finished()
        self.start()                 # lazily spin the driver up
        return await fut

    def _resolve_finished(self) -> None:
        fin = self.engine.finished
        while self._seen < len(fin):
            req = fin[self._seen]
            self._seen += 1
            fut = self._waiters.pop(id(req), None)
            if fut is not None and not fut.done():
                fut.set_result(req)

    def _pending(self) -> bool:
        eng = self.engine
        return bool(eng.queue) or any(a is not None for a in eng.active)

    async def _drive(self) -> None:
        try:
            while True:
                if self._pending():
                    self.engine.step()
                    self._resolve_finished()
                    await asyncio.sleep(0)   # admit work queued mid-tick
                elif self._stopping:
                    return
                else:
                    await asyncio.sleep(self._idle_sleep_s)
        except BaseException as e:
            for fut in self._waiters.values():
                if not fut.done():
                    fut.set_exception(e)
            self._waiters.clear()
            raise
