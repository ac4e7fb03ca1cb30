"""Chaos suite: deterministic fault injection (``core/faults.py``) driving
the graceful-degradation paths for real -- engine retry/quarantine/breaker/
degraded-VMEM replanning, ``degrade_plan``'s fallback ladder, and the
training harness's NaN-streak / straggler / preemption machinery.

CI runs this file as the ``chaos-smoke`` job; locally:

    PYTHONPATH=src python -m pytest tests/test_faults.py -q
"""

import json

import jax
import numpy as np
import pytest

from repro.core import capsnet, faults
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import PlanError, compile_plan, degrade_plan
from repro.core.faults import FaultSpec, InjectionError
from repro.serve import CapsRequest, CapsuleEngine, EngineStalled
from repro.train import checkpoint as ckpt
from repro.verify import assert_engine_stats
from repro.train.capsnet_loop import SMOKE, CapsLoopConfig, CapsTrainLoop

KEY = jax.random.PRNGKey(0)
CFG = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                    pc_kernel=3, num_primary_groups=4, primary_dim=4,
                    class_dim=8, use_decoder=False)
PARAMS = capsnet.init_params(KEY, CFG)


def _images(n):
    return np.asarray(jax.random.uniform(
        KEY, (n, CFG.image_hw, CFG.image_hw, 1)))


def _reference_lengths(image):
    return np.asarray(capsnet.forward(PARAMS, image[None], CFG)["lengths"][0])


def _assert_terminal(engine):
    """Every submitted request reached exactly one terminal status and the
    counters account for all of them -- the ISSUE acceptance invariant.
    The accounting itself lives in the shared checker
    (``repro.verify.assert_engine_stats``) so this suite and
    ``test_sharded_serving.py`` cannot drift apart."""
    return assert_engine_stats(engine)


# -- registry mechanics ------------------------------------------------------

def test_spec_validation():
    with pytest.raises(InjectionError, match="unknown fault kind"):
        FaultSpec(site="engine.tick", kind="meteor_strike")
    with pytest.raises(InjectionError, match="times"):
        FaultSpec(site="engine.tick", kind="nan_output", times=-1)
    with pytest.raises(InjectionError, match="factor"):
        FaultSpec(site="engine.tick", kind="vmem_shrink", factor=0.0)
    with pytest.raises(InjectionError, match="factor"):
        FaultSpec(site="engine.tick", kind="vmem_shrink", factor=1.5)
    FaultSpec(site="engine.tick", kind="vmem_shrink", factor=1.0)  # boundary


def test_fires_at_window():
    spec = FaultSpec(site="s", kind="nan_output", at=2, times=3)
    assert [spec.fires_at(i) for i in range(7)] == \
        [False, False, True, True, True, False, False]
    never = FaultSpec(site="s", kind="nan_output", at=2, times=0)
    assert not any(never.fires_at(i) for i in range(7))


def test_poll_indexes_and_fired_log():
    a = FaultSpec(site="s", kind="nan_output", at=1, times=2)
    b = FaultSpec(site="t", kind="stall", at=0, times=1)
    with faults.inject(a, b) as reg:
        assert faults.poll("s", index=0) == ()
        assert faults.poll("s", index=1) == (a,)
        # no explicit index: the site's own counter advances per poll
        assert faults.poll("t") == (b,)      # counter 0
        assert faults.poll("t") == ()        # counter 1
        # kind filter
        assert faults.poll("s", index=2, kinds=("stall",)) == ()
        assert faults.poll("s", index=2, kinds=("nan_output",)) == (a,)
        assert reg.fired == [("s", "nan_output", 1), ("t", "stall", 0),
                             ("s", "nan_output", 2)]
        assert reg.count() == 3
        assert reg.count(site="s") == 2
        assert reg.count(kind="stall") == 1
    assert not faults.enabled()


def test_nested_inject_refused():
    with faults.inject():
        with pytest.raises(InjectionError, match="already active"):
            with faults.inject():
                pass
    assert not faults.enabled()              # outer context tore down


def test_disabled_is_inert():
    assert not faults.enabled()
    assert faults.registry() is None
    assert faults.poll("engine.tick", index=0) == ()
    x = np.ones(3)
    assert faults.corrupt_array("ops.conv2d", x) is x   # same object, no copy


# -- ops.* kernel-wrapper sites (eager calls) --------------------------------

def test_ops_site_poisons_eager_forward():
    img = _images(1)
    clean = np.asarray(capsnet.forward(PARAMS, img, CFG, backend="pallas",
                                       interpret=True)["lengths"])
    assert np.all(np.isfinite(clean))
    with faults.inject(FaultSpec(site=faults.SITE_CONV2D,
                                 kind="nan_output")) as reg:
        out = capsnet.forward(PARAMS, img, CFG, backend="pallas",
                              interpret=True)
        assert not np.all(np.isfinite(np.asarray(out["lengths"])))
        assert reg.count(site=faults.SITE_CONV2D, kind="nan_output") == 1
    # injection torn down: the same call is clean (and bit-identical) again
    again = np.asarray(capsnet.forward(PARAMS, img, CFG, backend="pallas",
                                       interpret=True)["lengths"])
    np.testing.assert_array_equal(again, clean)


def test_ops_site_plan_error_raises():
    with faults.inject(FaultSpec(site=faults.SITE_CONV2D,
                                 kind="plan_error")):
        with pytest.raises(PlanError, match="injected plan_error"):
            capsnet.forward(PARAMS, _images(1), CFG, backend="pallas",
                            interpret=True)


def test_ops_inf_output_corrupts_array():
    with faults.inject(FaultSpec(site=faults.SITE_VOTES_ROUTING,
                                 kind="inf_output")):
        out = faults.corrupt_array(faults.SITE_VOTES_ROUTING,
                                   np.zeros((2, 2), np.float32))
        assert np.all(np.isposinf(np.asarray(out)))


# -- degrade_plan fallback ladder --------------------------------------------

def test_degrade_plan_full_budget_is_golden():
    """At 100% budget the degraded plan IS the normal plan (bit-identical
    frozen dataclasses) and the report concedes nothing."""
    for pipeline in (False, True):
        plan, rep = degrade_plan(CFG, batch=4, pipeline=pipeline)
        assert plan == compile_plan(CFG, batch=4, pipeline=pipeline)
        assert rep.concessions == ()
        assert not rep.degraded
        assert rep.batch == rep.requested_batch == 4


def test_degrade_plan_forces_streamed_schedule():
    plan, rep = degrade_plan(CFG, batch=16, vmem_budget=4_000_000,
                             pipeline=True)
    assert rep.degraded and rep.batch == 16
    assert any("resident -> streamed" in c for c in rep.concessions)
    modes = {op.name: op.mode for op in plan.ops}
    assert modes["PrimaryCaps-Routing"] == "streamed"
    assert all(op.vmem_bytes <= 4_000_000 for op in plan.ops)


def test_degrade_plan_reduces_batch():
    """On the full MNIST config the pipelined pair's resident ``u`` scales
    with batch, so a tight budget walks down to a smaller feasible batch
    (the last rung before the breaker) and says so."""
    plan, rep = degrade_plan(CapsNetConfig(), batch=8, vmem_budget=4_000_000,
                             pipeline=True)
    assert rep.requested_batch == 8
    assert rep.batch < 8
    assert plan.batch == rep.batch
    assert any(f"batch 8 -> {rep.batch}" in c for c in rep.concessions)


def test_degrade_plan_exhaustion_raises_planerror():
    with pytest.raises(PlanError, match="no feasible plan"):
        degrade_plan(CFG, batch=4, vmem_budget=60_000)
    # min_batch floors the walk-down even when smaller batches would fit
    with pytest.raises(PlanError, match="batch >= 8"):
        degrade_plan(CapsNetConfig(), batch=8, vmem_budget=4_000_000,
                     pipeline=True, min_batch=8)


def test_degraded_plan_output_parity():
    """A degraded plan changes the schedule, never the math."""
    imgs = _images(2)
    plan, rep = degrade_plan(CFG, batch=2, vmem_budget=600_000,
                             pipeline=True)
    assert rep.degraded
    got = np.asarray(capsnet.forward(PARAMS, imgs, CFG, backend="pallas",
                                     plan=plan, interpret=True)["lengths"])
    want = np.asarray(capsnet.forward(PARAMS, imgs, CFG)["lengths"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- engine chaos ------------------------------------------------------------

def test_engine_nan_storm_terminates_with_terminal_statuses():
    imgs = _images(5)
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    for i in range(5):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=2)) as reg:
        engine.run()
        assert reg.count(kind="nan_output") >= 1
    s = _assert_terminal(engine)
    assert s["poisoned"] >= 1
    assert s["retries"] >= 1
    # retried requests recovered once the storm passed
    assert s["ok"] == 5 and s["error"] == 0
    for r in engine.finished:
        np.testing.assert_allclose(r.lengths, _reference_lengths(imgs[r.rid]),
                                   rtol=1e-5, atol=1e-5)


def test_engine_errors_after_max_retries():
    engine = CapsuleEngine(PARAMS, CFG, slots=1, max_retries=1,
                           quarantine_after=10)
    engine.submit(CapsRequest(rid=0, image=_images(1)[0]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="inf_output", at=0, times=50)):
        engine.run()
    s = _assert_terminal(engine)
    assert engine.finished[0].status == "error"
    assert engine.finished[0].retries == 1
    assert s["error"] == 1 and s["ok"] == 0


def test_engine_quarantines_poisoned_slot_and_sheds_backlog():
    imgs = _images(3)
    engine = CapsuleEngine(PARAMS, CFG, slots=1, max_retries=5,
                           quarantine_after=2)
    for i in range(3):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=100)):
        engine.run()
    s = _assert_terminal(engine)
    assert engine.quarantined == {0}
    assert s["quarantined"] == 1
    assert s["error"] == 1          # the request that poisoned the lane
    assert s["shed"] == 2           # the unservable backlog, not a hang


def test_engine_quarantine_probation_restores_capacity():
    """Regression: quarantine used to be permanent, so a transient NaN
    storm shrank capacity forever.  After the ``FaultSpec`` window
    closes, ``probation_ticks`` consecutive clean ticks lift the
    quarantine and the lane serves again."""
    imgs = _images(5)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, max_retries=5,
                           retry_backoff_ticks=0, quarantine_after=2,
                           probation_ticks=3)
    # Phase 1: one request -> only slot 0 is active; two poisoned ticks
    # quarantine the lane and error the request.
    engine.submit(CapsRequest(rid=0, image=imgs[0]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=2)):
        engine.run()
    assert engine.quarantined == {0}
    assert engine.stats()["error"] == 1
    # Phase 2: the fault window is over.  Slot 1 keeps serving; after
    # three clean ticks slot 0 comes off probation and capacity returns.
    for i in range(1, 5):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    engine.run()
    s = _assert_terminal(engine)
    assert engine.quarantined == set()
    assert s["unquarantined"] == 1
    assert s["quarantined"] == 0
    assert s["ok"] == 4 and s["error"] == 1
    for r in engine.finished:
        if r.status == "ok":
            np.testing.assert_allclose(
                r.lengths, _reference_lengths(imgs[r.rid]),
                rtol=1e-5, atol=1e-5)


def test_engine_plan_swap_clears_quarantine():
    """A degrade-replan swaps the serving path, so standing quarantine
    verdicts are stale: the swap returns the lanes to the pool even with
    probation disabled."""
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas",
                           quarantine_after=1, probation_ticks=None)
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(
            FaultSpec(site=faults.SITE_ENGINE_FORWARD, kind="nan_output",
                      at=0, times=1),
            FaultSpec(site=faults.SITE_ENGINE_TICK, kind="vmem_shrink",
                      at=1, times=1, factor=0.036)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["error"] == 2           # quarantine_after=1: both lanes, tick 0
    assert s["replans"] == 1 and s["unquarantined"] == 2
    assert engine.quarantined == set()
    assert s["ok"] == 2              # served AFTER the swap lifted quarantine
    assert engine._forward_traces == 2


def test_engine_breaker_trip_clears_quarantine():
    """The circuit breaker re-traces onto the reference backend -- a
    fresh serving path, so quarantined lanes get a fresh chance too."""
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas")
    engine.quarantined = {0, 1}
    engine._poison_streak = [3, 3]
    engine._trip_breaker()
    assert engine.quarantined == set()
    assert engine._poison_streak == [0, 0]
    assert engine.stats()["unquarantined"] == 2


def test_engine_retry_past_deadline_times_out():
    """Regression: the deadline sweep only ran at tick start, so a
    request poisoned by a slow tick was re-dispatched past its
    ``deadline_s``.  The retry path must check the deadline first and
    terminate as ``timeout`` -- never burn another dispatch on a dead
    request."""
    engine = CapsuleEngine(PARAMS, CFG, slots=1, max_retries=5,
                           retry_backoff_ticks=0, quarantine_after=10)
    clock = {"t": 0.0}
    engine._now = lambda: clock["t"]
    orig_forward = engine._forward

    def slow_forward(*a):               # each dispatch costs 0.6s of clock
        out = orig_forward(*a)
        clock["t"] += 0.6
        return out

    engine._forward = slow_forward
    engine.submit(CapsRequest(rid=0, image=_images(1)[0], deadline_s=1.0))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=2)):
        engine.run()
    s = _assert_terminal(engine)
    # Tick 0 poisons at t=0.6 (inside deadline: one retry is scheduled);
    # tick 1 poisons at t=1.2 -- past the deadline, so the request must
    # time out THERE instead of being re-dispatched a second time.
    assert engine.finished[0].status == "timeout"
    assert s["timeout"] == 1 and s["ok"] == 0 and s["error"] == 0
    assert s["retries"] == 1 and s["poisoned"] == 2


def test_engine_sharded_nan_storm_terminal_and_per_shard_sums():
    """Chaos under the mesh path (1-shard mesh runs on a single device):
    a NaN storm still leaves every request terminal, and the per-shard
    counters + queue bucket sum to the aggregate."""
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, n_shards=1,
                           retry_backoff_ticks=0)
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=2)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["n_shards"] == 1 and s["poisoned"] >= 2
    assert engine._forward_traces == 1
    for r in engine.finished:
        if r.status == "ok":
            np.testing.assert_allclose(
                r.lengths, _reference_lengths(imgs[r.rid]),
                rtol=1e-5, atol=1e-5)


def test_engine_sharded_vmem_shrink_one_retrace():
    """A vmem_shrink under the mesh path swaps the degraded PER-SHARD
    plan with ONE re-trace across the whole mesh."""
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas",
                           n_shards=1)
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="vmem_shrink", at=1, times=2,
                                 factor=0.036)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["ok"] == 6 and s["replans"] == 1
    assert engine._forward_traces == 2       # healthy trace + degraded trace
    for r in engine.finished:
        np.testing.assert_allclose(r.lengths, _reference_lengths(imgs[r.rid]),
                                   rtol=1e-4, atol=1e-4)


def test_engine_slot_corrupt_healed_by_retry():
    """Device-row corruption (the host copy stays clean) is healed by the
    retry path's re-upload -- the request still finishes ``ok``."""
    engine = CapsuleEngine(PARAMS, CFG, slots=1)
    img = _images(1)[0]
    engine.submit(CapsRequest(rid=0, image=img))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="slot_corrupt", at=0, times=1,
                                 seed=7)) as reg:
        engine.run()
        assert reg.count(kind="slot_corrupt") == 1
    s = _assert_terminal(engine)
    assert s["ok"] == 1 and s["poisoned"] == 1 and s["retries"] == 1
    np.testing.assert_allclose(engine.finished[0].lengths,
                               _reference_lengths(img), rtol=1e-5, atol=1e-5)


def test_engine_vmem_shrink_swaps_degraded_plan():
    """Mid-run shrink: ONE replan at a tick boundary, ONE new trace, the
    surviving requests bit-match the reference forward."""
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas")
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    assert engine._forward_traces == 0
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="vmem_shrink", at=1, times=2,
                                 factor=0.036)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["ok"] == 6
    assert s["replans"] == 1                 # idempotent across the window
    assert s["breaker_trips"] == 0
    assert s["degraded"] and engine.degrade_report.degraded
    assert engine.plan.vmem_budget == engine.degrade_report.vmem_budget
    assert engine._forward_traces == 2       # healthy trace + degraded trace
    for r in engine.finished:
        np.testing.assert_allclose(r.lengths, _reference_lengths(imgs[r.rid]),
                                   rtol=1e-4, atol=1e-4)


def test_engine_vmem_shrink_noop_factor_keeps_plan():
    """factor=1.0 is the identity shrink: the budget is unchanged, so the
    engine must not replan or re-trace -- the reaction path is a no-op."""
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas")
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="vmem_shrink", at=1, times=1,
                                 factor=1.0)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["ok"] == 4 and s["replans"] == 0 and not s["degraded"]
    assert engine._forward_traces == 1
    assert s["vmem_budget"] == engine._orig_budget


def test_engine_vmem_shrink_infeasible_trips_breaker():
    """A budget nothing fits under falls through degrade_plan to the
    breaker: the engine re-traces on the jnp reference backend and keeps
    serving, parity intact."""
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas")
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="vmem_shrink", at=1, times=1,
                                 factor=0.0005)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["ok"] == 6
    assert s["breaker_trips"] == 1 and s["replans"] == 0
    assert s["degraded"] and engine.plan is None
    assert engine._backend == "jnp"
    assert engine._forward_traces == 2
    for r in engine.finished:
        np.testing.assert_allclose(r.lengths, _reference_lengths(imgs[r.rid]),
                                   rtol=1e-4, atol=1e-4)


def test_engine_plan_error_storm_trips_breaker():
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas",
                           breaker_after=2)
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="plan_error", at=0, times=2)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["forward_failures"] == 2
    assert s["breaker_trips"] == 1 and s["degraded"]
    assert s["ok"] == 4                      # the reference path served them
    assert engine._backend == "jnp"
    # the pallas forward raised before its first dispatch, so the only
    # trace ever taken is the breaker's jnp one
    assert engine._forward_traces == 1


def test_engine_stall_detection_raises_named_error():
    engine = CapsuleEngine(PARAMS, CFG, slots=1, stall_ticks=5)
    engine.submit(CapsRequest(rid=0, image=_images(1)[0]))
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="stall", at=0, times=1000)):
        with pytest.raises(EngineStalled, match="stalled"):
            engine.run()


def test_engine_run_max_ticks_bounds_the_loop():
    imgs = _images(3)
    engine = CapsuleEngine(PARAMS, CFG, slots=1)
    for i in range(3):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with pytest.raises(EngineStalled, match="max_ticks=1"):
        engine.run(max_ticks=1)


def test_engine_bounded_queue_reject_and_shed_oldest():
    imgs = _images(3)
    rej = CapsuleEngine(PARAMS, CFG, slots=1, max_queue=2,
                        admission="reject")
    for i in range(3):
        rej.submit(CapsRequest(rid=i, image=imgs[i]))
    assert [r.rid for r in rej.finished] == [2]      # the newcomer paid
    assert rej.finished[0].status == "shed"
    rej.run()
    s = _assert_terminal(rej)
    assert s["ok"] == 2 and s["shed"] == 1

    old = CapsuleEngine(PARAMS, CFG, slots=1, max_queue=2,
                        admission="shed-oldest")
    for i in range(3):
        old.submit(CapsRequest(rid=i, image=imgs[i]))
    assert [r.rid for r in old.finished] == [0]      # the oldest paid
    old.run()
    s = _assert_terminal(old)
    assert s["ok"] == 2 and s["shed"] == 1
    assert sorted(r.rid for r in old.finished if r.status == "ok") == [1, 2]

    with pytest.raises(ValueError, match="admission"):
        CapsuleEngine(PARAMS, CFG, admission="coin-flip")


def test_engine_deadline_expires_to_timeout():
    imgs = _images(2)
    engine = CapsuleEngine(PARAMS, CFG, slots=1)
    engine.submit(CapsRequest(rid=0, image=imgs[0], deadline_s=0.0))
    engine.submit(CapsRequest(rid=1, image=imgs[1]))
    engine.run()
    s = _assert_terminal(engine)
    assert s["timeout"] == 1 and s["ok"] == 1
    by_rid = {r.rid: r for r in engine.finished}
    assert by_rid[0].status == "timeout" and by_rid[0].lengths is None
    assert by_rid[1].status == "ok"


def test_engine_acceptance_nan_storm_plus_half_vmem():
    """The ISSUE acceptance scenario: a NaN storm AND a 50% VMEM shrink
    mid-run; the engine terminates, every request is terminal, the
    counters sum, and surviving outputs match the reference."""
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas")
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    with faults.inject(
            FaultSpec(site=faults.SITE_ENGINE_FORWARD, kind="nan_output",
                      at=0, times=2),
            FaultSpec(site=faults.SITE_ENGINE_TICK, kind="vmem_shrink",
                      at=2, times=1, factor=0.5)):
        engine.run()
    s = _assert_terminal(engine)
    assert s["poisoned"] >= 1
    assert s["vmem_budget"] == engine._orig_budget // 2
    for r in engine.finished:
        if r.status == "ok":
            np.testing.assert_allclose(
                r.lengths, _reference_lengths(imgs[r.rid]),
                rtol=1e-4, atol=1e-4)


def test_engine_no_faults_single_trace_regression():
    """With injection disabled the hardened engine behaves exactly like
    the seed: one forward trace across all occupancies, everything ok."""
    imgs = _images(5)
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    for i in range(5):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    engine.run()
    s = _assert_terminal(engine)
    assert s["ok"] == 5 and engine._forward_traces == 1
    assert not s["degraded"] and s["replans"] == 0


# -- training harness --------------------------------------------------------

def _loop(tmp_path, total=8, **kw):
    return CapsTrainLoop(SMOKE, CapsLoopConfig(
        total_steps=total, batch=8, ckpt_every=4,
        ckpt_dir=str(tmp_path / "ck"), log_every=1000, backend="jnp", **kw))


def test_nan_streak_bounds_consecutive_not_lifetime(tmp_path):
    """Regression for the satellite fix: three NON-consecutive NaN steps
    must survive max_nan_skips=2 (the bound is the streak), while three
    CONSECUTIVE ones must abort."""
    loop = _loop(tmp_path, total=8, max_nan_skips=2)
    with faults.inject(
            FaultSpec(site=faults.SITE_TRAIN_STEP, kind="nan_output", at=1),
            FaultSpec(site=faults.SITE_TRAIN_STEP, kind="nan_output", at=3),
            FaultSpec(site=faults.SITE_TRAIN_STEP, kind="inf_output", at=5)):
        hist = loop.run()
    assert loop.nan_skips == 3               # lifetime count still reported
    assert loop.step == 8
    steps = [h["step"] for h in hist]
    assert 2 not in steps and 4 not in steps and 6 not in steps

    dead = _loop(tmp_path / "dead", total=8, max_nan_skips=2)
    with faults.inject(FaultSpec(site=faults.SITE_TRAIN_STEP,
                                 kind="nan_output", at=1, times=3)):
        with pytest.raises(RuntimeError, match="diverged: 3 consecutive"):
            dead.run()


def test_stall_fault_fires_straggler_hook(tmp_path):
    calls = []
    loop = CapsTrainLoop(SMOKE, CapsLoopConfig(
        total_steps=10, batch=8, ckpt_every=100,
        ckpt_dir=str(tmp_path / "ck"), log_every=1000, backend="jnp",
        straggler_factor=3.0),
        on_straggler=lambda step, dt: calls.append((step, dt)))
    with faults.inject(FaultSpec(site=faults.SITE_TRAIN_STEP, kind="stall",
                                 at=8, seconds=30.0)):
        loop.run()
    assert len(calls) == 1
    step, dt = calls[0]
    assert step == 8 and dt >= 30.0          # virtual time, no real sleep


def test_preemption_save_commits_checkpoint(tmp_path):
    """``request_stop`` mid-run (here: from the straggler hook, the SIGTERM
    stand-in) commits a ``preempted`` checkpoint at the stopped step."""
    loop = CapsTrainLoop(SMOKE, CapsLoopConfig(
        total_steps=50, batch=8, ckpt_every=100,
        ckpt_dir=str(tmp_path / "ck"), log_every=1000, backend="jnp",
        straggler_factor=3.0),
        on_straggler=lambda step, dt: loop.request_stop())
    with faults.inject(FaultSpec(site=faults.SITE_TRAIN_STEP, kind="stall",
                                 at=7, seconds=30.0)):
        loop.run()
    assert loop.step < 50                    # preempted, not completed
    assert ckpt.latest_step(tmp_path / "ck") == loop.step
    manifest = json.loads(
        (tmp_path / "ck" / f"step_{loop.step:08d}" / "manifest.json")
        .read_text())
    assert manifest["extra"]["preempted"] is True
    # and the preempted state resumes cleanly
    resumed = _loop(tmp_path, total=loop.step + 2)
    hist = resumed.run(resume=True)
    assert hist and hist[0]["step"] == loop.step + 1


def test_heartbeat_tmp_does_not_collide_on_stem(tmp_path):
    """Satellite regression: the heartbeat staging file is ``a.json.tmp``
    (full name + suffix), so a sibling ``a.tmp`` is never clobbered and
    two heartbeats sharing a stem cannot race through one staging path."""
    sentinel = tmp_path / "hb.tmp"
    sentinel.write_text("do not touch")
    loop = _loop(tmp_path, total=1,
                 heartbeat_path=str(tmp_path / "hb.json"))
    loop._heartbeat(3, {"loss": 1.25})
    assert sentinel.read_text() == "do not touch"
    assert json.loads((tmp_path / "hb.json").read_text())["step"] == 3
    assert not (tmp_path / "hb.json.tmp").exists()   # staging file replaced
