"""CapsuleEngine: slot-batched classification vs the direct forward oracle,
queue refill, latency/throughput reporting, pallas-backend parity, the
sharded (mesh) layout, and the asyncio host loop.

The in-process tests exercise the mesh path with ``n_shards=1`` (one
CpuDevice); multi-device parity at 2/4/8 virtual devices lives in
``tests/test_sharded_serving.py`` (subprocess with forced host devices).
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.core import capsnet
from repro.core.capsnet import CapsNetConfig
from repro.core.execplan import PlanError, compile_plan
from repro.serve import AsyncCapsuleServer, CapsRequest, CapsuleEngine

KEY = jax.random.PRNGKey(0)
CFG = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                    pc_kernel=3, num_primary_groups=4, primary_dim=4,
                    class_dim=8, use_decoder=False)
PARAMS = capsnet.init_params(KEY, CFG)


def _images(n):
    return np.asarray(jax.random.uniform(
        KEY, (n, CFG.image_hw, CFG.image_hw, 1)))


def test_engine_matches_direct_forward():
    imgs = _images(5)
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    for i in range(5):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    done = engine.run()
    assert sorted(r.rid for r in done) == list(range(5))
    for r in done:
        want = np.asarray(capsnet.forward(
            PARAMS, imgs[r.rid][None], CFG)["lengths"][0])
        np.testing.assert_allclose(r.lengths, want, rtol=1e-5, atol=1e-5)
        assert r.pred == int(np.argmax(want))


def test_engine_refills_slots_from_queue():
    imgs = _images(7)
    engine = CapsuleEngine(PARAMS, CFG, slots=3)
    for i in range(7):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    done = engine.run()
    assert len(done) == 7
    assert engine.ticks >= 3                      # ceil(7 / 3)
    assert all(a is None for a in engine.active)
    assert not engine.queue
    # the last request waited in the queue while slots were busy
    last = next(r for r in done if r.rid == 6)
    assert last.admitted_s > last.submitted_s


def test_engine_reports_latency_and_throughput():
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    engine.run()
    s = engine.stats()
    assert s["requests"] == 4
    assert s["elapsed_s"] > 0
    assert s["requests_per_s"] > 0
    assert s["mean_latency_ms"] > 0
    assert s["max_latency_ms"] >= s["mean_latency_ms"]
    assert 0 < s["occupancy"] <= 1.0
    for r in engine.finished:
        assert r.latency_s is not None and r.latency_s >= 0


def test_engine_shares_one_plan():
    plan = compile_plan(CFG, batch=2)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, plan=plan)
    assert engine.plan is plan                    # amortized, not recompiled


def test_engine_rejects_plan_batch_below_slots():
    """A plan compiled for batch < slots would blow the validated VMEM
    footprint (or raise the opaque kernel batch error) on the first
    step(); the constructor rejects it naming both numbers."""
    plan = compile_plan(CFG, batch=2)
    with pytest.raises(PlanError, match=r"batch 2.*4 slots"):
        CapsuleEngine(PARAMS, CFG, slots=4, plan=plan)
    # batch == slots and batch > slots are both within the validated bound
    for slots in (2, 1):
        engine = CapsuleEngine(PARAMS, CFG, slots=slots, plan=plan)
        assert engine.plan is plan


def test_engine_traces_forward_once_across_occupancies():
    """Varying occupancy (full slots, partial refill, single straggler)
    must reuse ONE compiled forward: the active-slot gather runs inside
    the jit over a fixed-size padded index.  The old eager jnp.take
    compiled a fresh gather program per distinct occupancy count."""
    imgs = _images(7)
    engine = CapsuleEngine(PARAMS, CFG, slots=3)
    for i in range(7):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    done = engine.run()                   # occupancies 3, 3, 1
    assert len(done) == 7 and engine.ticks == 3
    assert engine._forward_traces == 1
    for r in done:                        # and results stay correct
        want = np.asarray(capsnet.forward(
            PARAMS, imgs[r.rid][None], CFG)["lengths"][0])
        np.testing.assert_allclose(r.lengths, want, rtol=1e-5, atol=1e-5)


def test_engine_reuses_slot_with_fresh_image():
    """The dirty-slot upload path must refresh a reused slot's device row
    -- stale device state would silently classify the PREVIOUS image."""
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=1)
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    done = engine.run()                   # slot 0 reused for every request
    assert [r.rid for r in done] == list(range(4))
    preds = set()
    for r in done:
        want = np.asarray(capsnet.forward(
            PARAMS, imgs[r.rid][None], CFG)["lengths"][0])
        np.testing.assert_allclose(r.lengths, want, rtol=1e-5, atol=1e-5)
        preds.add(tuple(np.round(r.lengths, 6)))
    assert len(preds) == 4                # four distinct images, not one


def test_engine_pallas_backend_matches_jnp_engine():
    imgs = _images(3)
    results = {}
    for backend in ("jnp", "pallas"):
        engine = CapsuleEngine(PARAMS, CFG, slots=2, backend=backend)
        for i in range(3):
            engine.submit(CapsRequest(rid=i, image=imgs[i]))
        done = engine.run()
        results[backend] = {r.rid: r.lengths for r in done}
    for rid in range(3):
        np.testing.assert_allclose(results["pallas"][rid],
                                   results["jnp"][rid],
                                   rtol=1e-4, atol=1e-4)


def test_engine_rejects_mismatched_image_layout():
    """A same-size CHW image must be rejected, not silently reinterpreted
    as HWC garbage (the old reshape accepted any same-size layout)."""
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    good = _images(1)[0]                                   # [14, 14, 1] HWC
    chw = np.transpose(good, (2, 0, 1))                    # [1, 14, 14] CHW
    with pytest.raises(ValueError, match="does not match"):
        engine.submit(CapsRequest(rid=0, image=chw))
    with pytest.raises(ValueError, match="does not match"):
        engine.submit(CapsRequest(rid=1, image=good.reshape(-1)))  # flat
    with pytest.raises(ValueError, match="does not match"):
        engine.submit(CapsRequest(rid=2, image=good[..., 0]))      # [14, 14]
    assert not engine.queue                                # nothing admitted
    engine.submit(CapsRequest(rid=3, image=good))          # correct layout
    assert len(engine.queue) == 1
    done = engine.run()
    want = np.asarray(capsnet.forward(PARAMS, good[None], CFG)["lengths"][0])
    np.testing.assert_allclose(done[0].lengths, want, rtol=1e-5, atol=1e-5)


def test_engine_empty_step_is_noop():
    engine = CapsuleEngine(PARAMS, CFG, slots=2)
    assert engine.step() == 0
    assert engine.stats()["requests"] == 0


def test_engine_preserves_fifo_admission():
    imgs = _images(6)
    engine = CapsuleEngine(PARAMS, CFG, slots=1)
    for i in range(6):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    done = engine.run()
    assert [r.rid for r in done] == list(range(6))  # one slot => strict FIFO


# -- sharded layout (n_shards=1 exercises the mesh path on one device) -------

def test_sharded_engine_bit_identical_to_plain():
    """The mesh path (shard_map, per-shard index, sharded device batch)
    must not perturb a single bit: the head is per-sample."""
    imgs = _images(7)
    results = {}
    for n_shards in (None, 1):
        engine = CapsuleEngine(PARAMS, CFG, slots=2, n_shards=n_shards)
        for i in range(7):
            engine.submit(CapsRequest(rid=i, image=imgs[i]))
        engine.run()
        assert engine._forward_traces == 1
        results[n_shards] = {r.rid: (r.lengths, r.pred)
                             for r in engine.finished}
    for rid in range(7):
        np.testing.assert_array_equal(results[None][rid][0],
                                      results[1][rid][0])
        assert results[None][rid][1] == results[1][rid][1]


def test_sharded_engine_pallas_per_shard_plan():
    """ONE compile_plan produces the per-shard plan: plan.batch equals
    slots_per_shard, and the pallas engine serves through it."""
    imgs = _images(4)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, backend="pallas",
                           n_shards=1)
    assert engine.plan.batch == engine.slots_per_shard == 2
    for i in range(4):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    engine.run()
    assert engine._forward_traces == 1
    for r in engine.finished:
        want = np.asarray(capsnet.forward(
            PARAMS, imgs[r.rid][None], CFG)["lengths"][0])
        np.testing.assert_allclose(r.lengths, want, rtol=1e-4, atol=1e-4)


def test_sharded_engine_validates_layout():
    with pytest.raises(ValueError, match="does not divide"):
        CapsuleEngine(PARAMS, CFG, slots=3, n_shards=2)
    with pytest.raises(ValueError, match="n_shards"):
        CapsuleEngine(PARAMS, CFG, slots=4,
                      n_shards=len(jax.devices()) + 1)


def test_sharded_engine_plan_batch_contract():
    """slots = n_shards * plan.batch: a caller plan below the PER-SHARD
    batch is rejected up front, one at (or above) it is accepted even
    though plan.batch < slots."""
    if len(jax.devices()) < 2:
        plan = compile_plan(CFG, batch=1)
        with pytest.raises(PlanError, match="per shard"):
            CapsuleEngine(PARAMS, CFG, slots=2, n_shards=1, plan=plan)
        ok = compile_plan(CFG, batch=2)
        engine = CapsuleEngine(PARAMS, CFG, slots=2, n_shards=1, plan=ok)
        assert engine.plan is ok
    else:
        plan = compile_plan(CFG, batch=2)
        with pytest.raises(PlanError, match="per shard"):
            CapsuleEngine(PARAMS, CFG, slots=8, n_shards=2, plan=plan)
        engine = CapsuleEngine(PARAMS, CFG, slots=4, n_shards=2, plan=plan)
        assert engine.plan is plan               # 4 = 2 shards * batch 2


def test_sharded_engine_stats_sum_per_shard():
    imgs = _images(5)
    engine = CapsuleEngine(PARAMS, CFG, slots=2, n_shards=1, max_queue=2)
    for i in range(5):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    engine.run()
    s = engine.stats()
    assert s["n_shards"] == 1 and s["slots_per_shard"] == 2
    for status in ("ok", "timeout", "error", "shed"):
        assert (sum(sh[status] for sh in s["per_shard"])
                + s["queue_bucket"][status] == s[status])
    assert s["ok"] + s["timeout"] + s["error"] + s["shed"] == s["submitted"]
    assert s["queue_bucket"]["shed"] == s["shed"] > 0   # admission sheds


# -- asyncio host loop -------------------------------------------------------

def test_async_server_serves_concurrent_submissions():
    imgs = _images(9)

    async def main():
        engine = CapsuleEngine(PARAMS, CFG, slots=3)
        async with AsyncCapsuleServer(engine) as server:
            reqs = await asyncio.gather(
                *(server.submit(imgs[i]) for i in range(9)))
        return engine, reqs

    engine, reqs = asyncio.run(main())
    assert all(r.status == "ok" for r in reqs)
    assert engine._forward_traces == 1          # the loop adds no traces
    for i, r in enumerate(reqs):
        want = np.asarray(capsnet.forward(
            PARAMS, imgs[i][None], CFG)["lengths"][0])
        np.testing.assert_allclose(r.lengths, want, rtol=1e-5, atol=1e-5)


def test_async_server_recycles_slots_continuously():
    """Work submitted while earlier requests are in flight is picked up
    by later ticks of the same driver -- no batch boundaries."""
    imgs = _images(6)

    async def main():
        engine = CapsuleEngine(PARAMS, CFG, slots=2)
        async with AsyncCapsuleServer(engine) as server:
            first = asyncio.ensure_future(
                asyncio.gather(*(server.submit(imgs[i]) for i in range(3))))
            await asyncio.sleep(0)              # let the first wave land
            second = asyncio.gather(
                *(server.submit(imgs[i]) for i in range(3, 6)))
            reqs = await first + await second
        return engine, reqs

    engine, reqs = asyncio.run(main())
    assert all(r.status == "ok" for r in reqs)
    assert len(engine.finished) == 6
    assert engine._forward_traces == 1


def test_async_server_admission_control_sheds():
    """The engine's bounded-queue admission applies unchanged: a shed
    request's future resolves immediately with status 'shed'."""
    imgs = _images(8)

    async def main():
        engine = CapsuleEngine(PARAMS, CFG, slots=1, max_queue=2,
                               admission="reject")
        async with AsyncCapsuleServer(engine) as server:
            reqs = await asyncio.gather(
                *(server.submit(imgs[i]) for i in range(8)))
        return engine, reqs

    engine, reqs = asyncio.run(main())
    statuses = [r.status for r in reqs]
    assert set(statuses) <= {"ok", "shed"} and "shed" in statuses
    s = engine.stats()
    assert s["ok"] + s["shed"] == s["submitted"] == 8


def test_async_server_over_sharded_engine():
    imgs = _images(6)

    async def main():
        engine = CapsuleEngine(PARAMS, CFG, slots=2, n_shards=1)
        async with AsyncCapsuleServer(engine) as server:
            reqs = await asyncio.gather(
                *(server.submit(imgs[i]) for i in range(6)))
        return engine, reqs

    engine, reqs = asyncio.run(main())
    assert all(r.status == "ok" for r in reqs)
    assert engine._forward_traces == 1
    s = engine.stats()
    assert sum(sh["ok"] for sh in s["per_shard"]) == 6
