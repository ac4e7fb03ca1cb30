"""Multi-device CapsuleEngine serving: CPU-mesh parity and chaos at
2/4/8 virtual devices (8 forced host devices in a subprocess so the main
test process keeps 1 device -- same idiom as ``test_sharding.py``).

The acceptance claims checked here:
  * the sharded engine serves ``n_shards * slots_per_shard`` concurrent
    requests with ONE forward trace (``_forward_traces``);
  * outputs are bit-identical to the single-device engine for the same
    request stream, at every shard count, on both backends;
  * fault injection (vmem_shrink replan, NaN storm) keeps working per
    shard: ONE re-trace across the whole mesh, terminal statuses, and
    per-shard counters that sum to ``submitted``.
"""

import json
import subprocess
import sys
import textwrap

import pytest

SUBPROCESS_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.core import capsnet, faults
    from repro.core.capsnet import CapsNetConfig
    from repro.core.faults import FaultSpec
    from repro.serve import CapsRequest, CapsuleEngine

    CFG = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                        pc_kernel=3, num_primary_groups=4, primary_dim=4,
                        class_dim=8, use_decoder=False)
    PARAMS = capsnet.init_params(jax.random.PRNGKey(0), CFG)
    IMGS = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (16, CFG.image_hw, CFG.image_hw, 1)),
        np.float32)

    def serve(engine, n=16):
        for i in range(n):
            engine.submit(CapsRequest(rid=i, image=IMGS[i % len(IMGS)]))
        engine.run()
        return {r.rid: (np.asarray(r.lengths), r.pred)
                for r in engine.finished}

    from repro.verify import check_engine_stats

    def shard_sums_ok(s):
        # Shared counter-sum checker (also used by tests/test_faults.py
        # and documented by ``python -m repro.verify``).
        return not check_engine_stats(s)

    out = {"device_count": jax.device_count()}

    # -- jnp parity at every shard count vs the single-device engine ----
    ref = serve(CapsuleEngine(PARAMS, CFG, slots=16))
    for n in (1, 2, 4, 8):
        eng = CapsuleEngine(PARAMS, CFG, slots=16, n_shards=n)
        got = serve(eng)
        out[f"jnp_x{n}"] = dict(
            bit_identical=all(np.array_equal(ref[k][0], got[k][0])
                              and ref[k][1] == got[k][1] for k in ref),
            traces=eng._forward_traces,
            ticks=eng.ticks,
            shard_sums=shard_sums_ok(eng.stats()))

    # -- 8 * slots_per_shard concurrent requests, one tick, one trace ---
    eng = CapsuleEngine(PARAMS, CFG, slots=16, n_shards=8)
    for i in range(16):
        eng.submit(CapsRequest(rid=i, image=IMGS[i]))
    eng.step()
    s = eng.stats()
    out["concurrent"] = dict(slots_per_shard=eng.slots_per_shard,
                             ok_first_tick=s["ok"],
                             occupancy=s["occupancy"],
                             traces=eng._forward_traces)

    # -- pallas: per-shard plan, bit-identical to single-device pallas --
    pref = serve(CapsuleEngine(PARAMS, CFG, slots=16, backend="pallas"))
    eng = CapsuleEngine(PARAMS, CFG, slots=16, backend="pallas",
                        n_shards=8)
    got = serve(eng)
    out["pallas_x8"] = dict(
        bit_identical=all(np.array_equal(pref[k][0], got[k][0])
                          for k in pref),
        plan_batch=eng.plan.batch, traces=eng._forward_traces)

    # -- vmem_shrink under sharding: one replan, ONE mesh-wide re-trace -
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_TICK,
                                 kind="vmem_shrink", at=1, times=2,
                                 factor=0.06)):
        eng = CapsuleEngine(PARAMS, CFG, slots=8, backend="pallas",
                            n_shards=2)
        serve(eng)
    s = eng.stats()
    out["vmem_shrink_x2"] = dict(ok=s["ok"], replans=s["replans"],
                                 degraded=s["degraded"],
                                 traces=eng._forward_traces,
                                 shard_sums=shard_sums_ok(s))

    # -- NaN storm under sharding: terminal + per-shard sums ------------
    with faults.inject(FaultSpec(site=faults.SITE_ENGINE_FORWARD,
                                 kind="nan_output", at=0, times=2)):
        eng = CapsuleEngine(PARAMS, CFG, slots=8, n_shards=4,
                            retry_backoff_ticks=0)
        serve(eng)
    s = eng.stats()
    out["nan_storm_x4"] = dict(
        submitted=s["submitted"], poisoned=s["poisoned"],
        terminal=s["ok"] + s["timeout"] + s["error"] + s["shed"],
        shard_sums=shard_sums_ok(s))

    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_results():
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SRC],
                         capture_output=True, text=True, timeout=900,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device_count"] == 8
    return res


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_sharded_parity_bit_identical(mesh_results, n):
    r = mesh_results[f"jnp_x{n}"]
    assert r["bit_identical"]
    assert r["traces"] == 1
    assert r["shard_sums"]


def test_full_mesh_serves_concurrently_one_trace(mesh_results):
    r = mesh_results["concurrent"]
    assert r["ok_first_tick"] == 8 * r["slots_per_shard"] == 16
    assert r["occupancy"] == 1.0
    assert r["traces"] == 1


def test_pallas_sharded_parity_and_per_shard_plan(mesh_results):
    r = mesh_results["pallas_x8"]
    assert r["bit_identical"]
    assert r["plan_batch"] == 2          # slots=16 over 8 shards
    assert r["traces"] == 1


def test_vmem_shrink_under_sharding_one_mesh_retrace(mesh_results):
    r = mesh_results["vmem_shrink_x2"]
    assert r["ok"] == 16 and r["replans"] == 1 and r["degraded"]
    assert r["traces"] == 2              # healthy trace + degraded trace
    assert r["shard_sums"]


def test_nan_storm_under_sharding_terminal(mesh_results):
    r = mesh_results["nan_storm_x4"]
    assert r["terminal"] == r["submitted"] == 16
    assert r["poisoned"] >= 2
    assert r["shard_sums"]
