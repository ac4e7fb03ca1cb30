"""Tests for the static plan auditor (``verify.lowering``) and the
shared runtime-invariant checker (``verify.invariants``).

The positive direction (every registered arch audits clean) is what
``python -m repro.verify`` sweeps in CI; here we pin a representative
slice plus the NEGATIVE direction: seeded wrong plan/kernel pairs that
the auditor must catch, and seeded inconsistent stats dicts the
invariant checker must flag.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import CAPSNET_ARCHS, get_config
from repro.core import execplan
from repro.verify import audit_config, audit_op, check_engine_stats
from repro.verify import lowering


def _checks_by_name(audit):
    return {c.name: c for c in audit.checks}


# ---------------------------------------------------------------------------
# Clean audits: every registered arch, plus train/pipeline coverage
# ---------------------------------------------------------------------------

class TestCleanAudit:

    @pytest.mark.parametrize("arch", CAPSNET_ARCHS)
    def test_full_budget_pipelined(self, arch):
        rep = audit_config(get_config(arch), batch=1, pipeline=True)
        assert rep.ok, [f"{op}: {c.name} {c.detail}"
                        for op, c in rep.failures()]

    def test_train_plan_covers_backward_tracers(self):
        rep = audit_config(get_config("capsnet-mnist"), batch=2,
                           train=True)
        assert rep.ok, [f"{op}: {c.name} {c.detail}"
                        for op, c in rep.failures()]
        kernels = {o.kernel for o in rep.ops}
        assert "conv_im2col_bwd" in kernels
        assert "votes_routing_bwd" in kernels

    def test_degraded_budget_audits_clean(self):
        # The quarter-budget rung forces a streamed pipelined pair with a
        # smaller produce K tile -- the lowering must still match the
        # degraded model.
        plan, _rep = execplan.degrade_plan(
            get_config("capsnet-mnist"), execplan.VMEM_BYTES // 4,
            batch=4, pipeline=True)
        rep = lowering.audit_plan(plan, label="mnist-25%")
        assert rep.ok, [f"{op}: {c.name} {c.detail}"
                        for op, c in rep.failures()]


# ---------------------------------------------------------------------------
# Seeded regressions: a wrong model/kernel pair MUST be caught
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def svhn_plan():
    return execplan.compile_plan(get_config("capsnet-svhn"), batch=4,
                                 pipeline=True)


@pytest.fixture(scope="module")
def svhn_routing_op(svhn_plan):
    # Multi-pass streamed op: its W stream crosses HBM n_passes times,
    # so every seeded lie below is observable in the lowering.
    (op,) = [o for o in svhn_plan.ops
             if o.kernel == "primary_routing"]
    assert op.n_passes and op.n_passes > 1
    return op


class TestSeededDrift:

    def test_understated_vmem_is_caught(self, svhn_plan, svhn_routing_op):
        lie = dataclasses.replace(svhn_routing_op,
                                  vmem_bytes=svhn_routing_op.vmem_bytes // 2)
        audit = audit_op(svhn_plan, lie)
        assert not audit.ok
        assert not _checks_by_name(audit)["vmem-under-model"].ok

    def test_overstated_vmem_is_caught(self, svhn_plan, svhn_routing_op):
        lie = dataclasses.replace(svhn_routing_op,
                                  vmem_bytes=svhn_routing_op.vmem_bytes * 4)
        audit = audit_op(svhn_plan, lie)
        assert not _checks_by_name(audit)["vmem-over-model"].ok

    def test_wrong_hbm_traffic_is_caught(self, svhn_plan, svhn_routing_op):
        lie = dataclasses.replace(svhn_routing_op,
                                  hbm_bytes=svhn_routing_op.hbm_bytes * 10)
        audit = audit_op(svhn_plan, lie)
        assert not _checks_by_name(audit)["hbm-traffic"].ok

    def test_wrong_pass_count_is_caught(self, svhn_plan, svhn_routing_op):
        lie = dataclasses.replace(svhn_routing_op,
                                  n_passes=svhn_routing_op.n_passes + 3)
        audit = audit_op(svhn_plan, lie)
        assert not _checks_by_name(audit)["w-pass-count"].ok

    def test_honest_op_passes_the_same_checks(self, svhn_plan,
                                              svhn_routing_op):
        audit = audit_op(svhn_plan, svhn_routing_op)
        assert audit.ok, [f"{c.name}: {c.detail}"
                          for c in audit.failures()]


# ---------------------------------------------------------------------------
# Zero-intermediate proof: the jaxpr shape scan itself
# ---------------------------------------------------------------------------

class TestShapeCheck:

    B, I, J, D = 2, 8, 4, 4

    def _outer_eqns(self, fn, *avals):
        jaxpr = jax.make_jaxpr(fn)(*avals)
        calls, outer = [], []
        lowering._walk(jaxpr.jaxpr, calls, outer)
        return outer

    def test_materialized_uhat_fails_the_claim(self):
        B, I, J, D = self.B, self.I, self.J, self.D

        def leaky(u, w):
            uhat = jnp.einsum("bid,idj->bij", u, w)   # (B, I, J) in HBM
            return uhat.sum()

        outer = self._outer_eqns(
            leaky,
            jax.ShapeDtypeStruct((B, I, D), jnp.float32),
            jax.ShapeDtypeStruct((I, D, J), jnp.float32))
        chk = lowering._shape_check(outer, {(B, I, J)}, set(),
                                    "uhat-never-in-hbm")
        assert not chk.ok
        assert str((B, I, J)) in chk.detail

    def test_clean_function_passes_the_claim(self):
        B, I, J, D = self.B, self.I, self.J, self.D

        def tight(u, w):
            return jnp.einsum("bid,idj->bj", u, w)    # (B, J) only

        outer = self._outer_eqns(
            tight,
            jax.ShapeDtypeStruct((B, I, D), jnp.float32),
            jax.ShapeDtypeStruct((I, D, J), jnp.float32))
        chk = lowering._shape_check(outer, {(B, I, J)}, set(),
                                    "uhat-never-in-hbm")
        assert chk.ok

    def test_allowed_shapes_are_exempt(self):
        B, I, J, D = self.B, self.I, self.J, self.D

        def leaky(u, w):
            return jnp.einsum("bid,idj->bij", u, w).sum()

        outer = self._outer_eqns(
            leaky,
            jax.ShapeDtypeStruct((B, I, D), jnp.float32),
            jax.ShapeDtypeStruct((I, D, J), jnp.float32))
        chk = lowering._shape_check(outer, {(B, I, J)}, {(B, I, J)},
                                    "uhat-never-in-hbm")
        assert chk.ok


# ---------------------------------------------------------------------------
# Runtime-counter invariants (verify.invariants)
# ---------------------------------------------------------------------------

def _healthy_stats():
    return {
        "submitted": 5, "ok": 3, "timeout": 1, "error": 0, "shed": 1,
        "quarantined": 1, "n_shards": 2,
        "per_shard": [
            {"ok": 2, "timeout": 0, "error": 0, "shed": 0,
             "quarantined": 1},
            {"ok": 1, "timeout": 1, "error": 0, "shed": 0,
             "quarantined": 0},
        ],
        "queue_bucket": {"ok": 0, "timeout": 0, "error": 0, "shed": 1},
    }


class TestEngineStatsChecker:

    def test_terminal_statuses_pinned_to_serving(self):
        # verify.invariants mirrors the tuple instead of importing the
        # serving stack; this is the pin that keeps the mirror honest.
        from repro.serve.capsule import TERMINAL_STATUSES as serve_ts
        from repro.verify.invariants import TERMINAL_STATUSES as verify_ts
        assert set(serve_ts) == set(verify_ts)

    def test_healthy_stats_pass(self):
        assert check_engine_stats(_healthy_stats()) == []

    def test_lost_request_is_flagged(self):
        s = _healthy_stats()
        s["submitted"] += 1              # one submission never terminated
        problems = check_engine_stats(s)
        assert any("submitted" in p for p in problems)

    def test_missing_shard_row_is_flagged(self):
        s = _healthy_stats()
        s["per_shard"] = s["per_shard"][:1]
        problems = check_engine_stats(s)
        assert any("per-shard" in p for p in problems)

    def test_shard_counter_drift_is_flagged(self):
        s = _healthy_stats()
        s["per_shard"][0]["ok"] += 1     # shard claims a request twice
        problems = check_engine_stats(s)
        assert any(p.startswith("ok:") for p in problems)

    def test_quarantine_drift_is_flagged(self):
        s = _healthy_stats()
        s["quarantined"] = 7
        problems = check_engine_stats(s)
        assert any("quarantined" in p for p in problems)
