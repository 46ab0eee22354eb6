"""Fault-injection harness + self-healing serving paths (PR 8).

Every failure path the serving tier claims to survive is exercised here
deterministically on CPU CI via :mod:`repro.testing.faults`: shard retry
on a different device, device quarantine + half-open probe recovery, the
fused -> flat -> grouped degraded-engine chain (against the scalar
oracle), worker resurrection with typed ``WorkerCrashed`` futures, part
timeouts with abandoned-future accounting, and snapshot-restore outcome
counters.  No wall-clock randomness: every plan is seeded and rules fire
at explicit occurrences or with ``rate=1.0`` under ``max_fires`` caps.
"""
import time

import numpy as np
import pytest

from repro.core import devicecost, elements as el, whatif
from repro.core.batchcost import pack_frontier
from repro.core.hardware import hw1
from repro.core.synthesis import Workload, cost_workload
from repro.serving import (DesignCalculatorService, ScoringShardPool,
                           WorkerCrashed)
from repro.testing import faults
from repro.testing.faults import FaultInjected, FaultPlan, FaultRule

pytestmark = pytest.mark.chaos

W = Workload(n_entries=150_000, n_queries=100)
MIX = {"get": 60.0, "range_get": 20.0, "update": 20.0}


def _packed():
    return pack_frontier([el.spec_btree(), el.spec_hash_table(),
                          el.spec_skip_list(), el.spec_trie()], W, MIX)


def _service(hw, **kwargs):
    kwargs.setdefault("window_s", 0.002)
    return DesignCalculatorService([hw], **kwargs)


# ---------------------------------------------------------------------------
# The harness itself
# ---------------------------------------------------------------------------
def test_fault_plan_is_seed_deterministic():
    def pattern(seed):
        plan = FaultPlan(seed, [FaultRule("x", kind="error", rate=0.5)])
        hits = []
        with plan.activate():
            for i in range(200):
                try:
                    faults.check("x", key="k")
                    hits.append(0)
                except FaultInjected:
                    hits.append(1)
        return hits, plan.fires()

    first, fires = pattern(42)
    again, fires2 = pattern(42)
    assert first == again and fires == fires2
    assert 40 < fires < 160    # rate=0.5 actually fires, and not always


def test_seams_are_noops_without_a_plan():
    assert faults.active() is None
    faults.check("anything", key=7)     # must not raise
    value = np.ones(3)
    assert faults.corrupt("anything", value) is value


def test_only_one_plan_active_per_process():
    with FaultPlan(0, []).activate():
        with pytest.raises(RuntimeError, match="already active"):
            with FaultPlan(1, []).activate():
                pass
    faults.check("fine")    # the seams are clean again


def test_corrupt_poisons_float_leaves_only():
    plan = FaultPlan(0, [FaultRule("s", kind="corrupt", rate=1.0)])
    banks = {"f": np.arange(3, dtype=np.float64),
             "i": np.arange(3, dtype=np.int32)}
    with plan.activate():
        out = faults.corrupt("s", banks)
    assert np.isnan(out["f"]).all()             # float leaves poisoned
    assert np.array_equal(out["i"], banks["i"])  # gather indices intact


# ---------------------------------------------------------------------------
# Shard pool healing
# ---------------------------------------------------------------------------
@pytest.mark.devices(2)
def test_failed_part_retries_on_a_different_device(device_count):
    assert device_count >= 2
    pool = ScoringShardPool(2, part_timeout_s=5.0)
    hw = hw1()
    packed = _packed()
    dev0 = pool.devices[0].id
    baseline = packed.score(hw, engine="fused", shard=False)
    plan = FaultPlan(3, [FaultRule("shards.dispatch", kind="error",
                                   key=dev0, at=(0,))])
    try:
        with plan.activate():
            totals, _ = pool.score_frontier(packed, hw)
        assert np.allclose(totals, baseline, rtol=1e-6)
        assert pool.stats()["shard_retries"] == 1
        retries = [e for e in pool.recent_events() if e[0] == "retry"]
        assert retries and all(frm != to for _, _, frm, to in retries)
    finally:
        pool.close()


def test_quarantine_opens_and_half_open_probe_recovers():
    pool = ScoringShardPool(1, quarantine_after=2, quarantine_s=0.25,
                            part_timeout_s=5.0)
    hw = hw1()
    packed = _packed()
    baseline = packed.score(hw, engine="fused", shard=False)
    # exactly two dispatch failures: initial + same-device retry -> the
    # breaker opens; the flat rescore still answers the window
    plan = FaultPlan(5, [FaultRule("shards.dispatch", kind="error",
                                   rate=1.0, max_fires=2)])
    try:
        with plan.activate():
            totals, _ = pool.score_frontier(packed, hw)
            assert np.allclose(totals, baseline, rtol=1e-6)
            stats = pool.stats()
            assert stats["shard_rescored"] == 1
            assert stats["device_quarantines"] == 1
            health = pool.device_health()[0]
            assert health["state"] == "quarantined"
            assert health["consecutive_failures"] == 2
            time.sleep(0.3)
            assert pool.device_health()[0]["state"] == "half-open"
            # next pick is the probe; the rule is spent, so it succeeds
            totals, _ = pool.score_frontier(packed, hw)
        assert np.allclose(totals, baseline, rtol=1e-6)
        stats = pool.stats()
        assert stats["device_probes"] >= 1
        assert stats["device_recoveries"] == 1
        assert pool.device_health()[0]["state"] == "ok"
        kinds = [e[0] for e in pool.recent_events()]
        assert ["quarantine", "probe", "recover"] == \
            [k for k in kinds if k != "retry"]
    finally:
        pool.close()


def test_hung_part_times_out_and_is_abandoned():
    pool = ScoringShardPool(1, part_timeout_s=0.05)
    hw = hw1()
    packed = _packed()
    baseline = packed.score(hw, engine="fused", shard=False)
    # warm the device-routed jit through the executor path (a rule-free
    # plan forces it) so the timing below measures healing, not compiles
    with FaultPlan(0, []).activate():
        pool.score_frontier(packed, hw)
    plan = FaultPlan(9, [FaultRule("shards.dispatch", kind="hang",
                                   rate=1.0, hang_s=0.5, max_fires=1)])
    try:
        with plan.activate():
            t0 = time.monotonic()
            totals, _ = pool.score_frontier(packed, hw)
            elapsed = time.monotonic() - t0
            # the abandoned part wakes after the hang and still scores:
            # keep this plan active until it has, so that its fused call
            # cannot land in (and use up) a later test's fault plan
            deadline = time.monotonic() + 10.0
            while plan.occurrences("devicecost.fused") < 2 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
        assert elapsed < 0.45   # did not wait out the hang
        assert plan.occurrences("devicecost.fused") == 2
        assert np.allclose(totals, baseline, rtol=1e-6)
        stats = pool.stats()
        assert stats["shard_timeouts"] == 1
        assert stats["abandoned_parts"] == 1   # uncancellable, accounted
        assert stats["shard_retries"] == 1
    finally:
        pool.close()


def test_corrupt_fused_output_heals_inside_the_pool():
    hw = hw1()
    with _service(hw) as svc:
        q = (el.spec_btree(), el.spec_csb_tree(), W, hw)
        plan = FaultPlan(11, [FaultRule("devicecost.fused",
                                        kind="corrupt", at=(0,))])
        with plan.activate():
            got = svc.what_if_design(*q)
        assert plan.fires("devicecost.fused") == 1
        oracle = whatif.what_if_design(*q, engine="scalar")
        assert got.baseline_seconds == pytest.approx(
            oracle.baseline_seconds, rel=1e-6)
        assert got.variant_seconds == pytest.approx(
            oracle.variant_seconds, rel=1e-6)
        # healed below the engine chain: the retried dispatch was clean
        assert got.engine == "fused"
        stats = svc.stats()
        assert stats["shard_nonfinite"] >= 1
        assert stats["shard_retries"] >= 1
        assert stats["fallback_grouped"] == 0


# ---------------------------------------------------------------------------
# Degraded-engine fallback chain
# ---------------------------------------------------------------------------
def test_nan_banks_fall_back_to_oracle_then_probe_recovers():
    hw = hw1()
    with _service(hw, engine_probe_s=0.3) as svc:
        q = (el.spec_btree(), el.spec_csb_tree(), W, hw)
        oracle = whatif.what_if_design(*q, engine="scalar")
        # poison the NEXT bank build (the live table must be dropped for
        # the corruption to reach the scorer), then ask
        devicecost.invalidate_table(hw)
        plan = FaultPlan(13, [FaultRule("devicecost.banks",
                                        kind="corrupt", rate=1.0,
                                        max_fires=1)])
        with plan.activate():
            got = svc.what_if_design(*q)
        assert plan.fires("devicecost.banks") == 1
        # sharded fused and flat fused both saw NaN banks; the grouped
        # oracle answered, exactly
        assert got.engine == "grouped"
        assert got.baseline_seconds == pytest.approx(
            oracle.baseline_seconds, rel=1e-9)
        assert got.variant_seconds == pytest.approx(
            oracle.variant_seconds, rel=1e-9)
        stats = svc.stats()
        assert stats["nonfinite_groups"] >= 2
        assert stats["fallback_grouped"] == 1
        assert stats["engine_degraded"] == 1
        health = svc.health()["engines"][hw.name]
        assert health["degraded"] and health["engine"] == "grouped"
        # still inside the probe window: the oracle keeps serving
        got2 = svc.what_if_design(*q)
        assert got2.engine == "grouped"
        assert svc.stats()["fallback_grouped"] == 2
        time.sleep(0.35)
        # probe window open: the fused attempt rebuilds clean banks
        # (invalidate_table) and succeeds -> recovery
        got3 = svc.what_if_design(*q)
        assert got3.engine == "fused"
        assert got3.baseline_seconds == pytest.approx(
            oracle.baseline_seconds, rel=1e-6)
        stats = svc.stats()
        assert stats["engine_recovered"] == 1
        assert not svc.health()["engines"][hw.name]["degraded"]


# ---------------------------------------------------------------------------
# Worker supervision
# ---------------------------------------------------------------------------
def test_worker_crash_fails_inflight_typed_and_resurrects():
    hw = hw1()
    with _service(hw) as svc:
        q = (el.spec_btree(), el.spec_csb_tree(), W, hw)
        plan = FaultPlan(17, [FaultRule("service.worker", kind="error",
                                        at=(0,))])
        with plan.activate():
            fut = svc.submit_design(*q)
            with pytest.raises(WorkerCrashed) as err:
                fut.result(timeout=30)
        assert isinstance(err.value.cause, FaultInjected)
        assert err.value.restarts == 1
        # the resurrected worker serves the next request normally
        got = svc.what_if_design(*q)
        oracle = whatif.what_if_design(*q, engine="scalar")
        assert got.baseline_seconds == pytest.approx(
            oracle.baseline_seconds, rel=1e-6)
        assert svc.stats()["worker_restarts"] == 1
        assert svc.health()["worker_alive"]


# ---------------------------------------------------------------------------
# Snapshot-restore outcomes (satellite S3 regression)
# ---------------------------------------------------------------------------
def test_corrupt_snapshot_is_counted_and_cold_starts(tmp_path):
    path = tmp_path / "snap.pkl"
    path.write_bytes(b"this is not a pickle")
    hw = hw1()
    with _service(hw, snapshot_path=str(path)) as svc:
        stats = svc.stats()
        assert stats["snapshot_corrupt"] == 1
        assert stats["snapshot_discarded"] == 1
        assert stats["snapshot_entries"] == 0
        assert svc.health()["snapshot"]["outcome"] == "corrupt"
        # cold start is fine: the service still answers
        q = (el.spec_btree(), el.spec_csb_tree(), W, hw)
        assert svc.what_if_design(*q).baseline_seconds == pytest.approx(
            cost_workload(el.spec_btree(), W, hw), rel=1e-6)


def test_restored_snapshot_is_counted(tmp_path):
    path = tmp_path / "snap.pkl"
    hw = hw1()
    with _service(hw, snapshot_path=str(path)) as svc:
        svc.what_if_design(el.spec_btree(), el.spec_csb_tree(), W, hw)
        assert svc.save_snapshot() > 0
    with _service(hw, snapshot_path=str(path)) as svc:
        stats = svc.stats()
        assert stats["snapshot_restored"] == 1
        assert stats["snapshot_discarded"] == 0
        assert stats["snapshot_entries"] > 0
        assert svc.health()["snapshot"]["outcome"] == "restored"
