"""Multi-device scoring through the serving shard pool (PR 7).

The scoring-shard pool is the one way several devices score a product.
The contract: its merged frontier totals and sweep grids are
bit-identical to the flat one-device ``score`` call (parts cut on
design boundaries), answers through ``whatif.workload_sweep`` and
``complete_design`` are identical run directly or through a service
with one scoring shard per device, repeat pool scores and hardware
swaps recompile nothing, and the pool partitions a window across >= 2
devices while keeping the PR 6 deadline semantics.

Multi-device cases carry ``@pytest.mark.devices(n)``: the
``device_count`` fixture re-invokes them in a subprocess under
``--xla_force_host_platform_device_count=n`` (2/8/48-way sharding in
one CI run, no hardware needed).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import batchcost, devicecost, elements as el, whatif
from repro.core.autocomplete import complete_design
from repro.core.batchcost import pack_frontier, pack_sweep
from repro.core.hardware import hw1, hw3
from repro.core.synthesis import Workload
from repro.serving import DesignCalculatorService, ScoringShardPool
from repro.serving.admission import DeadlineExceeded
from repro.testing.devices import (DEVICE_COUNT_FLAG, forced_device_count,
                                   forced_device_env)

BASE = Workload(n_entries=120_000, n_queries=100)
MIX = {"get": 60.0, "range_get": 20.0, "update": 20.0}


def _specs():
    return [el.spec_btree(), el.spec_array(1), el.spec_hash_table(),
            el.spec_skip_list(), el.spec_trie(), el.spec_linked_list(),
            el.spec_sorted_array(1), el.spec_csb_tree()]


def _workloads(n=5):
    return [dataclasses.replace(BASE, zipf_alpha=0.3 * i)
            for i in range(n)]


def _ragged_workloads():
    """Points whose data sizes change the designs' level structure: a
    non-rectangular sweep (one spliced flat call, no shared ids)."""
    return [BASE, dataclasses.replace(BASE, n_entries=50_000_000),
            dataclasses.replace(BASE, n_entries=1000)]


def test_forced_device_env_helpers():
    env = forced_device_env(8, {"XLA_FLAGS": f"{DEVICE_COUNT_FLAG}=2 "
                                             "--other=1"})
    assert forced_device_count(env) == 8
    assert "--other=1" in env["XLA_FLAGS"]
    assert env["XLA_FLAGS"].count(DEVICE_COUNT_FLAG) == 1
    assert forced_device_count({"XLA_FLAGS": ""}) is None


# ---------------------------------------------------------------------------
# Split/merge partitions (the shard pool's primitive) — any device count
# ---------------------------------------------------------------------------
def test_frontier_split_merge_bit_identical(hw_analytical):
    packed = pack_frontier(_specs(), BASE, MIX)
    whole = packed.score(hw_analytical)
    for n_parts in (1, 2, 3, len(_specs()), 64):
        parts = packed.split(n_parts)
        assert sum(p.n_segments for p in parts) == packed.n_segments
        merged = np.concatenate(
            [p.score(hw_analytical) for p in parts])
        assert np.array_equal(merged, whole)


def test_sweep_split_merge_bit_identical(hw_analytical):
    sweep = pack_sweep(_specs(), _workloads(), [MIX] * 5)
    whole = sweep.score(hw_analytical)
    for n_parts in (2, 3, 64):
        parts = sweep.split(n_parts)
        assert all(p.rectangular for p in parts)   # ids stay shared
        merged = np.concatenate(
            [p.score(hw_analytical) for p in parts], axis=1)
        assert np.array_equal(merged, whole)


@pytest.mark.parametrize("kind", ["frontier", "sweep", "one_row",
                                  "ragged"])
def test_pool_matches_flat_here(hw_analytical, kind):
    """On this process's devices, one scoring shard and a service answer
    equal the flat ``score`` call bit for bit — the 1-device leg of the
    parity matrix, the non-rectangular sweep's spliced call included."""
    specs = _specs()
    if kind == "frontier":
        product, call = pack_frontier(specs, BASE, MIX), "score_frontier"
    else:
        workloads = {"sweep": _workloads(), "one_row": [BASE],
                     "ragged": _ragged_workloads()}[kind]
        product = pack_sweep(specs, workloads, [MIX] * len(workloads))
        call = "score_sweep"
        assert product.rectangular == (kind != "ragged")
    flat = product.score(hw_analytical)
    pool = ScoringShardPool(1)
    try:
        pooled, used = getattr(pool, call)(product, hw_analytical)
    finally:
        pool.close()
    assert used == 1
    assert np.array_equal(pooled, flat)
    with DesignCalculatorService([hw_analytical], scoring_shards=1) as svc:
        if kind == "frontier":
            answer = svc.what_if_design(specs[0], specs[1], BASE,
                                        hw_analytical, MIX)
            served = [answer.baseline_seconds, answer.variant_seconds]
            flat = pack_frontier(specs[:2], BASE, MIX).score(hw_analytical)
        else:
            served = svc.workload_sweep(specs, workloads, hw_analytical,
                                        [MIX] * len(workloads)).totals
        assert svc.stats()["shard_dispatches"] == 0
    assert np.array_equal(np.asarray(served), flat)


@pytest.mark.parametrize("kind", ["frontier", "sweep"])
def test_score_entry_points_refuse_shard_true(hw_analytical, kind):
    """The fused entry points keep ``shard`` only as ``False`` (the flat
    call); ``shard=True`` is refused and names the shard pool."""
    if kind == "frontier":
        product = pack_frontier(_specs(), BASE, MIX)
        entry, n = devicecost.score_frontier, product.n_segments
        args, kwargs = product._fused_arrays(), {}
    else:
        product = pack_sweep(_specs(), _workloads(), [MIX] * 5)
        entry, n = devicecost.score_sweep, product.n_designs
        host_ids, args = product._sweep_arrays()
        kwargs = {"host_ids": host_ids}
    with pytest.raises(ValueError, match="ScoringShardPool"):
        entry(*args, n, hw_analytical, shard=True, **kwargs)
    assert np.array_equal(
        entry(*args, n, hw_analytical, shard=False, **kwargs),
        product.score(hw_analytical))


def test_pool_degenerate_is_plain_score(hw_analytical):
    pool = ScoringShardPool(1)
    assert pool.n_shards == 1
    packed = pack_frontier(_specs(), BASE, MIX)
    totals, used = pool.score_frontier(packed, hw_analytical)
    assert used == 1
    assert np.array_equal(totals, packed.score(hw_analytical))
    sweep = pack_sweep(_specs(), _workloads(), [MIX] * 5)
    grid, used = pool.score_sweep(sweep, hw_analytical)
    assert used == 1
    assert np.array_equal(grid, sweep.score(hw_analytical))


def test_one_shard_pool_places_no_banks_here(hw_analytical):
    """One shard, with no deadline and no fault plan, scores in the
    calling thread on the default device: no banks are committed to a
    device for it."""
    placed = set(devicecost._BANK_REPLICAS.keys())
    pool = ScoringShardPool(1)
    try:
        pool.score_sweep(pack_sweep(_specs(), _workloads(), [MIX] * 5),
                         hw_analytical)
    finally:
        pool.close()
    assert set(devicecost._BANK_REPLICAS.keys()) == placed


def test_pool_abort_when_probe_reports_dead(hw_analytical):
    pool = ScoringShardPool(1)
    packed = pack_frontier(_specs(), BASE, MIX)
    totals, used = pool.score_frontier(packed, hw_analytical,
                                       before_dispatch=lambda i: False)
    assert totals is None and used == 0


# ---------------------------------------------------------------------------
# Satellite 3: the multi-device parity matrix (subprocess per count)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_devices", [
    pytest.param(2, marks=pytest.mark.devices(2)),
    pytest.param(8, marks=pytest.mark.devices(8)),
    pytest.param(48, marks=[pytest.mark.devices(48), pytest.mark.slow]),
])
def test_sharded_parity_under_devices(n_devices, device_count):
    assert device_count == n_devices
    hw = hw1()
    specs, workloads = _specs(), _workloads()
    mixes = [MIX] * len(workloads)
    packed = pack_frontier(specs, BASE, MIX)
    flat = packed.score(hw)
    sweep = pack_sweep(specs, workloads, mixes)
    grid = sweep.score(hw)
    # 1e-6 against the grouped oracle, like every other engine pairing
    np.testing.assert_allclose(grid, sweep.score(hw, engine="grouped"),
                               rtol=1e-6)

    # the shard pool partitions and merges bit-identically
    pool = ScoringShardPool(min_cells_per_shard=1)
    assert pool.n_shards == n_devices
    totals, used = pool.score_frontier(packed, hw)
    assert used > 1
    assert np.array_equal(totals, flat)
    pooled, used = pool.score_sweep(sweep, hw)
    assert used > 1
    assert np.array_equal(pooled, grid)
    assert np.array_equal(np.argmin(pooled, axis=1), np.argmin(grid, axis=1))

    # zero recompiles on repeat pool scores AND hardware swaps
    pool.score_sweep(sweep, hw3())   # warm both tables on every device
    before = devicecost.trace_count()
    for _ in range(3):
        assert np.array_equal(pool.score_sweep(sweep, hw)[0], grid)
        pool.score_sweep(sweep, hw3())
    pool.score_frontier(packed, hw)
    assert devicecost.trace_count() == before

    # a single-row sweep splits on designs like any other, same grid
    one_row = pack_sweep(specs, [BASE], [MIX])
    assert np.array_equal(pool.score_sweep(one_row, hw)[0],
                          one_row.score(hw))
    pool.close()

    # identical answers run directly and through a service with one
    # scoring shard per device
    answer_flat = whatif.workload_sweep(specs, workloads, hw, mixes)
    best_flat = complete_design((), BASE, hw, mix=MIX, max_depth=2)
    batchcost.clear_caches()
    with DesignCalculatorService([hw], scoring_shards=n_devices,
                                 shard_min_cells=1) as service:
        answer_pooled = service.workload_sweep(specs, workloads, hw, mixes)
        best_pooled = service.complete_design((), BASE, hw, mix=MIX,
                                              max_depth=2)
        assert service.stats()["shard_dispatches"] >= 2
    assert np.array_equal(answer_pooled.totals, answer_flat.totals)
    assert best_pooled.spec == best_flat.spec
    assert best_pooled.cost_seconds == best_flat.cost_seconds


@pytest.mark.devices(2)
def test_one_shard_service_stays_on_one_device(device_count):
    """A service given one scoring shard scores on one device, however
    many the process sees: no part is dispatched, and no parameter banks
    are placed on a second device."""
    assert device_count == 2
    hw = hw1()
    specs, workloads = _specs(), _workloads()
    mixes = [MIX] * len(workloads)
    placed = set(devicecost._BANK_REPLICAS.keys())
    with DesignCalculatorService([hw], scoring_shards=1,
                                 shard_min_cells=1) as service:
        answer = service.workload_sweep(specs, workloads, hw, mixes)
        assert service.stats()["shard_dispatches"] == 0
    assert np.array_equal(
        answer.totals, pack_sweep(specs, workloads, mixes).score(hw))
    new = set(devicecost._BANK_REPLICAS.keys()) - placed
    assert all(len(k) == 3 and k[1] == "device" for k in new)
    assert len({k[2] for k in new}) <= 1


@pytest.mark.devices(2)
def test_service_routes_across_scoring_shards(device_count):
    """A mixed window served across >= 2 scoring shards: bit-identical
    answers, shard dispatches counted, PR 6 deadlines intact."""
    assert device_count == 2
    hw = hw1()
    specs, workloads = _specs(), _workloads()
    mixes = [MIX] * len(workloads)
    oracle = whatif.workload_sweep(specs, workloads, hw, mixes)
    service = DesignCalculatorService(
        [hw], scoring_shards=2, shard_min_cells=1, window_s=0.02)
    try:
        futures = [service.submit_sweep(specs, workloads, hw, mixes)
                   for _ in range(2)]
        futures.append(service.submit_design(
            el.spec_btree(), el.spec_array(1), BASE, hw, MIX))
        answers = [f.result(timeout=60) for f in futures]
        for sweep_answer in answers[:2]:
            assert np.array_equal(sweep_answer.totals, oracle.totals)
        direct = whatif.what_if_design(
            el.spec_btree(), el.spec_array(1), BASE, hw, MIX)
        np.testing.assert_allclose(answers[2].baseline_seconds,
                                   direct.baseline_seconds, rtol=1e-12)
        stats = service.stats()
        assert stats["shard_dispatches"] >= 2
        # deadline composition: an already-expired request fails fast
        # with DeadlineExceeded instead of occupying a sharded call
        doomed = service.submit_sweep(specs, workloads, hw, mixes,
                                      deadline_s=1e-9)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
    finally:
        service.stop()
