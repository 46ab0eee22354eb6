"""Workload-generalized frontier packing + the batched workload-sweep
engine (PR 5).

Every (design, workload) cell of a sweep must match the scalar oracle;
the grouped-engine grid must match the per-workload ``cost_many`` loop
bit for bit; repeat sweeps must be pure cache hits with zero fused-kernel
recompiles; degenerate and non-rectangular sweeps must degrade
gracefully; and the serving engine must coalesce sweep requests like the
PR-4 question kinds.
"""
import dataclasses
import threading

import numpy as np
import pytest

from repro.core import batchcost, devicecost, elements as el, whatif
from repro.core.autocomplete import (complete_design, design_continuum,
                                     default_candidates, default_terminals,
                                     enumerate_completions)
from repro.core.batchcost import (concat_sweeps, cost_many, cost_sweep,
                                  normalize_points, pack_sweep)
from repro.core.hardware import hw1, hw3
from repro.core.synthesis import Workload, cost_workload
from repro.serving import DesignCalculatorService

BASE = Workload(n_entries=150_000, n_queries=100)


def _axis():
    """A realistic sweep axis: read fraction, skew, selectivity and query
    count all vary; the data size stays fixed (the rectangular case)."""
    workloads = [
        BASE,
        dataclasses.replace(BASE, zipf_alpha=0.8),
        dataclasses.replace(BASE, zipf_alpha=1.6, n_queries=1000),
        dataclasses.replace(BASE, selectivity=0.01),
        dataclasses.replace(BASE, zipf_alpha=0.4, selectivity=0.005),
    ]
    mixes = [
        {"get": 100.0},
        {"get": 80.0, "update": 20.0},
        {"get": 50.0, "update": 50.0},
        {"get": 60.0, "range_get": 30.0, "update": 10.0},
        {"get": 20.0, "range_get": 10.0, "update": 60.0,
         "bulk_load": 1.0},
    ]
    return workloads, mixes


def _frontier(depth: int = 2):
    return list(enumerate_completions((), default_candidates(),
                                      default_terminals(), depth, "sweep"))


def test_every_sweep_cell_matches_scalar_oracle(hw_analytical):
    """The acceptance contract: all (design, workload) cells of a fused
    sweep at 1e-6 of the per-cell scalar expert system."""
    workloads, mixes = _axis()
    specs = _frontier()
    grid = cost_sweep(specs, workloads, hw_analytical, mixes)
    assert grid.shape == (len(workloads), len(specs))
    scalar = np.asarray(
        [[cost_workload(s, w, hw_analytical, m) for s in specs]
         for w, m in zip(workloads, mixes)])
    np.testing.assert_allclose(grid, scalar, rtol=1e-6)
    # argmin per point — the continuum — agrees with the oracle
    assert np.array_equal(np.argmin(grid, axis=1),
                          np.argmin(scalar, axis=1))


def test_sweep_matches_per_workload_cost_many_exactly(hw_analytical):
    """The grouped-engine grid is BIT-identical to looping ``cost_many``
    per workload (same segments, same float64 accumulation order); the
    fused grid matches the fused loop to the engines' shared f32
    tolerance."""
    workloads, mixes = _axis()
    specs = _frontier()
    grid_g = cost_sweep(specs, workloads, hw_analytical, mixes,
                        engine="grouped")
    loop_g = np.stack([cost_many(specs, w, hw_analytical, m,
                                 engine="grouped")
                       for w, m in zip(workloads, mixes)])
    np.testing.assert_array_equal(grid_g, loop_g)
    grid_f = cost_sweep(specs, workloads, hw_analytical, mixes)
    loop_f = np.stack([cost_many(specs, w, hw_analytical, m)
                       for w, m in zip(workloads, mixes)])
    np.testing.assert_allclose(grid_f, loop_f, rtol=1e-6)


def test_degenerate_sweeps(hw_analytical):
    """1-workload and 0-design sweeps work end to end; 0 workloads and
    mismatched mixes are explicit errors."""
    w = Workload(n_entries=50_000)
    specs = [el.spec_btree(), el.spec_trie()]
    one = cost_sweep(specs, [w], hw_analytical)
    assert one.shape == (1, 2)
    np.testing.assert_allclose(one[0], cost_many(specs, w, hw_analytical),
                               rtol=0)
    empty = pack_sweep([], [w, dataclasses.replace(w, zipf_alpha=1.0)])
    assert empty.n_designs == 0
    for engine in ("fused", "grouped"):
        assert empty.score(hw_analytical, engine=engine).shape == (2, 0)
    with pytest.raises(ValueError, match="at least one workload"):
        pack_sweep(specs, [])
    with pytest.raises(ValueError, match="mixes"):
        pack_sweep(specs, [w], [{"get": 1.0}, {"get": 2.0}])
    with pytest.raises(ValueError, match="unknown engine"):
        pack_sweep(specs, [w]).score(hw_analytical, engine="bogus")


def test_repeat_sweeps_zero_recompiles_and_pure_cache_hits(hw_analytical):
    """Steady-state contract: a repeated sweep is one sweep-cache hit and
    one fused dispatch — no re-packing, no statics recompute, and zero
    XLA retraces, including across a what-if-hardware profile swap."""
    workloads, mixes = _axis()
    specs = _frontier()
    first = pack_sweep(specs, workloads, mixes)
    cost_sweep(specs, workloads, hw_analytical, mixes)   # warm the shape
    variant = hw3()
    cost_sweep(specs, workloads, variant, mixes)
    traces = devicecost.trace_count()
    info_before = batchcost.cache_info()
    for _ in range(3):
        cost_sweep(specs, workloads, hw_analytical, mixes)
    cost_sweep(specs, workloads, variant, mixes)         # pure table swap
    assert devicecost.trace_count() == traces
    assert pack_sweep(specs, workloads, mixes) is first
    info = batchcost.cache_info()
    # repeats are served whole from the sweep memo: no new misses in any
    # packing layer beneath it
    assert {k: v.misses for k, v in info.items()} == \
        {k: v.misses for k, v in info_before.items()}


def test_sweep_statics_shared_across_workloads(hw_analytical):
    """The PR-5 cache-key refactor, observable: packing one chain set
    under many same-structure workloads resolves template statics ONCE
    per chain, and every point's frontier references the *same* ids
    array (only the numeric sizes/weights rows are per-workload).  A
    later ``pack_frontier`` at a sweep point scores equal to the
    sweep's row."""
    batchcost.clear_caches()
    workloads, _ = _axis()
    # one op set across all points (the read/write-ratio axis), so every
    # point lands in one (ops, depth signature) group
    mixes = whatif.read_fraction_mixes((1.0, 0.8, 0.6, 0.4, 0.2))
    specs = [el.spec_btree(), el.spec_hash_table(), el.spec_skip_list()]
    sweep = pack_sweep(specs, workloads, mixes)
    info = batchcost.cache_info()
    # one statics entry per distinct chain — NOT per (chain, workload)
    assert info["chain_statics"].currsize == len(specs)
    ids0 = sweep.frontiers[0].ids
    for f in sweep.frontiers[1:]:
        assert f.ids is ids0, "every point must share one ids array"
        assert f.tile_segments is sweep.frontiers[0].tile_segments
    assert sweep.rectangular
    # a later single-point pack_frontier, packed afresh through the
    # per-chain segment path, scores equal to the sweep's row
    batchcost.clear_caches()
    packed = batchcost.pack_frontier(specs, workloads[1], mixes[1])
    np.testing.assert_array_equal(packed.score(hw_analytical,
                                               engine="grouped"),
                                  sweep.score(hw_analytical,
                                              engine="grouped")[1])
    np.testing.assert_allclose(packed.score(hw_analytical),
                               sweep.score(hw_analytical)[1], rtol=1e-6)


def test_sweep_repacks_only_missing_points(hw_analytical):
    """Sweeps and single-point calls agree: a sweep over a point already
    warmed by ``cost_many`` packs that point's row bit for bit as
    ``cost_many`` packed it, without looking up the per-chain segment
    cache."""
    batchcost.clear_caches()
    w1 = BASE
    w2 = dataclasses.replace(BASE, zipf_alpha=0.9)
    specs = [el.spec_btree(), el.spec_trie()]
    row1 = cost_many(specs, w1, hw_analytical, engine="grouped")
    warm = batchcost.pack_frontier(specs, w1)
    lookups = batchcost.cache_info()["packed_spec"]
    sweep = pack_sweep(specs, [w1, w2])
    info = batchcost.cache_info()["packed_spec"]
    assert (info.hits, info.misses) == (lookups.hits, lookups.misses)
    row = sweep.frontiers[0]
    for name in ("ids", "sizes", "weights", "tile_segments"):
        np.testing.assert_array_equal(getattr(row, name),
                                      getattr(warm, name))
    assert row.n_segments == warm.n_segments
    np.testing.assert_array_equal(
        sweep.score(hw_analytical, engine="grouped")[0], row1)


def _grid_cases():
    """(specs, workloads, mixes) per case of the grid-path test."""
    designs = _frontier()[:24]
    skewed = [dataclasses.replace(BASE, zipf_alpha=a)
              for a in (0.0, 0.5, 0.99)]
    small, big = Workload(n_entries=10_000), Workload(n_entries=4_000_000)
    return {
        "get_update": (designs, skewed,
                       whatif.read_fraction_mixes((0.95, 0.5, 0.05))),
        "range_put": (designs, skewed,
                      [{"range_get": 95.0, "put": 5.0},
                       {"range_get": 70.0, "put": 30.0},
                       {"range_get": 50.0, "put": 50.0}]),
        "duplicates": (designs[:6] + designs[:3] + [designs[4]] * 3,
                       skewed[:2], None),
        "two_depths": (designs[:10],
                       [small, big, dataclasses.replace(small,
                                                        zipf_alpha=0.9),
                        dataclasses.replace(big, zipf_alpha=0.9)],
                       {"get": 60.0, "update": 40.0}),
    }


@pytest.mark.parametrize("case", ["get_update", "range_put", "duplicates",
                                  "two_depths"])
def test_grid_sweep_equals_stacked_pack_frontier(case):
    """The grid path packs every point's frontier bit for bit as a cold
    per-point ``pack_frontier`` does (ids, sizes, weights, tile
    segments); the points of one group share one ids array; it counts
    designs x points grid cells and never looks up the per-chain segment
    cache."""
    from repro.core import spans
    specs, workloads, mixes = _grid_cases()[case]
    batchcost.clear_caches()
    cells = spans.grid_cells()
    sweep = pack_sweep(specs, workloads, mixes)
    assert spans.grid_cells() - cells == len(specs) * len(workloads)
    info = batchcost.cache_info()["packed_spec"]
    assert info.hits + info.misses == 0
    points = normalize_points(workloads, mixes)
    by_ids = {}
    for pi, (w, mix_items) in enumerate(points):
        row = sweep.frontiers[pi]
        assert not row.sizes.flags.writeable
        by_ids.setdefault(id(row.ids), []).append(w.n_entries)
        batchcost.clear_caches()
        cold = batchcost.pack_frontier(specs, w, dict(mix_items))
        for name in ("ids", "sizes", "weights", "tile_segments"):
            np.testing.assert_array_equal(getattr(row, name),
                                          getattr(cold, name))
        assert row.n_segments == cold.n_segments == len(specs)
    # one ids object per (ops, depth signature) group
    groups = {w.n_entries for w, _ in points}
    assert len(by_ids) == len(groups)
    assert all(len(set(sizes)) == 1 for sizes in by_ids.values())
    assert sweep.rectangular == (len(groups) == 1)


def test_sweep_pad_rows_reference_real_model_ids(hw_analytical):
    """Bucket padding must repeat a real model id, never a blind 0: the
    scorer's availability check runs on the padded array, and a profile
    without a fitted model for the first-interned name must not reject
    sweeps that never use it."""
    sweep = pack_sweep([el.spec_btree()] * 5, [BASE])   # 80 -> bucket 128
    host_ids, _ = sweep._sweep_arrays()
    n = len(sweep.frontiers[0].ids)
    assert len(host_ids) > n, "pick a frontier that actually pads"
    assert (host_ids[n:] == host_ids[n - 1]).all()
    assert set(np.unique(host_ids)) <= set(np.unique(host_ids[:n]))


def test_mix_only_sweep_shares_sizes(hw_analytical):
    """A pure read/write-ratio sweep (one workload, varying mixes) shares
    every size column — only the mix weights differ across points."""
    mixes = whatif.read_fraction_mixes((1.0, 0.75, 0.5, 0.25, 0.0))
    sweep = pack_sweep([el.spec_btree(), el.spec_trie()],
                       [BASE] * len(mixes), mixes)
    assert sweep.rectangular
    f0 = sweep.frontiers[0]
    for f in sweep.frontiers[1:]:
        np.testing.assert_array_equal(f.sizes, f0.sizes)
    assert not np.array_equal(sweep.frontiers[0].weights,
                              sweep.frontiers[-1].weights)


def test_non_rectangular_sweep_degrades_gracefully(hw_analytical):
    """Data-size axes that change a chain's expansion depths cannot share
    a record layout; the sweep falls back to per-point frontiers spliced
    into one flat fused call — same grid contract, same oracle parity."""
    workloads = [Workload(n_entries=10_000),
                 Workload(n_entries=4_000_000)]
    specs = [el.spec_btree(), el.spec_hash_table()]
    sweep = pack_sweep(specs, workloads)
    assert not sweep.rectangular
    grid = sweep.score(hw_analytical)
    scalar = np.asarray(
        [[cost_workload(s, w, hw_analytical) for s in specs]
         for w in workloads])
    np.testing.assert_allclose(grid, scalar, rtol=1e-6)


def test_workload_sweep_answer_and_continuum(hw_analytical):
    """whatif.workload_sweep: grid + best-per-point accessors match the
    scalar-engine answer; design_continuum matches per-point
    complete_design exactly (same frontier, same argmin)."""
    workloads = [BASE, dataclasses.replace(BASE, zipf_alpha=1.2)]
    mixes = whatif.read_fraction_mixes((0.9, 0.3))
    specs = [el.spec_btree(), el.spec_hash_table(), el.spec_trie()]
    ans = whatif.workload_sweep(specs, workloads, hw_analytical, mixes)
    oracle = whatif.workload_sweep(specs, workloads, hw_analytical, mixes,
                                   engine="scalar")
    np.testing.assert_allclose(ans.totals, oracle.totals, rtol=1e-6)
    assert np.array_equal(ans.best_indices, oracle.best_indices)
    for i, (point, spec, cost) in enumerate(ans.continuum()):
        assert point == ans.points[i]
        assert spec is specs[int(ans.best_indices[i])]
        assert cost == float(ans.totals[i].min())
    assert "2 workloads x 3 designs" in ans.summary()

    results = design_continuum((), workloads, hw_analytical, mixes=mixes,
                               max_depth=2)
    for w, m, r in zip(workloads, mixes, results):
        single = complete_design((), w, hw_analytical, mix=m, max_depth=2)
        assert r.cost_seconds == pytest.approx(single.cost_seconds,
                                               rel=1e-9)
        assert r.spec.describe() == single.spec.describe()
        assert r.explored == single.explored


def test_serving_sweep_matches_direct_and_coalesces():
    """The service's sweep kind: answers match the direct engine, sweeps
    over the same point axis submitted in one window coalesce into one
    fused call, and session repeats hit the pinned sweep."""
    h1, h3 = hw1(), hw3()
    workloads = [BASE, dataclasses.replace(BASE, zipf_alpha=1.0)]
    mixes = whatif.read_fraction_mixes((1.0, 0.5))
    a = [el.spec_btree(), el.spec_trie()]
    b = [el.spec_skip_list()]
    direct_a = whatif.workload_sweep(a, workloads, h1, mixes)
    direct_b = whatif.workload_sweep(b, workloads, h1, mixes)
    with DesignCalculatorService([h1, h3], window_s=0.5) as svc:
        fut_a = svc.submit_sweep(a, workloads, h1, mixes)
        fut_b = svc.submit_sweep(b, workloads, h1, mixes)
        got_a, got_b = fut_a.result(), fut_b.result()
        stats = svc.stats()
        assert stats["sweeps"] == 2 and stats["failed"] == 0
        # both sweeps share the point axis -> one spliced fused call
        assert stats["score_calls"] == 1 and stats["coalesced"] == 2
        sess = svc.session("sweeper")
        sess.workload_sweep(a, workloads, h1, mixes)
        sess.workload_sweep(a, workloads, h1, mixes)
        assert svc.stats()["session_frontier_hits"] == 1
    np.testing.assert_allclose(got_a.totals, direct_a.totals, rtol=1e-9)
    np.testing.assert_allclose(got_b.totals, direct_b.totals, rtol=1e-9)
    assert got_a.question == direct_a.question


def test_serving_sweep_failure_isolation():
    """A sweep against an unregistered profile name fails its own future
    without poisoning the window's other requests."""
    h1 = hw1()
    workloads = [BASE]
    with DesignCalculatorService([h1]) as svc:
        ok = svc.submit_sweep([el.spec_btree()], workloads, h1)
        with pytest.raises(KeyError, match="unregistered"):
            svc.submit_sweep([el.spec_btree()], workloads, "nope")
        assert ok.result().totals.shape == (1, 1)


def test_concat_sweeps_contract(hw_analytical):
    """Splicing sweeps along the design axis scores identically to
    packing the concatenated spec list; mismatched point axes are
    rejected."""
    workloads = [BASE, dataclasses.replace(BASE, zipf_alpha=0.7)]
    a = [el.spec_btree(), el.spec_hash_table()]
    b = [el.spec_trie()]
    spliced = concat_sweeps([pack_sweep(a, workloads),
                             pack_sweep(b, workloads)])
    scratch = pack_sweep(a + b, workloads)
    np.testing.assert_array_equal(spliced.score(hw_analytical),
                                  scratch.score(hw_analytical))
    with pytest.raises(ValueError, match="different workload points"):
        concat_sweeps([pack_sweep(a, workloads),
                       pack_sweep(b, [BASE])])
    with pytest.raises(ValueError, match="at least one sweep"):
        concat_sweeps([])
