"""Template-vectorized synthesis + incremental frontier packing (PR 3).

Record-level parity against the scalar expert system, symbolic-breakdown
schema conformance, incremental packing/splicing equivalence, and the
hill-climb/beam seen-set.
"""
import inspect

import numpy as np
import pytest

from repro.core import (autocomplete, batchcost, elements as el, synthesis,
                        templatecost, whatif)
from repro.core.autocomplete import (design_beam, design_hillclimb,
                                     design_neighbors, default_candidates,
                                     default_terminals,
                                     enumerate_completions)
from repro.core.batchcost import (compile_breakdown, concat_frontiers,
                                  cost_many, pack_frontier)
from repro.core.devicecost import model_id
from repro.core.synthesis import Workload, cost_workload

OPS = ("get", "range_get", "update", "bulk_load", "put")


def _grid_specs():
    specs = []
    for name, make in sorted(el.ALL_PAPER_SPECS.items()):
        sig = inspect.signature(make)
        specs.append(make(10_000) if "n_puts" in sig.parameters else make())
    return specs


WORKLOADS = [
    Workload(n_entries=10_000),
    Workload(n_entries=250_000, zipf_alpha=1.5),
    Workload(n_entries=1_000_000, selectivity=0.01, n_queries=1000),
]


@pytest.mark.parametrize("workload", WORKLOADS,
                         ids=["uniform", "zipf", "ranges"])
@pytest.mark.parametrize("op", OPS)
def test_vectorized_records_match_scalar_synthesis(workload, op):
    """The strongest parity contract: for every paper spec the vectorized
    packer must emit the *same records* as the scalar pipeline — identical
    model-id sequence, sizes/counts to 1e-12 — once count-0 rows (records
    the scalar walker skips) and tile pads are dropped."""
    specs = _grid_specs()
    segs = templatecost.pack_specs([s.chain for s in specs], workload,
                                   ((op, 1.0),))
    for spec, (ids, sizes, weights) in zip(specs, segs):
        comp = compile_breakdown(
            synthesis.synthesize_operation(op, spec, workload))
        m = weights != 0.0
        assert np.array_equal(ids[m], comp.model_ids), (spec.name, op)
        np.testing.assert_allclose(sizes[m], comp.sizes, rtol=1e-12)
        np.testing.assert_allclose(weights[m], comp.counts, rtol=1e-12)


@pytest.mark.parametrize("op", OPS)
def test_emission_matches_symbolic_breakdown(op):
    """Each chain's emitted layout (count-0 slots included) must equal the
    symbolic record schema synthesis.py declares for its template."""
    w = Workload(n_entries=77_000)
    specs = _grid_specs()
    segs = templatecost.pack_specs([s.chain for s in specs], w,
                                   ((op, 1.0),))
    for spec, (ids, _, _) in zip(specs, segs):
        geom = templatecost.chain_geometry(spec.chain, w)
        schema = synthesis.symbolic_breakdown(op, geom.template)
        assert np.array_equal(ids[:len(schema)],
                              [model_id(l2) for _, l2 in schema]), spec.name


def test_chains_share_templates_across_parameters():
    """The point of template grouping: parameter mutations (fanout and
    capacity doublings — the hill-climb neighborhood) and sibling elements
    taking the same synthesis branches (B+ vs CSB+) share one template and
    therefore one symbolic breakdown."""
    w = Workload(n_entries=1_000_000)
    t = lambda spec: templatecost.chain_geometry(spec.chain, w).template
    assert t(el.spec_btree(fanout=20)) == t(el.spec_btree(fanout=21))
    assert t(el.spec_btree()) == t(el.spec_csb_tree())
    assert t(el.spec_btree(page=256)) == t(el.spec_btree(page=512))
    # different branch classes are different templates
    assert t(el.spec_btree()) != t(el.spec_hash_table())
    # recursion depth changes the expanded level sequence
    assert t(el.spec_btree(fanout=20)) != t(el.spec_btree(fanout=2))


def test_statics_workload_independent():
    """Element statics (node bytes included) are cached per element value
    across workloads — the record-parity grid above would catch a workload
    dependence sneaking into _node_bytes."""
    e = el.btree_internal(20)
    st = templatecost.statics_of(e)
    assert templatecost.statics_of(el.btree_internal(20)) is st
    assert templatecost.statics_of(el.btree_internal(40)) is not st


def test_concat_frontiers_matches_from_scratch_pack(hw_analytical):
    """Splicing retained frontiers must score identically (bit-for-bit
    segments, only design numbering shifts) to packing the concatenated
    spec list from scratch."""
    w = Workload(n_entries=300_000)
    mix = {"get": 10.0, "update": 5.0}
    a = [el.spec_btree(), el.spec_hash_table()]
    b = [el.spec_skip_list(), el.spec_trie(), el.spec_btree(fanout=40)]
    spliced = concat_frontiers([pack_frontier(a, w, mix),
                                pack_frontier(b, w, mix)])
    scratch = pack_frontier(a + b, w, mix)
    assert spliced.n_segments == scratch.n_segments == len(a) + len(b)
    np.testing.assert_array_equal(spliced.ids, scratch.ids)
    np.testing.assert_array_equal(spliced.sizes, scratch.sizes)
    np.testing.assert_array_equal(spliced.weights, scratch.weights)
    np.testing.assert_array_equal(spliced.tile_segments,
                                  scratch.tile_segments)
    for engine, rtol in (("grouped", 1e-9), ("fused", 1e-6)):
        sp = spliced.score(hw_analytical, engine=engine)
        sc = scratch.score(hw_analytical, engine=engine)
        np.testing.assert_allclose(sp, sc, rtol=rtol)
        assert int(np.argmin(sp)) == int(np.argmin(sc))


def test_incremental_hillclimb_rounds_parity(hw_analytical):
    """Across simulated hill-climb rounds, packing each round's frontier
    with warm segment caches (splicing) must score identically to packing
    the same frontier in a fresh cache state."""
    w = Workload(n_entries=500_000)
    mix = {"get": 60.0, "update": 40.0}
    candidates = default_candidates()
    terminals = default_terminals()
    spec = el.spec_btree()
    batchcost.clear_caches()
    for _ in range(3):
        frontier = design_neighbors(spec.chain, candidates, terminals)
        warm = pack_frontier(frontier, w, mix)
        warm_grouped = warm.score(hw_analytical, engine="grouped")
        warm_fused = warm.score(hw_analytical)
        saved = (batchcost._segment_cache, batchcost._frontier_cache)
        try:
            # fresh caches: everything synthesizes from scratch
            batchcost._segment_cache = batchcost._DictCache(maxsize=65536)
            batchcost._frontier_cache = batchcost._DictCache(maxsize=16)
            cold = pack_frontier(frontier, w, mix)
        finally:
            batchcost._segment_cache, batchcost._frontier_cache = saved
        cold_grouped = cold.score(hw_analytical, engine="grouped")
        np.testing.assert_allclose(warm_grouped, cold_grouped, rtol=1e-9)
        np.testing.assert_allclose(warm_fused, cold_grouped, rtol=1e-6)
        assert int(np.argmin(warm_fused)) == int(np.argmin(warm_grouped))
        spec = frontier[int(np.argmin(warm_grouped))]
    scalar = [cost_workload(s, w, hw_analytical, mix) for s in frontier]
    np.testing.assert_allclose(warm_grouped, scalar, rtol=1e-9)


def test_what_if_design_splice_matches_two_design_pack(hw_analytical):
    """what_if_design splices two independently-packed one-design
    frontiers; the answer must match both the two-design pack and the
    scalar oracle."""
    w = Workload(n_entries=400_000)
    mix = {"get": 20.0}
    base = el.spec_hash_table()
    variant = whatif.add_bloom_filters(base)
    ans = whatif.what_if_design(base, variant, w, hw_analytical, mix)
    both = cost_many([base, variant], w, hw_analytical, mix)
    assert ans.baseline_seconds == pytest.approx(float(both[0]), rel=1e-9)
    assert ans.variant_seconds == pytest.approx(float(both[1]), rel=1e-9)
    scalar = whatif.what_if_design(base, variant, w, hw_analytical, mix,
                                   engine="scalar")
    assert ans.baseline_seconds == pytest.approx(
        scalar.baseline_seconds, rel=1e-6)
    assert ans.variant_seconds == pytest.approx(
        scalar.variant_seconds, rel=1e-6)
    assert ans.beneficial == scalar.beneficial


def test_hillclimb_never_recosts_a_chain(hw_analytical, monkeypatch):
    """The seen-set contract: across all rounds of a climb, no chain
    reaches the costing engine twice, and ``designs_costed`` counts
    exactly the unique designs costed."""
    costed = []
    real = autocomplete.cost_many

    def recording(specs, *args, **kwargs):
        costed.extend(s.chain for s in specs)
        return real(specs, *args, **kwargs)

    monkeypatch.setattr(autocomplete, "cost_many", recording)
    w = Workload(n_entries=200_000)
    result = design_hillclimb(w, hw_analytical, {"get": 60.0, "update": 40.0},
                              max_steps=10)
    assert len(costed) == len(set(costed)), "a chain was costed twice"
    assert result["designs_costed"] == len(costed)
    assert result["designs_costed"] > 1


def test_hillclimb_engines_agree_after_seen_set(hw_analytical):
    w = Workload(n_entries=200_000)
    mix = {"get": 60.0, "update": 40.0}
    f = design_hillclimb(w, hw_analytical, mix, max_steps=10)
    s = design_hillclimb(w, hw_analytical, mix, max_steps=10, batched=False)
    assert (f["design"], f["fanouts"]) == (s["design"], s["fanouts"])
    assert f["cost_s"] == pytest.approx(s["cost_s"], rel=1e-6)
    assert f["designs_costed"] == s["designs_costed"]


def test_design_beam_improves_and_engines_agree(hw_analytical):
    """Beam search must do at least as well as the greedy climb from the
    same start, and its answer must agree across costing engines."""
    w = Workload(n_entries=200_000)
    mix = {"get": 60.0, "update": 40.0}
    climb = design_hillclimb(w, hw_analytical, mix, max_steps=10)
    beam = design_beam(w, hw_analytical, mix, beam_width=4, max_rounds=6)
    assert beam["cost_s"] <= climb["cost_s"] * (1 + 1e-6)
    assert beam["designs_costed"] >= climb["designs_costed"]
    grouped = design_beam(w, hw_analytical, mix, beam_width=4,
                          max_rounds=6, engine="grouped")
    assert beam["cost_s"] == pytest.approx(grouped["cost_s"], rel=1e-6)
    scalar = design_beam(w, hw_analytical, mix, beam_width=4,
                         max_rounds=6, batched=False)
    assert grouped["cost_s"] == pytest.approx(scalar["cost_s"], rel=1e-9)


def test_frontier_cache_serves_repacks_and_bounds_memory(hw_analytical):
    batchcost.clear_caches()
    w = Workload(n_entries=50_000)
    specs = [el.spec_btree(), el.spec_trie()]
    p1 = pack_frontier(specs, w, None)
    assert pack_frontier(specs, w, None) is p1
    # a different mix is a different frontier
    p2 = pack_frontier(specs, w, {"get": 3.0})
    assert p2 is not p1
    info = batchcost.cache_info()
    assert info["frontier"].maxsize is not None  # bounded, evicts oldest


@pytest.mark.slow
def test_large_frontier_template_pack_matches_scalar(hw_analytical):
    """Benchmark-grade frontier (full depth-4 enumeration, >3000 unique
    chains): template-vectorized packing must match the per-design scalar
    path to 1e-9 totals with the identical argmin design."""
    w = Workload(n_entries=1_000_000)
    mix = {"get": 80.0, "update": 20.0}
    frontier = enumerate_completions((), default_candidates(),
                                     default_terminals(), 4, "big")
    batchcost.clear_caches()
    grouped = cost_many(frontier, w, hw_analytical, mix, engine="grouped")
    sample = np.linspace(0, len(frontier) - 1, 37).astype(int)
    scalar = [cost_workload(frontier[i], w, hw_analytical, mix)
              for i in sample]
    np.testing.assert_allclose(grouped[sample], scalar, rtol=1e-9)
    fused = cost_many(frontier, w, hw_analytical, mix)
    np.testing.assert_allclose(fused, grouped, rtol=1e-6)
    assert int(np.argmin(fused)) == int(np.argmin(grouped))
