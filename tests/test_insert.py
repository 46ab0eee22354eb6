"""Puts (inserts of new keys): the scalar oracle, the fused emission and
the served sweep path (CPU).

``synthesis.synthesize_put`` prices a put as an unskewed point-lookup
miss, an in-page write and amortised node splits that cascade up the
sorted internal levels; ``templatecost`` emits the same records for
every chain at once, with count 0 in the split slots above where the
cascade stops.
"""
import dataclasses
import hashlib
import itertools

import numpy as np
import pytest

from repro.core import elements as el
from repro.core import access, synthesis, templatecost
from repro.core.batchcost import compile_breakdown, pack_frontier
from repro.core.devicecost import _MODEL_NAMES
from repro.core.hardware import analytical_profile
from repro.core.synthesis import (OPERATIONS, PTR_BYTES, Workload,
                                  cost_workload, synthesize_put)

HW3 = dict(cpu_ns_per_cmp=0.7, l1_bytes=32768, l2_bytes=262144,
           l3_bytes=33554432, l1_ns=1.5, l2_ns=5.0, l3_ns=20.0,
           mem_ns=70.0, bw_bytes_per_s=4.0e10)

#: YCSB E's record and scan shape: 10M records of 1 KB, scans of 50.5
YCSB_E = Workload(n_entries=10_000_000, key_bytes=8, value_bytes=1000,
                  zipf_alpha=0.99, selectivity=50.5 / 10_000_000)


def _bloom(element):
    return element.with_values(bloom_filters=("on", 3, 64),
                               filters_memory_layout="scatter")


#: every emission class, with two knob values each where it has one
INTERNALS = {
    "Hash": lambda k: el.hash_element(k),                       # IND_FUNC
    "Range": lambda k: el.range_element(k),                     # IND
    "Trie": lambda k: el.trie_element(k, 4),                    # IND
    "B+": lambda k: el.btree_internal(k),                       # DEP
    "CSB+": lambda k: el.csb_internal(k),                       # DEP
    "FAST": lambda k: el.fast_internal(k),                      # DEP
    "B+bloom": lambda k: _bloom(el.btree_internal(k)),          # DEP_BLOOM
    "Append": lambda k: el.range_element(k).with_values(        # APPEND
        key_partitioning=("append", "fw")),
    "LL": lambda k: el.linked_list_element(k),                  # LL
    "SL": lambda k: el.skip_list_element(k),                    # SKIP
}
TERMINALS = {
    "UDP": lambda k: el.unordered_data_page(k),
    "ODP": lambda k: el.ordered_data_page(k),
    "ODPbloom": lambda k: _bloom(el.ordered_data_page(k)),
}
UNLIMITED = ("LL", "SL")


def _class_chains():
    """Every chain of up to two internal elements over every terminal."""
    chains = []
    for depth in range(3):
        for names in itertools.product(sorted(INTERNALS), repeat=depth):
            if any(a in UNLIMITED and b in UNLIMITED
                   for a, b in zip(names, names[1:])):
                continue
            for term in sorted(TERMINALS):
                for knob in (16, 64):
                    chains.append(tuple(INTERNALS[n](knob) for n in names)
                                  + (TERMINALS[term](4 * knob),))
    return chains


@pytest.mark.parametrize("zipf", [0.0, 0.99])
def test_fused_emission_matches_the_scalar_put_on_every_class(zipf):
    wl = Workload(n_entries=3_000_000, key_bytes=8, value_bytes=1000,
                  zipf_alpha=zipf, selectivity=50.5 / 3_000_000)
    chains = _class_chains()
    segs = templatecost.pack_specs(chains, wl, (("put", 1.0),))
    classes = set()
    for chain, (ids, sizes, weights) in zip(chains, segs):
        comp = compile_breakdown(synthesize_put(
            el.DataStructureSpec("t", chain), wl))
        m = weights != 0.0
        assert np.array_equal(ids[m], comp.model_ids), chain
        np.testing.assert_allclose(sizes[m], comp.sizes, rtol=1e-12)
        np.testing.assert_allclose(weights[m], comp.counts, rtol=1e-12)
        classes |= set(templatecost.chain_geometry(chain, wl).template[0])
    assert classes == set(range(7))
    # scored: the fused scorer against the scalar oracle
    hw = analytical_profile("HW3", **HW3)
    specs = [el.DataStructureSpec("t", c) for c in chains]
    fused = pack_frontier(specs, wl, {"put": 1.0}).score(hw)
    scalar = [cost_workload(s, wl, hw, {"put": 1.0}) for s in specs]
    np.testing.assert_allclose(fused, scalar, rtol=1e-6)


def test_served_sweep_agrees_with_the_scalar_oracle_on_a_ycsb_e_mix():
    from repro.serving import DesignCalculatorService
    hw = analytical_profile("HW3", **HW3)
    rng = np.random.default_rng(17)
    chains = _class_chains()
    specs = [el.DataStructureSpec("e", chains[int(i)])
             for i in rng.choice(len(chains), 48, replace=False)]
    mixes = [{"range_get": 100.0 * (1 - u), "put": 100.0 * u}
             for u in (0.05, 0.25, 0.5)]
    workloads = [YCSB_E] * len(mixes)
    with DesignCalculatorService([hw]) as svc:
        ans = svc.submit_sweep(specs, workloads, hw, mixes).result()
        stats = svc.stats()
    assert ans.engine == "fused" and stats["failed"] == 0
    want = [[cost_workload(s, YCSB_E, hw, mix) for s in specs]
            for mix in mixes]
    np.testing.assert_allclose(ans.totals, want, rtol=1e-6)


def _writes(chain, wl):
    """(size, count) of a put's serial writes, after its search."""
    cb = synthesize_put(el.DataStructureSpec("t", chain), wl)
    return [(r.size, r.count) for r in cb.records
            if r.level1 == access.SERIAL_WRITE]


SMALL = Workload(n_entries=640, key_bytes=8, value_bytes=8, zipf_alpha=0.9)


def test_split_cascade_btree_over_btree_over_odp():
    """B+ (16) over B+ (8) over ODP (64), 640 entries: one root, 16
    nodes, 128 pages of 5 entries; a pair is 16 B."""
    chain = (el.btree_internal(16), el.btree_internal(8),
             el.ordered_data_page(64))
    assert [lv.n_nodes for lv in synthesis.instantiate(
        el.DataStructureSpec("t", chain), SMALL).levels] == [1, 16, 128]
    s = 2 / 64
    assert _writes(chain, SMALL) == [
        (5 / 2 * 16, 1.0),                  # shift the upper half
        (32 * 16, s),                       # the page's moved half
        (7 / 2 * 16, s), (8 / 2 * 16, s * 2 / 8),          # B+ (8)
        (15 / 2 * 16, s * 2 / 8), (16 / 2 * 16, s * 2 / 8 * 2 / 16)]


def test_split_cascade_hash_over_udp():
    chain = (el.hash_element(32), el.unordered_data_page(32))
    assert _writes(chain, SMALL) == [
        (16.0, 1.0), (16 * 16, 2 / 32), (float(PTR_BYTES), 2 / 32)]


def test_split_cascade_linked_list_over_odp():
    chain = (el.linked_list_element(32), el.ordered_data_page(32))
    # 20 pages of 32 entries
    assert _writes(chain, SMALL) == [
        (32 / 2 * 16, 1.0), (16 * 16, 2 / 32), (2.0 * PTR_BYTES, 2 / 32)]


def test_split_cascade_bare_odp():
    chain = (el.ordered_data_page(256),)
    # three pages of 640 / 3 entries; nothing above the page's split
    assert _writes(chain, SMALL) == [(640 / 3 / 2 * 16, 1.0),
                                     (128 * 16, 2 / 256)]


def test_cascade_slots_above_a_stop_carry_count_zero():
    """B+ over Hash over UDP: the Hash level stops the cascade, so the
    B+ level's new-entry and split slots are emitted with count 0."""
    chain = (el.btree_internal(16), el.hash_element(64),
             el.unordered_data_page(64))
    (ids, sizes, weights), = templatecost.pack_specs([chain], SMALL,
                                                     (("put", 1.0),))
    geom = templatecost.chain_geometry(chain, SMALL)
    schema = synthesis.symbolic_breakdown("put", geom.template)
    weights = weights[:len(schema)]
    assert len(schema) == len(compile_breakdown(synthesize_put(
        el.DataStructureSpec("t", chain), SMALL)).counts) + 2
    np.testing.assert_array_equal(weights[-2:], [0.0, 0.0])
    assert weights[-3] == 2 / 64          # the Hash level's pointer


def test_the_search_is_an_unskewed_miss_with_no_value_fetch():
    for term in (el.unordered_data_page(64), el.ordered_data_page(64)):
        spec = el.DataStructureSpec("t", (el.btree_internal(16), term))
        put = synthesize_put(spec, SMALL).records
        flat = synthesis.synthesize_get(
            spec, dataclasses.replace(SMALL, zipf_alpha=0.0)).records
        search = [r for r in put if r.level1 != access.SERIAL_WRITE]
        # the lookup's records, less its value fetch; a sorted page is
        # searched as the lookup searches it, an unsorted one scanned whole
        assert [(r.level2, r.count) for r in search] == \
            [(r.level2, r.count) for r in flat[:-1]]
        scale = [1.0] * (len(search) - 1) + [1.0 if term.sorted_keys
                                             else 2.0]
        assert [r.size for r in search] == \
            [r.size * k for r, k in zip(flat[:-1], scale)]


def _ycsb_a_frontier():
    """A seeded frontier of depth-2 completions over 10M 1 KB records."""
    rng = np.random.default_rng(2_147_483_653)
    names = ["Hash", "Range", "B+", "CSB+", "LL", "SL", "Trie"]
    make = {**INTERNALS, "UDP": TERMINALS["UDP"], "ODP": TERMINALS["ODP"]}
    chains = []
    for _ in range(256):
        depth = int(rng.integers(3))
        internal = [names[int(i)] for i in rng.integers(len(names),
                                                        size=depth)]
        if any(a in UNLIMITED and b in UNLIMITED
               for a, b in zip(internal, internal[1:])):
            continue
        term = ("UDP", "ODP")[int(rng.integers(2))]
        chains.append(tuple(make[n](int(2 ** rng.integers(2, 12)))
                            for n in internal + [term]))
    return chains


def _digest(chains, ops):
    """SHA-256 of every op's emitted records over a ycsb_a-like sweep
    axis: model names, sizes and weights, bit for bit."""
    points = [(Workload(n_entries=10_000_000, key_bytes=8, value_bytes=1000,
                        zipf_alpha=z), ((op, 100.0),))
              for op in ops for z in (0.0, 0.99)]
    h = hashlib.sha256()
    for row in templatecost.pack_points(chains, points):
        for ids, sizes, weights in row:
            h.update("|".join(_MODEL_NAMES[i] for i in ids).encode())
            h.update(np.ascontiguousarray(sizes, np.float64).tobytes())
            h.update(np.ascontiguousarray(weights, np.float64).tobytes())
    return h.hexdigest()


#: taken from the emission as it stood before puts were added
DIGEST_BEFORE_PUTS = \
    "24ac558a2d5a29e0ec22fa29f61cb8f9135e1c3db5e10327c8226e41ccd66d65"


def test_other_operations_emit_what_they_emitted_before_puts():
    assert _digest(_ycsb_a_frontier(),
                   ("get", "range_get", "update", "bulk_load")) == \
        DIGEST_BEFORE_PUTS


def test_records_counter_in_stats():
    """``stats()`` carries ``records_n.<op>`` for every operation, and
    packing a put mix adds its nonzero record slots times points; it
    carries ``pack_grid_cells`` too, which the sweep adds designs x
    points to."""
    from repro.serving import DesignCalculatorService
    hw = analytical_profile("HW3", **HW3)
    chain = (el.btree_internal(16), el.btree_internal(8),
             el.ordered_data_page(64))
    with DesignCalculatorService([hw]) as svc:
        before = svc.stats()
        assert {f"records_n.{op}" for op in OPERATIONS} <= set(before)
        svc.submit_sweep([el.DataStructureSpec("t", chain)],
                         [SMALL, SMALL], hw,
                         [{"put": 1.0}, {"put": 2.0}]).result()
        after = svc.stats()
    # 6 search records (a node access and a fence search per B+ level, a
    # page access and its key search) and 6 writes, at each of 2 points
    assert after["records_n.put"] - before["records_n.put"] == 2 * 12
    assert after["records_n.get"] == before["records_n.get"]
    assert after["pack_grid_cells"] - before["pack_grid_cells"] == 1 * 2
