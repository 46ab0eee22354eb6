"""Fused device-resident frontier scoring (PR 2 tentpole): parameter-table
swaps without recompilation, pooled scoring, bank coverage of every model
kind, and the bounded compiled-shape set."""
import numpy as np
import pytest

from repro.core import batchcost, devicecost, elements as el, models, whatif
from repro.core.batchcost import cost_many, pack_frontier
from repro.core.hardware import HardwareProfile, hw1, hw2, hw3
from repro.core.synthesis import Workload


def _frontier(n_entries=500_000):
    specs = [el.spec_btree(), el.spec_hash_table(), el.spec_skip_list(),
             el.spec_btree(fanout=40), el.spec_btree(fanout=10)]
    return specs, Workload(n_entries=n_entries), {"get": 10.0, "update": 5.0}


def test_whatif_hardware_swaps_table_without_recompilation(hw_analytical):
    """The acceptance probe: once a frontier shape is compiled, scoring it
    on *new* hardware is a pure parameter-table swap — the jit cache must
    serve every what-if-hardware question with zero retraces."""
    specs, w, mix = _frontier()
    packed = pack_frontier(specs, w, mix)
    packed.score(hw1())                      # may compile this shape once
    before = devicecost.trace_count()
    totals = {}
    for hw in (hw2(), hw3(), hw1()):
        totals[hw.name] = packed.score(hw)
    assert devicecost.trace_count() == before
    # the swap changes answers (different hardware), not shapes
    assert not np.allclose(totals["HW2"], totals["HW3"])
    # a one-design what-if frontier is its own (smaller) bucket shape: it
    # may compile once, after which hardware swaps stay recompile-free
    whatif.what_if_hardware(specs[0], w, hw1(), hw3(), mix)
    before = devicecost.trace_count()
    ans = whatif.what_if_hardware(specs[0], w, hw2(), hw3(), mix)
    assert devicecost.trace_count() == before
    assert ans.beneficial  # HW3 is strictly faster in every constant


def test_bucketing_bounds_compiled_shapes(hw_analytical):
    """Frontier sizes vary call to call; pow2 bucketing must keep the
    compiled-shape set bounded — many same-bucket frontiers, one trace."""
    specs, w, mix = _frontier()
    cost_many(specs[:3], w, hw_analytical, mix)
    before = devicecost.trace_count()
    for k in (2, 3, 4, 5, 4, 3, 2):          # all within the same buckets
        cost_many(specs[:k], w, hw_analytical, mix)
    assert devicecost.trace_count() == before


@pytest.mark.devices(2)
def test_pool_parts_match_single_device(hw_analytical, device_count):
    """A 200-design frontier split across two devices by the scoring
    shard pool equals the flat one-device call."""
    from repro.serving import ScoringShardPool
    assert device_count == 2
    specs, w, mix = _frontier()
    packed = pack_frontier(specs * 40, w, mix)   # 200 designs
    single = packed.score(hw_analytical)
    pool = ScoringShardPool(min_cells_per_shard=1)
    try:
        pooled, used = pool.score_frontier(packed, hw_analytical)
    finally:
        pool.close()
    assert used == 2
    np.testing.assert_allclose(pooled, single, rtol=1e-12)


def test_chunked_scoring_matches_unchunked(hw_analytical, monkeypatch):
    specs, w, mix = _frontier()
    packed = pack_frontier(specs * 40, w, mix)
    full = packed.score(hw_analytical)
    monkeypatch.setattr(devicecost, "_MAX_FUSED_RECORDS", 256)
    chunked = packed.score(hw_analytical)
    # chunks cut on design boundaries: every design reduces in one call
    np.testing.assert_array_equal(chunked, full)


def test_chunked_sweep_matches_one_call_bitwise(hw_analytical, monkeypatch):
    """A sweep too large for one fused chunk scores chunk by chunk; cut on
    design boundaries, the grid equals the one-call grid bit for bit (the
    equality the shard pool's parity with the flat path rests on)."""
    specs, w, mix = _frontier()
    workloads = [w, Workload(n_entries=500_000, zipf_alpha=1.0)]
    full = batchcost.pack_sweep(specs * 40, workloads, mix).score(
        hw_analytical)
    monkeypatch.setattr(devicecost, "_MAX_FUSED_RECORDS", 512)
    batchcost.clear_caches()
    chunked = batchcost.pack_sweep(specs * 40, workloads, mix).score(
        hw_analytical)
    np.testing.assert_array_equal(chunked, full)


def _knn_profile(base: HardwareProfile, n_points: int) -> HardwareProfile:
    """A profile whose quicksort model is a trained k-NN (Table 1 allows
    any family per primitive) — exercises the knn bank end to end."""
    xs = np.logspace(1, 6, n_points)
    ys = 2e-9 * xs * np.log(xs) + 1e-8
    models_ = dict(base.models)
    models_["quicksort"] = models.fit("knn", xs, ys)
    return HardwareProfile(base.name + "+knn", models_)


@pytest.mark.parametrize("n_points", [12, 3], ids=["knn", "knn-small"])
def test_knn_models_join_the_device_table(hw_analytical, n_points):
    """The jittable fixed-k top-k covers any support size: sentinel slots
    carry zero weight, so n < 4 reduces to the numpy k=min(4, n) result."""
    hw = _knn_profile(hw1(), n_points)
    specs, w, mix = _frontier()
    fused = cost_many(specs, w, hw, mix)
    grouped = cost_many(specs, w, hw, mix, engine="grouped")
    np.testing.assert_allclose(fused, grouped, rtol=1e-6)
    table = devicecost.device_table(hw)
    assert table.has_knn


@pytest.mark.parametrize("with_knn", [False, True], ids=["plain", "knn"])
def test_bank_predict_sums_in_a_fixed_order(hw_analytical, with_knn):
    """Per-record values must not depend on the batch shape: a TPU orders
    a ``reduce_sum`` by layout, so a [2, R] chunk and an [8, R] chunk
    rounded the same record differently.  bank_predict spells
    its short sums out as ordered adds — no reduction primitive at all."""
    import jax
    import jax.numpy as jnp
    table = devicecost.device_table(_knn_profile(hw1(), 12) if with_knn
                                    else hw1())
    ids = jnp.zeros(64, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda x: devicecost.bank_predict(
        table.banks, ids, x, with_knn))(jnp.ones((2, 64), jnp.float32))
    prims = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
    assert not {p for p in prims if p.startswith("reduce_")}, prims
    assert ("top_k" in prims) == with_knn


def test_sigmoids2d_banks_as_its_m1_slice(hw_analytical):
    x = np.tile(np.logspace(2, 6, 20), 4)
    m_in = np.repeat([1, 2, 3, 4], 20)
    y = (1e-8 / (1 + np.exp(-(np.log(x + 1.0) - 8.0)))) * m_in
    hw = hw1()
    hw = HardwareProfile("HW1+2d", dict(hw.models))
    hw.models["bloom_probe_multiply_shift"] = models.fit2d_sigmoids(
        x, m_in, y)
    specs = [whatif.add_bloom_filters(el.spec_btree())]
    w = Workload(n_entries=200_000)
    fused = cost_many(specs, w, hw, {"get": 5.0})
    grouped = cost_many(specs, w, hw, {"get": 5.0}, engine="grouped")
    np.testing.assert_allclose(fused, grouped, rtol=1e-6)


def test_foreign_interned_model_does_not_poison_pads():
    """Regression: pad rows used to carry model id 0; once some *other*
    profile's model name claimed that global id, weight-0 pads tripped the
    availability check on profiles that never fit it.  Needs a fresh
    process so the foreign name is interned first (id 0)."""
    import os
    import subprocess
    import sys
    code = (
        "import numpy as np\n"
        "from repro.core import batchcost, devicecost, elements as el\n"
        "from repro.core.hardware import hw1\n"
        "from repro.core.synthesis import Workload\n"
        "devicecost.model_id('exotic_model')   # claims global id 0\n"
        "w = Workload(n_entries=10_000)\n"
        "fused = batchcost.cost_many([el.spec_btree()], w, hw1())\n"
        "grouped = batchcost.cost_many([el.spec_btree()], w, hw1(),\n"
        "                              engine='grouped')\n"
        "np.testing.assert_allclose(fused, grouped, rtol=1e-6)\n"
        "print('PADS-OK')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "PADS-OK" in proc.stdout


def test_missing_model_raises_keyerror(hw_analytical):
    specs, w, mix = _frontier()
    partial = HardwareProfile("partial", {
        k: m for k, m in hw1().models.items() if "write" not in k})
    with pytest.raises(KeyError, match="write"):
        cost_many(specs, w, partial, mix)


def test_replace_derived_profile_rebuilds_banks(hw_analytical):
    """Regression: a profile derived via dataclasses.replace must never
    score frontiers with its parent's cached parameter banks."""
    import dataclasses
    specs, w, mix = _frontier()
    hw = hw1()
    cost_many(specs, w, hw, mix)            # builds + caches hw's table
    derived = dataclasses.replace(hw, name="HW1-as-HW3",
                                  models=hw3().models)
    fused = cost_many(specs, w, derived, mix)
    grouped = cost_many(specs, w, derived, mix, engine="grouped")
    np.testing.assert_allclose(fused, grouped, rtol=1e-6)
    assert not np.allclose(fused, cost_many(specs, w, hw, mix))


def test_device_table_cached_per_profile(hw_analytical):
    hw = hw1()
    t1 = devicecost.device_table(hw)
    assert devicecost.device_table(hw) is t1
    # a different profile builds its own banks but shares bank shapes
    # (that shape-sharing is what makes the swap recompile-free)
    t2 = devicecost.device_table(hw3())
    assert t2 is not t1
    assert {k: v.shape for k, v in t1.banks.items()} == \
        {k: v.shape for k, v in t2.banks.items()}


def test_tile_padding_is_invisible(hw_analytical):
    """Pad rows (weight 0, model row 0) must contribute exactly nothing:
    a one-design frontier equals its cost_workload_batched total."""
    from repro.core.batchcost import cost_workload_batched
    spec = el.spec_btree()
    w = Workload(n_entries=100_000)
    packed = pack_frontier([spec], w, None)
    assert len(packed.ids) % devicecost.TILE == 0
    assert cost_workload_batched(spec, w, hw_analytical, engine="grouped") \
        == pytest.approx(float(packed.score(hw_analytical)[0]), rel=1e-6)
