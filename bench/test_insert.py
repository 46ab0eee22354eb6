"""YCSB E's sweep cell and its plain reference for puts (CPU).

``bench/reference_ops/put.py`` prices a put (YCSB's insert) from the
plain inputs; these tests hold it to the program's scalar oracle, run
the ``ycsb_e.sweep`` cell through ``bench.run.execute`` at a size a CPU
test can hold, once sound and once with each of three faults planted in
the program, and read the cell's record shares from the program's
``records_n.<op>`` counters.
"""
import jax
import numpy as np
import pytest

from bench import check, designs, reference as ref, run
from bench import traffic as tr
from bench.system import System
from bench.test_faults import _altered

E_CELL = {"name": "ycsb_e.sweep", "config": "ycsb_e",
          "traffic": "sweep_insert", "chips": 1}
A_CELL = {"name": "ycsb_a.sweep", "config": "ycsb_a", "traffic": "sweep",
          "chips": 1}
SMALL = {"designs": 64, "prebuilt_per_client": 1, "warm_designs": 16,
         "check_cells_per_sweep": 32}


def small_run(cell: dict = E_CELL) -> dict:
    params = dict(tr.load_traffic(cell["traffic"]), **SMALL)
    return run.execute(run.load_benchmark(), cell, 2**31 + 11, 1.0, False,
                       jax.devices(), None, params)


def test_the_reference_put_agrees_with_the_programs_scalar_oracle():
    from repro.core.hardware import analytical_profile
    from repro.core.synthesis import Workload, cost_workload
    config = tr.load_config("ycsb_e")
    source = designs.design_source(config)
    specs = designs.ProgramSpecs()
    rng = np.random.default_rng(29)
    base = tr.base_workload(config)
    constants = config["hardware"]["HW3"]
    hw = analytical_profile("HW3", **constants)
    for u in (0.05, 0.25, 0.5):
        mix = tr.share_mix(config, "put", u)
        for wl in (base, base._replace(zipf_alpha=0.0)):
            for _ in range(40):
                design = source.draw_design(rng)
                want = cost_workload(specs.spec(design), Workload(*wl), hw,
                                     mix)
                got = ref.cost(design, wl, constants, mix)
                assert check.rel_err(got, want) < 1e-6, (design, u)


def test_the_control_fails_the_limit_on_the_ycsb_e_mix():
    config = tr.load_config("ycsb_e")
    params = dict(tr.load_traffic("sweep_insert"), designs=96)
    ds = tr.sweep_designs(config, params, 2**31 + 3, 0, 0)
    points = tr.sweep_points(config, params)
    assert points[0][1] == {"range_get": 95.0, "put": 5.0}
    limit = config["correct"]["max_rel_err"]
    comparisons = [check.Comparison(
        ref.cost(d, wl, config["hardware"]["HW3"], mix), d, wl, "HW3", mix)
        for d in ds[:32] for wl, mix in points[::5]]
    assert check.max_rel_err(comparisons, config["hardware"]) == 0.0
    assert check.max_rel_err(comparisons, config["hardware"],
                             control=True) > limit


def _recorded(monkeypatch) -> list:
    """Every ``System.counters()`` reading a run takes, in order."""
    seen = []
    orig = System.counters

    def recording(self):
        out = orig(self)
        seen.append(out)
        return out
    monkeypatch.setattr(System, "counters", recording)
    return seen


def _shares(seen: list) -> tuple:
    before, after = seen[-2], seen[-1]
    ctx = {"counters": {k: after[k] - before[k] for k in after}}
    return (run.read_metric("insert_record_pct.sweep", ctx),
            run.read_metric("range_record_pct.sweep", ctx))


def test_sweep_sound_run_is_correct_and_reads_its_record_shares(
        monkeypatch):
    seen = _recorded(monkeypatch)
    result = small_run()
    assert result["correct"] is True
    assert result["checks"]["answers_compared"]["value"] >= 32
    puts, scans = _shares(seen)
    assert 0.0 < puts < 100.0 and 0.0 < scans < 100.0
    assert puts + scans == pytest.approx(100.0)


def test_ycsb_a_sweep_reads_no_puts(monkeypatch):
    seen = _recorded(monkeypatch)
    assert small_run(A_CELL)["correct"] is True
    assert _shares(seen) == (0.0, 0.0)


def test_sweep_with_the_splits_left_out_is_not_correct(monkeypatch):
    """The page split and its cascade emitted with count 0."""
    from repro.core import templatecost
    orig = templatecost._emit_tail_put

    def no_splits(t, wc, rows):
        inner = templatecost._Rows(rows.W)
        orig(t, wc, inner)
        head, *splits = inner.parts
        rows.parts.append(head)
        rows.parts += [(*p[:4], np.zeros_like(p[4])) for p in splits]
    monkeypatch.setattr(templatecost, "_emit_tail_put", no_splits)
    assert small_run()["correct"] is False


def test_sweep_with_a_skewed_put_search_is_not_correct(monkeypatch):
    from repro.core import templatecost
    monkeypatch.setattr(templatecost, "_unskewed", lambda wc: wc)
    assert small_run()["correct"] is False


def test_sweep_with_altered_answers_is_not_correct(monkeypatch):
    from repro.core import devicecost
    monkeypatch.setattr(devicecost, "score_sweep",
                        _altered(devicecost.score_sweep))
    assert small_run()["correct"] is False
