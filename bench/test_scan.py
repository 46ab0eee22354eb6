"""A configuration that brings its own operations: scans and updates (CPU).

The test-only configuration ``bench/testdata/scan_update.json`` sends
95% range reads of 1-100 records (YCSB's ``maxscanlength`` 100,
``uniform``) and 5% updates, and its mix ``bench/testdata/
scan_sweep.json`` sweeps the update share.  Its sweep runs through
``bench.run.execute`` as a cell does, once sound and once with the scan
size dropped on the way to the program.
"""
import json
import os

import jax
import pytest

from bench import reference as ref
from bench import run
from bench import traffic as tr

BENCH = os.path.dirname(os.path.abspath(__file__))


def from_testdata(name: str) -> dict:
    with open(os.path.join(BENCH, "testdata", f"{name}.json")) as fh:
        return json.load(fh)


def scan_run() -> dict:
    cell = {"name": "scan_update.scan_sweep", "config": "scan_update",
            "traffic": "scan_sweep", "chips": 1}
    return run.execute(run.load_benchmark(), cell, 2**31 + 7, 1.0, False,
                       jax.devices(), None, from_testdata("scan_sweep"),
                       from_testdata("scan_update"))


def test_the_scan_size_comes_from_ycsbs_own_keys():
    config = from_testdata("scan_update")
    wl = tr.base_workload(config)
    assert wl.selectivity == (1 + 100) / 2 / 10_000_000
    # a configuration that states no scan length keeps the default range
    assert tr.base_workload(tr.load_config("ycsb_a")).selectivity == 0.001
    for dist in ("zipfian", None):
        bad = dict(config, scanlengthdistribution=dist)
        with pytest.raises(ValueError, match="uniform"):
            tr.base_workload(bad)


def test_a_share_sweep_keeps_the_other_operations_proportions():
    config = dict(from_testdata("scan_update"),
                  operations={"range_get": 60, "get": 30, "update": 10})
    params = {"op_share": {"op": "update", "from": 0.2, "to": 0.6,
                           "steps": 3}, "zipf_alphas": [0.99]}
    mixes = [mix for _, mix in tr.sweep_points(config, params)]
    # in the configuration's order, which the program sums in
    assert [list(m) for m in mixes] == [["range_get", "get", "update"]] * 3
    for mix, u in zip(mixes, (0.2, 0.4, 0.6)):
        assert sum(mix.values()) == pytest.approx(100.0)
        assert mix["update"] == pytest.approx(100.0 * u)
        assert mix["range_get"] == pytest.approx(2 * mix["get"])
    # an operation the configuration does not send joins the mix
    shares = dict(params, op_share=dict(params["op_share"], op="scan"))
    assert list(tr.sweep_points(config, shares)[0][1]) == \
        ["range_get", "get", "update", "scan"]
    with pytest.raises(ValueError, match="not both"):
        tr.sweep_points(config, dict(params, update_share={}))
    with pytest.raises(ValueError, match="no operation but"):
        tr.sweep_points(dict(config, operations={"update": 1}), params)


def test_scan_sweep_sound_run_is_correct():
    result = scan_run()
    assert result["correct"] is True
    assert result["checks"]["answers_compared"]["value"] >= 32


def test_scan_sweep_with_the_scan_size_dropped_is_not_correct(monkeypatch):
    """The program prices its own default range (0.001 of the keys, some
    hundreds of pages) where the configuration scans 50.5 records."""
    from bench.system import System
    orig = System.workload

    def dropped(self, wl):
        return orig(self, wl._replace(selectivity=ref.DEFAULT_SELECTIVITY))
    monkeypatch.setattr(System, "workload", dropped)
    result = scan_run()
    assert result["correct"] is False
    assert result["checks"]["max_rel_err"]["value"] > \
        100 * result["checks"]["max_rel_err"]["limit"]
