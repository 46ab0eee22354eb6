"""The sweep work count and the plain reference (CPU)."""
import ast
import glob
import json
import math
import os
import re

import numpy as np
import pytest

from bench import check, designs, reference as ref
from bench import traffic as tr
from bench.work import SweepWork

BENCH = os.path.dirname(os.path.abspath(__file__))


def _testdata(name):
    with open(os.path.join(BENCH, "testdata", f"{name}.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def sweep():
    config = tr.load_config("ycsb_a")
    params = dict(tr.load_traffic("sweep"), designs=96)
    return (tr.sweep_designs(config, params, 5, 0, 0),
            tr.sweep_points(config, params))


def test_bytes_do_not_depend_on_how_the_sweep_is_cut(sweep):
    ds, points = sweep
    work = SweepWork()
    whole = work.sweep_bytes(ds, points)
    assert whole > 0
    # split along designs, as the shard pool splits, and along points,
    # as chunks of a workload axis would: the parts add up to the whole,
    # less the record layout the points share
    for parts in (2, 3, 7):
        cuts = np.linspace(0, len(ds), parts + 1).astype(int)
        assert sum(work.sweep_bytes(ds[a:b], points)
                   for a, b in zip(cuts[:-1], cuts[1:])) == whole
    halves = work.sweep_bytes(ds, points[:8]) + \
        work.sweep_bytes(ds, points[8:])
    records = sum(work.records(d, points[0][0], ["get", "update"])
                  for d in ds)
    assert halves - whole == 2 * 4 * records
    # a fresh counter gives the same number: nothing cached leaks in
    assert SweepWork().sweep_bytes(ds, points) == whole


def _imports(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


REFERENCE_OPS = sorted(os.path.relpath(p, BENCH) for p in glob.glob(
    os.path.join(BENCH, "reference_ops", "*.py")))


@pytest.mark.parametrize("module", ["reference.py", "work.py", "check.py",
                                    "traffic.py", "trace.py",
                                    *REFERENCE_OPS])
def test_the_yardstick_imports_nothing_of_the_program(module):
    assert not [m for m in _imports(os.path.join(BENCH, module))
                if m.startswith("repro")]


@pytest.mark.parametrize("config_name", ["ycsb_c", "ycsb_a"])
def test_reference_agrees_with_the_programs_scalar_oracle(config_name):
    """The plain reference restates the program's cost semantics: on
    random designs of each configuration it agrees with the scalar
    expert system to float32 rounding of the model parameters."""
    from repro.core.hardware import analytical_profile
    from repro.core.synthesis import Workload, cost_workload
    config = tr.load_config(config_name)
    source = designs.design_source(config)
    specs = designs.ProgramSpecs()
    rng = np.random.default_rng(3)
    mix = tr.config_mix(config)
    ops = sum(mix.values())
    range_mixes = ({"range_get": ops},
                   {"range_get": 0.95 * ops, "update": 0.05 * ops})
    base = tr.base_workload(config)
    pages, terminals = set(), set()
    for hw_name, constants in config["hardware"].items():
        hw = analytical_profile(hw_name, **constants)
        for wl in (base, base._replace(zipf_alpha=0.0),
                   base._replace(n_entries=10 * base.n_entries)):
            program_wl = Workload(*wl)
            for _ in range(25):
                if isinstance(source, designs.Families):
                    design = source.build(*source.draw_family(rng))
                else:
                    design = source.draw_design(rng)
                spec = specs.spec(design)
                assert designs.from_spec(spec) == design
                want = cost_workload(spec, program_wl, hw, mix)
                got = ref.cost(design, wl, constants, mix)
                assert check.rel_err(got, want) < 1e-6, design
                # range reads of one page, of several, of every page
                n_term = ref.geometry(design, wl)[-1].n_nodes
                terminals.add(design[-1].name)
                for sel in (1e-12, 3.5 / n_term, 1.0):
                    scan_wl = wl._replace(selectivity=sel)
                    pages.add(min(max(math.ceil(sel * n_term), 1), 2)
                              if sel < 1.0 else "all")
                    for range_mix in range_mixes:
                        want = cost_workload(spec, Workload(*scan_wl), hw,
                                             range_mix)
                        got = ref.cost(design, scan_wl, constants, range_mix)
                        assert check.rel_err(got, want) < 1e-6, \
                            (design, sel, range_mix)
    assert pages == {1, 2, "all"}
    # terminals with leaf links (ODP) and without (UDP)
    assert terminals == {"ODP", "UDP"}


def test_an_operation_with_no_definition_and_no_file_raises():
    design = (ref.Level("B+", 20), ref.Level("ODP", 256))
    wl = tr.base_workload(tr.load_config("ycsb_a"))
    for op in ("insert", "scan", "../run", "range_get.py"):
        with pytest.raises(ValueError, match=re.escape(op)):
            ref.op_records(op, design, wl)
    # the one defined by its file: a one-page range is a get and a scan
    one_page = wl._replace(selectivity=1e-12)
    assert ref.op_records("range_get", design, one_page)[:-1] == \
        ref.op_records("get", design, one_page)


def test_the_existing_sweep_cells_read_what_they_read_before():
    """The point axis of ycsb_a's sweep is the list its generator built
    when every point was get + update, float for float, and the work a
    seeded sweep of it needs is the count taken then."""
    config = tr.load_config("ycsb_a")
    params = tr.load_traffic("sweep")
    points = tr.sweep_points(config, params)
    base = ref.Workload(10_000_000, 100, 8, 1000, 0.99)
    before = [(base, {"get": 100.0 * (1.0 - float(u)),
                      "update": 100.0 * float(u)})
              for u in np.linspace(0.05, 0.5, 16)]
    assert [(wl, list(mix.items())) for wl, mix in points] == \
        [(wl, list(mix.items())) for wl, mix in before]
    assert all(wl.selectivity == 0.001 for wl, _ in points)
    ds = tr.sweep_designs(config, params, 2**31 + 5, 1, 2)
    assert SweepWork().sweep_bytes(ds, points) == 9_790_032


def test_the_control_fails_the_limit_and_the_reference_holds_it(sweep):
    """The bfloat16 control, put in the program's place, reads far above
    the configuration's limit on the cell's own inputs."""
    ds, points = sweep
    config = tr.load_config("ycsb_a")
    limit = config["correct"]["max_rel_err"]
    comparisons = [check.Comparison(
        ref.cost(d, wl, config["hardware"]["HW3"], mix), d, wl, "HW3", mix)
        for d in ds[:32] for wl, mix in points[::5]]
    assert check.max_rel_err(comparisons, config["hardware"]) == 0.0
    assert check.max_rel_err(comparisons, config["hardware"],
                             control=True) > 10 * limit


def test_the_control_fails_the_limit_on_a_range_mix():
    """The same on the sweep points of the test-only configuration of
    scans and updates (bench/testdata), 95% range reads at the start."""
    config = _testdata("scan_update")
    params = dict(_testdata("scan_sweep"), designs=96)
    ds = tr.sweep_designs(config, params, 7, 0, 0)
    points = tr.sweep_points(config, params)
    assert points[0][1] == {"range_get": 95.0, "update": 5.0}
    limit = config["correct"]["max_rel_err"]
    comparisons = [check.Comparison(
        ref.cost(d, wl, config["hardware"]["HW3"], mix), d, wl, "HW3", mix)
        for d in ds[:32] for wl, mix in points[::5]]
    assert check.max_rel_err(comparisons, config["hardware"]) == 0.0
    assert check.max_rel_err(comparisons, config["hardware"],
                             control=True) > 10 * limit
