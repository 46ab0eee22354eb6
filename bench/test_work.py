"""The sweep work count and the plain reference (CPU)."""
import ast
import os

import numpy as np
import pytest

from bench import check, designs, reference as ref
from bench import traffic as tr
from bench.work import SweepWork

BENCH = os.path.dirname(os.path.abspath(__file__))


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


@pytest.mark.parametrize("module", ["reference.py", "work.py", "check.py",
                                    "traffic.py", "trace.py"])
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
    base = tr.base_workload(config)
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
