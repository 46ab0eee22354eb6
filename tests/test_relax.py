"""The compiled refinement loop (``relax.refine``): the same arithmetic
as an eager AdamW loop over the surrogate, one compile per
(template, steps, learning rate), and no recompile on a repeat
refinement, a hardware swap or a rehearsed search.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import devicecost, relax, search
from repro.core.hardware import hw1, hw2
from repro.core.relax import RelaxedDesign, RelaxTemplate
from repro.core.synthesis import Workload
from repro.optim.adamw import adamw_init, adamw_update, apply_updates
from repro.serving import DesignCalculatorService

N_ENTRIES = float(1 << 20)
WORKLOAD = Workload(n_entries=1 << 16, n_queries=100)
MIX = {"get": 80.0, "update": 20.0}

#: every internal level kind, both terminals and the bloom knob
DESIGNS = [
    RelaxedDesign(RelaxTemplate(("Hash", "UDP"), bloom=True),
                  (9.0, 7.5, 14.0)),
    RelaxedDesign(RelaxTemplate(("Range", "CSB+", "ODP")), (6.0, 4.0, 9.0)),
    RelaxedDesign(RelaxTemplate(("B+", "UDP")), (4.3, 8.0)),
    RelaxedDesign(RelaxTemplate(("Trie", "ODP")), (8.0, 6.2)),
    RelaxedDesign(RelaxTemplate(("Hash", "B+", "ODP"), bloom=True),
                  (10.0, 5.0, 8.0, 12.0)),
]


def eager_refine(design, hw, n_entries, read_fraction=1.0,
                 value_bytes=8.0, steps=8, learning_rate=0.35):
    """The plain reference: one AdamW step at a time, each operation
    dispatched on its own, the surrogate's gradient from its own jit."""
    grad_fn = relax._surrogate_fn(design.template)
    banks = devicecost.device_table(hw).banks
    lo, hi = design.template.knob_bounds()
    params = {"theta": jnp.asarray(design.theta, jnp.float32)}
    state = adamw_init(params)
    run = relax._RefineConfig(learning_rate=learning_rate)
    n = jnp.asarray(float(n_entries), jnp.float32)
    r = jnp.asarray(float(read_fraction), jnp.float32)
    vb = jnp.asarray(float(value_bytes), jnp.float32)
    for _ in range(max(int(steps), 1)):
        _, grad = grad_fn(params["theta"], banks, n, r, vb)
        updates, state = adamw_update({"theta": grad}, state, params, run)
        params = apply_updates(params, updates)
        params = {"theta": jnp.clip(params["theta"],
                                    jnp.asarray(lo, jnp.float32),
                                    jnp.asarray(hi, jnp.float32))}
    return np.asarray(params["theta"], np.float64)


def _in_bounds(design):
    lo, hi = design.template.knob_bounds()
    theta = np.asarray(design.theta)
    return bool(np.all(theta >= lo) and np.all(theta <= hi))


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("design", DESIGNS,
                         ids=lambda d: d.template.describe())
def test_compiled_refine_matches_eager_loop(design, steps):
    hw = hw1()
    for read_fraction in (1.0, 0.5):
        want = eager_refine(design, hw, N_ENTRIES, read_fraction,
                            steps=steps)
        got = relax.refine(design, hw, N_ENTRIES, read_fraction,
                           steps=steps)
        assert got.template == design.template
        np.testing.assert_allclose(np.asarray(got.theta), want,
                                   rtol=0.0, atol=1e-6)
        assert _in_bounds(got)


@pytest.mark.parametrize("edge", ["lo", "hi"])
@pytest.mark.parametrize("design", DESIGNS,
                         ids=lambda d: d.template.describe())
def test_refine_projects_onto_bounds(design, edge):
    """Knobs started on a bound under a large step stay inside the
    bounds, and are projected after every step as the eager loop does:
    at 8 steps a loop that projected only its result ends elsewhere on
    three of the five templates."""
    lo, hi = design.template.knob_bounds()
    start = RelaxedDesign(design.template,
                          tuple(float(v) for v in (lo if edge == "lo"
                                                   else hi)))
    got = relax.refine(start, hw1(), N_ENTRIES, 0.5, steps=8,
                       learning_rate=6.0)
    assert _in_bounds(got)
    np.testing.assert_allclose(
        np.asarray(got.theta),
        eager_refine(start, hw1(), N_ENTRIES, 0.5, steps=8,
                     learning_rate=6.0), rtol=0.0, atol=1e-6)


def test_refine_compiles_once_per_template():
    design = DESIGNS[1]
    relax.refine(design, hw1(), N_ENTRIES, steps=2)
    before = relax.refine_trace_count()
    relax.refine(design, hw1(), N_ENTRIES, 0.7, steps=2)
    assert relax.refine_trace_count() == before          # same template
    relax.refine(design, hw2(), N_ENTRIES, steps=2)
    assert relax.refine_trace_count() == before          # other banks
    # a template no search draws (three internal levels), at a step
    # count no other test uses, so it is new to this process
    fresh = RelaxedDesign(RelaxTemplate(("Trie", "Range", "Hash", "ODP")),
                          (4.0, 3.0, 5.0, 8.0))
    relax.refine(fresh, hw1(), N_ENTRIES, steps=5)
    assert relax.refine_trace_count() == before + 1
    relax.refine(fresh, hw2(), N_ENTRIES, steps=5)
    assert relax.refine_trace_count() == before + 1


def test_rehearsed_search_does_not_retrace_refinement():
    hw = hw1()
    kwargs = dict(population=8, generations=30, refine_top=2,
                  refine_steps=2, seed=7)
    first = search.population_search(
        WORKLOAD, hw, MIX, budget=search.SearchBudget(48), **kwargs)
    before = relax.refine_trace_count()
    again = search.population_search(
        WORKLOAD, hw, MIX, budget=search.SearchBudget(48), **kwargs)
    assert relax.refine_trace_count() == before
    assert again["design"].chain == first["design"].chain
    assert again["designs_costed"] == first["designs_costed"]
    assert again["cost_s"] == first["cost_s"]


def test_service_stats_count_refine_traces():
    hw = hw1()
    kwargs = dict(budget_designs=32, population=8, generations=10,
                  refine_top=2, refine_steps=2, seed=11)
    with DesignCalculatorService([hw]) as svc:
        svc.submit_search(WORKLOAD, hw, MIX, **kwargs).result(timeout=120)
        before = svc.stats()["refine_traces"]
        assert before == relax.refine_trace_count()
        svc.submit_search(WORKLOAD, hw, MIX, **kwargs).result(timeout=120)
        assert svc.stats()["refine_traces"] == before
