"""Continuous relaxation of design knobs for gradient-guided search.

The fused engine (:mod:`repro.core.devicecost`) scores designs through
*differentiable* parameter banks — the linear-basis and sigmoid Level-2
model families are smooth in their size argument.  This module exploits
that: a discrete element chain is re-parameterized as a
:class:`RelaxedDesign` — a structural :class:`RelaxTemplate` (which
element class sits at each level) plus a continuous knob vector ``theta``
in log2 space (per-level fanout / partition count, terminal capacity,
optional bloom bits) — and a smooth surrogate of the chain's per-query
cost is evaluated against the profile's *real* bank rows via
:func:`repro.core.devicecost.bank_predict`.  ``jax.grad`` through that
surrogate plus :mod:`repro.optim.adamw` gives :func:`refine`: a few
optimizer steps that walk a knob vector downhill.

The surrogate is a *proposer*, not an oracle: it shares the fitted bank
rows with the fused engine but simplifies the geometry (smooth level
depths, uniform partitioning, no cache-line effects beyond what the
sigmoid rows encode).  :mod:`repro.core.search` therefore only ever uses
gradients to propose knob updates; every decoded discrete design is
scored by the real fused engine and winners are re-verified against the
scalar oracle (``repro.core.synthesis.cost_workload``) — see
``docs/design_search.md`` for the contract.

The objective is conditioned on the workload's read fraction (an
``update`` in the mix pays the get path plus a serial write), so a
read-fraction axis relaxes into the same knob space — the
"read-fraction-conditioned split" of a hybrid design is a per-point
argmin over the relaxed continuum.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import devicecost, elements as el
from repro.core.elements import DataStructureSpec, Element
from repro.core.hardware import HardwareProfile
from repro.optim.adamw import adamw_init, adamw_update, apply_updates

# ---------------------------------------------------------------------------
# Templates: the discrete skeleton the knobs hang off.
# ---------------------------------------------------------------------------
#: internal element classes with a tunable ("fixed", n) fanout knob
INTERNAL_NAMES = ("Hash", "Range", "B+", "CSB+", "Trie")
#: terminal element classes with a tunable ("terminal", c) capacity knob
TERMINAL_NAMES = ("UDP", "ODP")

#: log2 knob bounds: fanouts/partition counts in [2, 65536]
FANOUT_LO, FANOUT_HI = 1.0, 16.0
#: terminal capacities in [16, 65536] (the hill-climb mutation range)
CAPACITY_LO, CAPACITY_HI = 4.0, 16.0
#: bloom filter bits in [1024, 1048576]
BLOOM_LO, BLOOM_HI = 10.0, 20.0

_INTERNAL_BUILDERS = {
    "Hash": lambda n: el.hash_element(n),
    "Range": lambda n: el.range_element(n),
    "B+": lambda n: el.btree_internal(n),
    "CSB+": lambda n: el.csb_internal(n),
    "Trie": lambda n: el.trie_element(n, 4),
}
_TERMINAL_BUILDERS = {
    "UDP": lambda c: el.unordered_data_page(c),
    "ODP": lambda c: el.ordered_data_page(c),
}


@dataclasses.dataclass(frozen=True)
class RelaxTemplate:
    """The structural skeleton of a relaxed design.

    ``levels`` holds the internal element-class names root-first with the
    terminal class last; ``bloom`` adds a per-sub-block bloom filter (and
    its bits knob) to the root level, valid only when the root is a Hash.
    The knob vector of a template has one log2 entry per level plus one
    trailing bloom-bits entry when ``bloom`` is set.
    """

    levels: Tuple[str, ...]
    bloom: bool = False

    def __post_init__(self) -> None:
        if len(self.levels) < 1:
            raise ValueError("template needs at least a terminal level")
        if self.levels[-1] not in TERMINAL_NAMES:
            raise ValueError(f"unknown terminal class: {self.levels[-1]!r}")
        for name in self.levels[:-1]:
            if name not in INTERNAL_NAMES:
                raise ValueError(f"unknown internal class: {name!r}")
        if self.bloom and (len(self.levels) < 2
                           or self.levels[0] != "Hash"):
            raise ValueError("bloom knob requires a Hash root level")

    @property
    def n_knobs(self) -> int:
        return len(self.levels) + (1 if self.bloom else 0)

    def knob_bounds(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-knob (lo, hi) log2 bounds, aligned with ``theta``."""
        lo = [FANOUT_LO] * (len(self.levels) - 1) + [CAPACITY_LO]
        hi = [FANOUT_HI] * (len(self.levels) - 1) + [CAPACITY_HI]
        if self.bloom:
            lo.append(BLOOM_LO)
            hi.append(BLOOM_HI)
        return np.asarray(lo), np.asarray(hi)

    def describe(self) -> str:
        tag = "+BF" if self.bloom else ""
        return " -> ".join(self.levels) + tag


@dataclasses.dataclass(frozen=True)
class RelaxedDesign:
    """One point of the relaxed continuum: a template plus log2 knobs."""

    template: RelaxTemplate
    theta: Tuple[float, ...]

    def __post_init__(self) -> None:
        if len(self.theta) != self.template.n_knobs:
            raise ValueError(
                f"{len(self.theta)} knobs for a "
                f"{self.template.n_knobs}-knob template "
                f"{self.template.describe()!r}")

    def clipped(self) -> "RelaxedDesign":
        lo, hi = self.template.knob_bounds()
        return RelaxedDesign(
            self.template,
            tuple(float(v) for v in np.clip(self.theta, lo, hi)))


def decode(design: RelaxedDesign, name: str = "relaxed"
           ) -> DataStructureSpec:
    """Round a relaxed design back to a discrete, valid specification.

    Knobs round to the nearest integer in linear space (clipped to the
    template's bounds first), so two designs within half an integer knob
    step decode identically — the discretization the search's seen-set
    dedups on.
    """
    design = design.clipped()
    template = design.template
    theta = design.theta
    chain = []
    for i, level in enumerate(template.levels[:-1]):
        fanout = max(int(round(2.0 ** theta[i])), 2)
        element = _INTERNAL_BUILDERS[level](fanout)
        if i == 0 and template.bloom:
            bits = max(int(round(2.0 ** theta[-1])), 8)
            element = element.with_values(
                bloom_filters=("on", 2, bits),
                filters_memory_layout="scatter")
        chain.append(element)
    capacity = max(int(round(2.0 ** theta[len(template.levels) - 1])), 16)
    chain.append(_TERMINAL_BUILDERS[template.levels[-1]](capacity))
    return DataStructureSpec(name, tuple(chain))


def encode(spec: DataStructureSpec) -> Optional[RelaxedDesign]:
    """The inverse of :func:`decode` where one exists.

    Returns ``None`` for chains outside the relaxable family (unlimited
    fanouts, unknown element classes, non-knob primitive settings), so
    callers can seed a population from discrete search results without
    special-casing."""
    levels = []
    theta = []
    bloom = False
    for i, element in enumerate(spec.chain[:-1]):
        if element.name not in INTERNAL_NAMES:
            return None
        fanout = element.fanout
        if fanout is None:
            return None
        levels.append(element.name)
        theta.append(float(np.log2(fanout)))
        bf = element.get("bloom_filters")
        if isinstance(bf, tuple) and bf[0] == "on":
            if i != 0 or element.name != "Hash":
                return None
            bloom = True
            bloom_theta = float(np.log2(bf[2]))
    terminal = spec.chain[-1]
    if terminal.name not in TERMINAL_NAMES or terminal.capacity is None:
        return None
    levels.append(terminal.name)
    theta.append(float(np.log2(terminal.capacity)))
    if bloom:
        theta.append(bloom_theta)
    try:
        template = RelaxTemplate(tuple(levels), bloom)
    except ValueError:
        return None
    return RelaxedDesign(template, tuple(theta)).clipped()


# ---------------------------------------------------------------------------
# The smooth surrogate: real bank rows, relaxed geometry.
# ---------------------------------------------------------------------------
#: Level-2 model name used per surrogate term
_SORTED_SEARCH = "binary_search_columnstore"
_HASH_PROBE = "hash_probe_multiply_shift"
_BLOOM_PROBE = "bloom_probe_multiply_shift"
_RANDOM_ACCESS = "random_memory_access"
_SCAN = "scalar_scan_columnstore_equal"
_SERIAL_WRITE = "serial_write"

_SURROGATE_MODELS = (_SORTED_SEARCH, _HASH_PROBE, _BLOOM_PROBE,
                     _RANDOM_ACCESS, _SCAN, _SERIAL_WRITE)


def _surrogate_rows() -> Dict[str, int]:
    """Interned bank-row ids of the surrogate's model zoo (process-wide,
    shared with the fused engine's frontier records)."""
    return {name: devicecost.model_id(name) for name in _SURROGATE_MODELS}


def _cost_fn(template: RelaxTemplate):
    """The surrogate's per-query cost ``cost(theta, banks, n_entries,
    read_fraction, value_bytes)`` of one template, untraced.

    The template's level structure is baked in statically (a bounded set
    of templates appears in any search run, so the compile set is
    bounded); banks, data size and read fraction stay traced inputs —
    a hardware swap reuses every compiled function built from it exactly
    like the fused scorer reuses its executable.
    """
    rows = _surrogate_rows()
    levels = template.levels
    bloom = template.bloom

    def cost(theta, banks, n_entries, read_fraction, value_bytes):
        cap = 2.0 ** theta[len(levels) - 1]
        xs = []          # model input sizes, one per surrogate term
        ids = []         # bank rows, aligned with xs
        weights = []     # smooth visit counts, aligned with xs
        n = n_entries
        for i, level in enumerate(levels[:-1]):
            fanout = 2.0 ** theta[i]
            log_f = jnp.log(jnp.maximum(fanout, 2.0))
            if level in ("B+", "CSB+"):
                # recursive sorted level: height to reach leaves of the
                # terminal's capacity, one bounded search per node
                depth = jnp.maximum(
                    jnp.log(jnp.maximum(n / cap, 2.0)) / log_f, 1.0)
                ids.append(rows[_SORTED_SEARCH])
                xs.append(fanout)
                weights.append(depth)
                n = cap
            elif level == "Range":
                ids.append(rows[_SORTED_SEARCH])
                xs.append(fanout)
                weights.append(jnp.asarray(1.0))
                n = n / fanout
            elif level == "Hash":
                if i == 0 and bloom:
                    ids.append(rows[_BLOOM_PROBE])
                    xs.append(2.0 ** theta[-1] / 8.0)
                    weights.append(jnp.asarray(1.0))
                ids.append(rows[_HASH_PROBE])
                xs.append(fanout)
                weights.append(jnp.asarray(1.0))
                ids.append(rows[_RANDOM_ACCESS])
                xs.append(jnp.maximum(n, 1.0))
                weights.append(jnp.asarray(1.0))
                n = n / fanout
            else:      # Trie: radix descent, one random access per hop
                depth = jnp.minimum(
                    jnp.log(jnp.maximum(n, 2.0)) / log_f, 4.0)
                ids.append(rows[_RANDOM_ACCESS])
                xs.append(fanout)
                weights.append(depth)
                n = n / fanout ** depth
            n = jnp.maximum(n, 1.0)
        page = jnp.minimum(jnp.maximum(n, 1.0), cap)
        if levels[-1] == "ODP":
            ids.append(rows[_SORTED_SEARCH])
            xs.append(page)
            weights.append(jnp.asarray(1.0))
        else:          # UDP: expected half-page scan
            ids.append(rows[_SCAN])
            xs.append(0.5 * page)
            weights.append(jnp.asarray(1.0))
        # writes pay the read path plus a serial value write
        ids.append(rows[_SERIAL_WRITE])
        xs.append(value_bytes)
        weights.append(1.0 - read_fraction)
        y = devicecost.bank_predict(
            banks, jnp.asarray(ids, jnp.int32), jnp.stack(xs),
            with_knn=False)
        return (jnp.stack(weights) * y).sum()

    return cost


@functools.lru_cache(maxsize=512)
def _surrogate_fn(template: RelaxTemplate):
    """The jitted ``(cost, grad_theta)`` function of one template."""
    return jax.jit(jax.value_and_grad(_cost_fn(template)))


def surrogate_cost(design: RelaxedDesign, hw: HardwareProfile,
                   n_entries: float, read_fraction: float = 1.0,
                   value_bytes: float = 8.0) -> float:
    """The smooth surrogate's per-query cost estimate (diagnostics)."""
    value, _ = _surrogate_fn(design.template)(
        jnp.asarray(design.theta, jnp.float32),
        devicecost.device_table(hw).banks,
        jnp.asarray(float(n_entries), jnp.float32),
        jnp.asarray(float(read_fraction), jnp.float32),
        jnp.asarray(float(value_bytes), jnp.float32))
    return float(value)


@dataclasses.dataclass(frozen=True)
class _RefineConfig:
    """The RunConfig slice :func:`repro.optim.adamw.adamw_update` reads —
    a constant schedule (no warmup, no cosine decay tail)."""

    learning_rate: float
    warmup_steps: int = 0
    total_steps: int = 1 << 30     # flat schedule over any step count
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.95


#: traced-function entry counter of :func:`_refine_fn` — increments only
#: while jax (re)traces a refinement loop, i.e. once per compiled
#: (template, steps, learning rate); tests probe it to assert that repeat
#: refinements and hardware swaps never recompile
_REFINE_TRACES = [0]


def refine_trace_count() -> int:
    return _REFINE_TRACES[0]


@functools.lru_cache(maxsize=512)
def _refine_fn(template: RelaxTemplate, steps: int, learning_rate: float):
    """The jitted ``(theta, banks, n_entries, read_fraction, value_bytes)
    -> theta`` refinement loop of one template: ``steps`` AdamW steps on
    the surrogate, each projected back into the template's knob bounds
    (baked in as constants).  A ``fori_loop``, so the compile stays the
    size of one step's."""
    grad_fn = jax.grad(_cost_fn(template))
    run = _RefineConfig(learning_rate=learning_rate)
    lo, hi = template.knob_bounds()

    def loop(theta, banks, n_entries, read_fraction, value_bytes):
        _REFINE_TRACES[0] += 1

        def step(_, carry):
            params, state = carry
            grad = grad_fn(params["theta"], banks, n_entries,
                           read_fraction, value_bytes)
            updates, state = adamw_update({"theta": grad}, state, params,
                                          run)
            params = apply_updates(params, updates)
            params = {"theta": jnp.clip(params["theta"],
                                        jnp.asarray(lo, jnp.float32),
                                        jnp.asarray(hi, jnp.float32))}
            return params, state

        params = {"theta": theta}
        params, _ = jax.lax.fori_loop(0, steps, step,
                                      (params, adamw_init(params)))
        return params["theta"]

    return jax.jit(loop)


def refine(design: RelaxedDesign, hw: HardwareProfile,
           n_entries: float, read_fraction: float = 1.0,
           value_bytes: float = 8.0, steps: int = 8,
           learning_rate: float = 0.35) -> RelaxedDesign:
    """Walk a knob vector downhill on the surrogate with AdamW.

    Returns the refined (clipped) design; the caller decodes it and
    scores the discrete result with the real fused engine — gradients
    only ever *propose*.  Knobs are projected back into the template's
    log2 bounds after every step, so the optimizer cannot escape the
    decodable continuum.  The whole loop is one compiled call per
    (template, steps, learning rate): one dispatch, one fetch.
    """
    fn = _refine_fn(design.template, max(int(steps), 1),
                    float(learning_rate))
    theta = fn(np.asarray(design.theta, np.float32),
               devicecost.device_table(hw).banks,
               np.float32(n_entries), np.float32(read_fraction),
               np.float32(value_bytes))
    return RelaxedDesign(design.template,
                         tuple(float(v) for v in np.asarray(theta,
                                                            np.float64)))


def read_fraction_of(mix: Optional[Dict[str, float]],
                     default_queries: float = 100.0) -> float:
    """The read share of an operation mix (``get``/``range_get`` weight
    over total) — the conditioning input of the relaxed objective."""
    if not mix:
        return 1.0
    total = sum(float(v) for v in mix.values())
    if total <= 0.0:
        return 1.0
    reads = sum(float(v) for op, v in mix.items()
                if op in ("get", "range_get"))
    return reads / total
