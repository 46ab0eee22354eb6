"""The general traffic generator: a mix's parameter file plus a seed.

Every traffic mix is a data file ``bench/traffic/<name>.json`` whose
``kind`` selects one of three shapes this module draws from the seed:

``open_whatif``
    Designer sessions asking what-if questions on an open loop: Poisson
    arrivals at ``rate_per_s`` spread over ``sessions``; each session asks
    a run of questions (geometric length, mean ``run_mean``) about one
    baseline (design, hardware, workload) before it draws a new one.
    Questions change the design, the hardware or the workload in the
    proportions of ``question_mix``.
``closed_sweep``
    ``clients`` callers, each submitting fresh (designs x workload-point)
    sweeps back to back: ``designs`` designs drawn per sweep, over the
    update shares and Zipf constants of the point axis.
``closed_search``
    Back-to-back design searches, one caller; the run searches the fixed
    list of ``searches`` search seeds, in an order the seed permutes, so
    every run does the same work.

Nothing here touches the program: questions carry plain designs
(:mod:`bench.designs`) and plain workloads (:class:`bench.reference.
Workload`).
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from bench.designs import Completions, Families, design_source
from bench.reference import Design, Workload

HERE = os.path.dirname(os.path.abspath(__file__))

#: independent random streams drawn from one run seed
STREAM_WHATIF, STREAM_SWEEP, STREAM_SEARCH, STREAM_CHECK, STREAM_WARM = \
    range(1, 6)


def load_traffic(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        return json.load(fh)


def load_config(name: str) -> Dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as fh:
        return json.load(fh)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         *stream]))


def base_workload(config: Dict) -> Workload:
    return Workload(int(config["recordcount"]), int(config["n_queries"]),
                    int(config["key_bytes"]), int(config["value_bytes"]),
                    float(config["zipf_alpha"]))


def config_mix(config: Dict) -> Dict[str, float]:
    return {op: float(v) for op, v in config["operations"].items()}


# -- open-loop what-if questions ---------------------------------------------
class Question(NamedTuple):
    due_s: float
    session: int
    kind: str                    # "design" | "hardware" | "workload"
    design: Design               # the baseline design
    variant: Optional[Design]    # design questions: the new design
    hw: str
    new_hw: Optional[str]        # hardware questions: the new profile
    workload: Workload
    new_workload: Optional[Workload]   # workload questions


class _Session(NamedTuple):
    family: str
    values: Dict[str, int]
    design: Design
    hw: str


def workload_variant(base: Workload, change: Dict) -> Workload:
    wl = base
    if "zipf_alpha" in change:
        wl = wl._replace(zipf_alpha=float(change["zipf_alpha"]))
    if "n_entries_scale" in change:
        wl = wl._replace(n_entries=int(wl.n_entries
                                       * change["n_entries_scale"]))
    return wl


def whatif_questions(config: Dict, params: Dict, seed: int,
                     seconds: float, stream: int = STREAM_WHATIF
                     ) -> List[Question]:
    """Every question due in ``[0, seconds)``, in due order."""
    source = design_source(config)
    if not isinstance(source, Families):
        raise ValueError("what-if traffic needs a families design block")
    rng = rng_for(seed, stream)
    rate = float(params["rate_per_s"])
    n_sessions = int(params["sessions"])
    kinds = sorted(params["question_mix"])
    kind_p = np.asarray([params["question_mix"][k] for k in kinds], float)
    kind_p /= kind_p.sum()
    hws = sorted(config["hardware"])
    base_wl = base_workload(config)
    changes = params["workload_variants"]
    sessions: List[Optional[_Session]] = [None] * n_sessions
    left = [0] * n_sessions
    out: List[Question] = []
    t = 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return out
        s = int(rng.integers(n_sessions))
        if left[s] == 0:
            family, values = source.draw_family(rng)
            sessions[s] = _Session(family, values,
                                   source.build(family, values),
                                   hws[int(rng.integers(len(hws)))])
            left[s] = int(rng.geometric(1.0 / float(params["run_mean"])))
        left[s] -= 1
        st = sessions[s]
        kind = kinds[int(rng.choice(len(kinds), p=kind_p))]
        variant = new_hw = new_wl = None
        if kind == "design":
            variant = source.variant(rng, st.family, st.values,
                                     float(params["variant_switch_p"]))
        elif kind == "hardware":
            others = [h for h in hws if h != st.hw]
            new_hw = others[int(rng.integers(len(others)))]
        elif kind == "workload":
            new_wl = workload_variant(
                base_wl, changes[int(rng.integers(len(changes)))])
        else:
            raise ValueError(f"unknown question kind {kind!r}")
        out.append(Question(t, s, kind, st.design, variant, st.hw, new_hw,
                            base_wl, new_wl))


# -- closed-loop sweeps -------------------------------------------------------
def sweep_points(config: Dict, params: Dict
                 ) -> List[Tuple[Workload, Dict[str, float]]]:
    """The point axis: each Zipf constant x each update share."""
    share = params["update_share"]
    shares = np.linspace(float(share["from"]), float(share["to"]),
                         int(share["steps"]))
    ops = float(sum(config["operations"].values()))
    points = []
    for alpha in params["zipf_alphas"]:
        wl = base_workload(config)._replace(zipf_alpha=float(alpha))
        for u in shares:
            points.append((wl, {"get": ops * (1.0 - float(u)),
                                "update": ops * float(u)}))
    return points


def sweep_designs(config: Dict, params: Dict, seed: int, client: int,
                  index: int, source: Optional[Completions] = None,
                  stream: int = STREAM_SWEEP,
                  count: Optional[int] = None) -> List[Design]:
    """The ``index``-th sweep of ``client``: fresh designs from the seed."""
    source = source or design_source(config)
    rng = rng_for(seed, stream, client, index)
    return [source.draw_design(rng)
            for _ in range(int(count or params["designs"]))]


# -- closed-loop searches -----------------------------------------------------
def search_seeds(params: Dict, seed: int) -> List[int]:
    """The run's search seeds: the fixed list, permuted by the seed."""
    fixed = [int(params["search_seed_base"]) + i
             for i in range(int(params["searches"]))]
    order = rng_for(seed, STREAM_SEARCH).permutation(len(fixed))
    return [fixed[i] for i in order]


def search_start_designs(params: Dict) -> List[Design]:
    from bench.reference import Level
    return [tuple(Level(*level) for level in design)
            for design in params["start_designs"]]


def sample(rng: np.random.Generator, n: int, k: int) -> Sequence[int]:
    """``min(k, n)`` distinct indices of ``range(n)``, sorted."""
    return sorted(rng.choice(n, size=min(k, n), replace=False).tolist())
