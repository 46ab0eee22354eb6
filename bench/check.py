"""The comparison that decides ``correct``.

A cell hands over *comparisons*: an answer the timed path produced,
with the plain inputs it answers (design, workload, hardware profile,
operation mix).  Each is priced again by the plain reference
(:mod:`bench.reference`, float64) and the widest relative gap is the
number held against the configuration's limit.  The control prices the
same inputs with the reference in bfloat16 in place of the program.

Every number compared is printed with its limit; ``correct`` is true
when all of them hold.
"""
from __future__ import annotations

import sys
from typing import Dict, List, NamedTuple, Sequence

import numpy as np

from bench import reference as ref
from bench.reference import Design, Workload


class Comparison(NamedTuple):
    got: float
    design: Design
    workload: Workload
    hw: str
    mix: Dict[str, float]


def rel_err(got: float, want: float) -> float:
    if not np.isfinite(got):        # the largest finite gap: JSON has no inf
        return float(np.finfo(np.float64).max)
    return abs(got - want) / max(abs(want), 1e-30)


def max_rel_err(comparisons: Sequence[Comparison], hardware: Dict,
                control: bool = False) -> float:
    """Widest relative gap to the float64 reference; with ``control``
    the bfloat16 reference stands in for the program's answers."""
    worst = 0.0
    for c in comparisons:
        want = ref.cost(c.design, c.workload, hardware[c.hw], c.mix)
        got = c.got
        if control:
            import ml_dtypes
            got = ref.cost(c.design, c.workload, hardware[c.hw], c.mix,
                           dtype=ml_dtypes.bfloat16)
        worst = max(worst, rel_err(float(got), want))
    return worst


class Checks:
    """Numbers compared, each with its limit and which side must hold."""

    def __init__(self) -> None:
        self.items: Dict[str, Dict] = {}

    def at_most(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit, "rule": "<="}

    def at_least(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit, "rule": ">="}

    @property
    def correct(self) -> bool:
        return all((v["value"] <= v["limit"]) if v["rule"] == "<="
                   else (v["value"] >= v["limit"])
                   for v in self.items.values())

    def lines(self) -> List[str]:
        return [f"check {k}: {v['value']!r} {v['rule']} {v['limit']!r}"
                for k, v in self.items.items()]

    def report(self, stream=sys.stderr) -> None:
        for line in self.lines():
            print(line, file=stream, flush=True)
        print(f"correct: {self.correct}", file=stream, flush=True)
