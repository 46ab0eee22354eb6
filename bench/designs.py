"""Designs of a configuration, drawn from the seed, and their program form.

A design is a plain tuple of :class:`bench.reference.Level`.  A
configuration's ``designs`` block says where designs come from:

* ``"families"`` -- named structures (B-tree, hash table, ...) whose
  levels share named numeric knobs, e.g. a skip list's pages and its
  ordered data pages have one ``page`` size;
* ``"completions"`` -- every chain of up to ``max_internal`` internal
  elements over ``candidates`` ending in one of ``terminals`` (a linked
  or skip list never directly follows another), each element with its
  own knob.

A knob takes one of ``knob_grid`` log-spaced integers between its
bounds, drawn uniformly, so every element a run can use is known
before it starts and its program object is built once
(:class:`ProgramSpecs`).  :func:`from_spec` reads a design the program
returned back into plain form.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import TERMINALS, UNLIMITED, Design, Level

INTERNALS = ("Hash", "Range", "B+", "CSB+", "Trie")


def knob_values(bounds: Sequence[int], grid: int) -> np.ndarray:
    """The distinct integers of a ``grid``-point log scale over bounds."""
    lo, hi = int(bounds[0]), int(bounds[1])
    return np.unique(np.round(np.geomspace(lo, hi, grid)).astype(np.int64))


class _Knobs:
    def __init__(self, block: Dict) -> None:
        grid = int(block["knob_grid"])
        self.values = {k: knob_values(v, grid)
                       for k, v in block["knobs"].items()
                       if isinstance(v, list)}

    def draw(self, rng: np.random.Generator, knob: str) -> int:
        values = self.values[knob]
        return int(values[int(rng.integers(len(values)))])


class Families(_Knobs):
    """Named structures with shared knobs (a ``"families"`` block)."""

    def __init__(self, block: Dict) -> None:
        super().__init__(block)
        self.families = block["families"]
        self.bloom_hashes = int(block["knobs"]["bloom_hashes"])
        self.names = sorted(self.families)

    def knob_names(self, family: str) -> List[str]:
        names: List[str] = []
        for level in self.families[family]:
            for k in level[1:]:
                if isinstance(k, str) and k not in names:
                    names.append(k)
        return names

    def draw_knobs(self, rng: np.random.Generator,
                   family: str) -> Dict[str, int]:
        return {k: self.draw(rng, "bloom_bits" if k == "bloom" else k)
                for k in self.knob_names(family)}

    def build(self, family: str, values: Dict[str, int]) -> Design:
        levels = []
        for spec in self.families[family]:
            name, knob = spec[0], spec[1]
            depth = next((v for v in spec[2:] if isinstance(v, int)), 0)
            bloom = (self.bloom_hashes, values["bloom"]) \
                if "bloom" in spec[2:] else None
            levels.append(Level(name, values[knob], depth, bloom))
        return tuple(levels)

    def draw_family(self, rng: np.random.Generator
                    ) -> Tuple[str, Dict[str, int]]:
        family = self.names[int(rng.integers(len(self.names)))]
        return family, self.draw_knobs(rng, family)

    def variant(self, rng: np.random.Generator, family: str,
                values: Dict[str, int], switch_p: float) -> Design:
        """A designer's next design: one knob redrawn, or (with
        probability ``switch_p``) another family with fresh knobs."""
        if rng.random() < switch_p:
            others = [f for f in self.names if f != family]
            other = others[int(rng.integers(len(others)))]
            return self.build(other, self.draw_knobs(rng, other))
        names = self.knob_names(family)
        knob = names[int(rng.integers(len(names)))]
        changed = dict(values)
        changed[knob] = self.draw(rng, "bloom_bits" if knob == "bloom"
                                  else knob)
        return self.build(family, changed)


class Completions(_Knobs):
    """Every chain of a ``"completions"`` block, with per-element knobs."""

    def __init__(self, block: Dict) -> None:
        super().__init__(block)
        self.trie_depth = int(block["trie_depth"])
        chains = []
        for k in range(int(block["max_internal"]) + 1):
            for internals in itertools.product(block["candidates"],
                                               repeat=k):
                if any(a in UNLIMITED and b in UNLIMITED
                       for a, b in zip(internals, internals[1:])):
                    continue
                for term in block["terminals"]:
                    chains.append(internals + (term,))
        self.chains = chains

    def build(self, rng: np.random.Generator,
              chain: Sequence[str]) -> Design:
        return tuple(Level(name, self.draw(rng, name),
                           self.trie_depth if name == "Trie" else 0)
                     for name in chain)

    def draw_design(self, rng: np.random.Generator) -> Design:
        return self.build(rng, self.chains[int(rng.integers(
            len(self.chains)))])

    def levels(self) -> List[Level]:
        return [Level(name, int(n), self.trie_depth if name == "Trie"
                      else 0)
                for name, values in self.values.items() for n in values]


def design_source(config: Dict):
    block = config["designs"]
    if block["kind"] == "families":
        return Families(block)
    if block["kind"] == "completions":
        return Completions(block)
    raise ValueError(f"unknown design source {block['kind']!r}")


class ProgramSpecs:
    """Builds the program's specifications for plain designs.

    Each distinct level becomes one program element, built once: the
    program validates every element it constructs, which costs far more
    than composing a chain from elements already built."""

    def __init__(self) -> None:
        from repro.core import elements as el
        self._el = el
        self._elements: Dict[Level, object] = {}

    def element(self, level: Level):
        element = self._elements.get(level)
        if element is None:
            el = self._el
            build = {"Hash": el.hash_element, "Range": el.range_element,
                     "B+": el.btree_internal, "CSB+": el.csb_internal,
                     "LL": el.linked_list_element,
                     "SL": el.skip_list_element,
                     "UDP": el.unordered_data_page,
                     "ODP": el.ordered_data_page}
            if level.name == "Trie":
                element = el.trie_element(level.n, level.depth)
            else:
                element = build[level.name](level.n)
            if level.bloom:
                element = element.with_values(
                    bloom_filters=("on", level.bloom[0], level.bloom[1]),
                    filters_memory_layout="scatter")
            self._elements[level] = element
        return element

    def spec(self, design: Design, name: str = "bench"):
        return self._el.DataStructureSpec(
            name, tuple(self.element(level) for level in design))


def from_spec(spec) -> Design:
    """Plain form of a specification the program returned."""
    levels = []
    for element in spec.chain:
        name = element.name
        if name in TERMINALS:
            n = element.capacity
        elif name in UNLIMITED:
            n = element.get("sub_block_capacity")[1]
        elif name in INTERNALS:
            n = element.fanout
        else:
            raise ValueError(f"element {name!r} is outside the reference")
        depth = int(element.get("recursion")[1]) if name == "Trie" else 0
        bloom = None
        bf = element.get("bloom_filters")
        if isinstance(bf, tuple) and bf[0] == "on":
            bloom = (int(bf[1]), int(bf[2]))
        levels.append(Level(name, int(n), depth, bloom))
    return tuple(levels)
