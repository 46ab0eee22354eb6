"""Plain reference of the Data Calculator's cost semantics.

A straightforward, scalar restatement of the cost synthesis the
benchmark's configurations are judged by (paper §3, Fig. 5, Appendix E):
populate a chain of elements level by level, walk it for each operation
emitting access-primitive records (model, size, count), price every
record with the hardware profile's analytical Level-2 models, and sum.
It imports nothing of the program under test and takes nothing the
program made: designs arrive as plain :class:`Level` tuples, workloads
and hardware profiles as the numbers in a configuration file.

``get`` and ``update`` are defined here.  Any other operation is
defined by a file of its own, ``bench/reference_ops/<op>.py``, whose
``records(design, wl)`` builds the operation's records from this
module's helpers (:func:`geometry`, :func:`get_records`); an operation
with neither raises.

``dtype`` selects the precision the records are priced and summed in:
``float64`` is the reference; ``bfloat16`` (via ``ml_dtypes``) is the
control, the lower precision a fused scorer might be tempted to use.
The structure geometry (level sizes, skew weights) stays in float64 in
both, as the program computes it on the host.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

PTR_BYTES = 8
FENCE_BYTES = 8
#: size arguments are clipped into the analytical models' fitted range
X_LO, X_HI = 1.0, 1e12
#: the calculator's range when a configuration states no scan length
DEFAULT_SELECTIVITY = 0.001


class Level(NamedTuple):
    """One element of a design, root first; the last one is terminal.

    ``n`` is the fanout (Hash buckets, Range partitions, B+/CSB+ fanout,
    Trie radix), the page capacity of a linked or skip list, or the
    capacity of a terminal page.  ``depth`` is a Trie's recursion limit.
    ``bloom`` is ``(hashes, bits)`` when the element carries bloom
    filters (terminal leaves, or a Hash root)."""

    name: str
    n: int
    depth: int = 0
    bloom: Optional[Tuple[int, int]] = None


Design = Tuple[Level, ...]


class Workload(NamedTuple):
    n_entries: int
    n_queries: int
    key_bytes: int
    value_bytes: int
    zipf_alpha: float
    #: the share of the key space one range operation reads
    selectivity: float = DEFAULT_SELECTIVITY


# -- the element library: how each element partitions and lays out ---------
TERMINALS = ("UDP", "ODP")
UNLIMITED = ("LL", "SL")
#: partitioning of fixed-fanout internal elements
PARTITIONING = {"Hash": "func", "Range": "range", "Trie": "radix",
                "B+": "sorted", "CSB+": "sorted"}


def _recursion_limit(level: Level) -> int:
    """0 for no recursion; else the most levels the element may stack."""
    if level.name == "Trie":
        return level.depth
    if level.name in ("B+", "CSB+"):
        return 64          # "log n" recursion: bounded only by the data
    return 0


def _node_bytes(level: Level, fanout: int) -> float:
    """Bytes of one internal node: child pointers, fences, bloom bits."""
    if level.name == "CSB+":
        ptr = PTR_BYTES                  # children contiguous: one pointer
    else:
        ptr = fanout * PTR_BYTES         # one pointer per sub-block
    fences = (fanout - 1) * FENCE_BYTES if level.name in ("B+", "CSB+") \
        else 0.0
    bloom = fanout * level.bloom[1] / 8.0 if level.bloom else 0.0
    return ptr + fences + bloom


class _Node(NamedTuple):
    level: Level
    n_nodes: int
    node_bytes: float
    entries: float          # data entries routed through one node
    region: float           # cache region of a random access to it


def geometry(design: Design, wl: Workload) -> Tuple[_Node, ...]:
    """Populate the structure: nodes per level, their sizes and regions."""
    if not design or design[-1].name not in TERMINALS:
        raise ValueError(f"design must end in a terminal page: {design}")
    n = max(wl.n_entries, 1)
    term = design[-1]
    capacity = term.n or 256
    n_leaves = max(math.ceil(n / capacity), 1)
    pair = wl.key_bytes + wl.value_bytes
    rows: List[List] = []           # [level, n_nodes, node_bytes, entries]
    blocks, entries = 1, float(n)
    for level in design[:-1]:
        if level.name in UNLIMITED:
            rows.append([level, blocks, 2 * PTR_BYTES,
                         entries / max(blocks, 1)])
            continue
        fanout = level.n or 2
        nb = _node_bytes(level, fanout)
        limit = _recursion_limit(level)
        if limit:
            depth = 0
            while blocks * fanout < n_leaves and depth < limit - 1:
                rows.append([level, blocks, nb, entries / blocks])
                blocks *= fanout
                depth += 1
        rows.append([level, blocks, nb, entries / blocks])
        blocks *= fanout
    partitioned = len(design) > 1 and design[-2].name not in UNLIMITED
    n_term = max(n_leaves, blocks if partitioned else n_leaves)
    term_bytes = min(capacity, n / max(n_term, 1)) * pair
    rows.append([term, int(n_term), max(term_bytes, pair),
                 entries / max(n_term, 1)])
    out = []
    cumulative = 0.0
    for level, n_nodes, nb, ent in rows:
        cumulative += n_nodes * nb
        region = cumulative
        if level.name == "CSB+":
            # children laid out contiguously: an access resolves inside
            # the parent's child group
            region = min(cumulative, max((level.n or 2) * nb, nb))
        out.append(_Node(level, n_nodes, nb, ent, region))
    return tuple(out)


@functools.lru_cache(maxsize=4096)
def _zipf_collision(n_items: int, alpha: float) -> float:
    """sum of squared Zipf(alpha) masses over ``n_items`` ranks."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** (-alpha)
    w /= w.sum()
    return float((w * w).sum())


def _skew(node: _Node, wl: Workload) -> float:
    """Region multiplier of a random access under Zipf skew (§3)."""
    if wl.zipf_alpha <= 0.0:
        return 1.0
    p = _zipf_collision(min(max(node.n_nodes, 1), 4096), wl.zipf_alpha)
    s = wl.n_queries
    if p <= 0.0 or s <= 1:
        return 1.0
    s0 = min(max(1.0 / p, 1.0), s)
    total = s0 + (math.log(s) - math.log(s0)) / p
    return min(total / s, 1.0)


Record = Tuple[str, float, float]        # (Level-2 model, size, count)


def get_records(design: Design, wl: Workload) -> List[Record]:
    """The access records of one point lookup."""
    nodes = geometry(design, wl)
    term = nodes[-1]
    cap = term.level.n or 256
    recs: List[Record] = []

    def random_access(node: _Node) -> None:
        recs.append(("random_memory_access",
                     max(node.region * _skew(node, wl), 1.0), 1.0))

    for node in nodes[:-1]:
        level = node.level
        if level.name == "SL":
            # skip links: a binary search over the page minima
            recs.append(("binary_search_columnstore",
                         max(max(node.entries / cap, 1.0) * FENCE_BYTES,
                             1.0), 1.0))
        elif level.name == "LL":
            pages = max(node.entries / cap, 1.0)
            visited = (pages + 1) / 2.0
            random_access(term)
            if visited > 1:
                recs.append(("random_memory_access", term.region,
                             visited - 1))
                recs.append(("scalar_scan_columnstore_equal",
                             cap * wl.key_bytes, visited - 1))
        elif PARTITIONING[level.name] in ("func", "range", "radix"):
            random_access(node)
            if PARTITIONING[level.name] == "func":
                recs.append(("hash_probe_multiply_shift",
                             max(node.n_nodes * (level.n or 1) * PTR_BYTES,
                                 1.0), 1.0))
        else:                                   # sorted fences
            random_access(node)
            recs.append(("binary_search_rowstore",
                         max(max((level.n or 2) - 1, 1) * FENCE_BYTES, 1.0),
                         1.0))
            if level.bloom:
                recs.append(("bloom_probe_multiply_shift",
                             max(level.bloom[1] / 8.0, 1.0), 1.0))
    entries = max(term.entries, 1.0)
    random_access(term)
    if term.level.bloom:
        recs.append(("bloom_probe_multiply_shift",
                     max(term.level.bloom[1] / 8.0, 1.0), 1.0))
    if term.level.name == "ODP":
        recs.append(("binary_search_columnstore",
                     max(entries * wl.key_bytes, 1.0), 1.0))
    else:                                       # expected half-page scan
        recs.append(("scalar_scan_columnstore_equal",
                     entries * wl.key_bytes / 2, 1.0))
    recs.append(("random_memory_access",
                 max(entries * wl.value_bytes, 1.0), 1.0))
    return recs


OPS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "reference_ops")


@functools.lru_cache(maxsize=None)
def _op_file(op: str):
    """The ``records`` function of ``reference_ops/<op>.py``, or None."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", op):
        return None
    path = os.path.join(OPS_DIR, f"{op}.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_reference_op_" + op, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.records


def op_records(op: str, design: Design, wl: Workload) -> List[Record]:
    if op == "get":
        return get_records(design, wl)
    if op == "update":          # a point query, then one value write
        return get_records(design, wl) + [
            ("serial_write", max(float(wl.value_bytes), 1.0), 1.0)]
    records = _op_file(op)
    if records is None:
        raise ValueError(f"the reference defines get and update, and no "
                         f"reference_ops/{op}.py defines {op!r}")
    return records(design, wl)


def mix_records(design: Design, wl: Workload,
                mix: Dict[str, float]) -> List[Record]:
    """Every record of an operation mix, its count scaled by the mix."""
    out: List[Record] = []
    for op, weight in mix.items():
        out += [(m, size, count * float(weight))
                for m, size, count in op_records(op, design, wl)]
    return out


# -- Level-2 models of an analytical hardware profile ------------------------
def models(hw: Dict[str, float]) -> Dict[str, Tuple[str, Dict]]:
    """The analytical profile's model zoo from its published constants.

    ``hw`` holds ``cpu_ns_per_cmp``, ``l1_bytes``, ``l2_bytes``,
    ``l3_bytes``, ``l1_ns``, ``l2_ns``, ``l3_ns``, ``mem_ns`` and
    ``bw_bytes_per_s``."""
    ns = 1e-9

    def cache_steps(per_elem: float) -> Tuple[str, Dict]:
        return ("sigmoids", {
            "c": [(hw["l2_ns"] - hw["l1_ns"]) * ns,
                  (hw["l3_ns"] - hw["l2_ns"]) * ns,
                  (hw["mem_ns"] - hw["l3_ns"]) * ns],
            "k": [8.0, 8.0, 8.0],
            "x0": [math.log(hw[k] / per_elem)
                   for k in ("l1_bytes", "l2_bytes", "l3_bytes")],
            "y0": hw["l1_ns"] * ns})

    scan = ("linear", {"w": [hw["cpu_ns_per_cmp"] * ns], "y0": 5 * ns})
    write = ("linear", {"w": [16.0 / hw["bw_bytes_per_s"]], "y0": 10 * ns})
    search = ("log_linear", {
        "w": [0.0, (hw["mem_ns"] / 3 + hw["cpu_ns_per_cmp"]) * ns],
        "y0": 5 * ns})
    ra = cache_steps(8.0)
    return {
        "scalar_scan_rowstore_equal": scan,
        "scalar_scan_columnstore_equal": scan,
        "scalar_scan_columnstore_range": scan,
        "binary_search_rowstore": search,
        "binary_search_columnstore": search,
        "hash_probe_multiply_shift": ra,
        "bloom_probe_multiply_shift": ra,
        "random_memory_access": ra,
        "batched_random_memory_access": cache_steps(64.0),
        "serial_write": write,
        "ordered_batch_write": write,
        "scattered_batch_write": ra,
    }


def _predict(model: Tuple[str, Dict], x, dtype):
    kind, p = model
    c = lambda v: np.asarray(v, dtype=dtype)          # noqa: E731
    x = np.clip(c(x), c(X_LO), c(X_HI))
    if kind == "linear":
        y = x * c(p["w"][0]) + c(p["y0"])
    elif kind == "log_linear":
        y = x * c(p["w"][0]) + np.log(x + c(1.0)) * c(p["w"][1]) \
            + c(p["y0"])
    elif kind == "sigmoids":
        lx = np.log(x + c(1.0))
        y = c(p["y0"])
        for ci, ki, xi in zip(p["c"], p["k"], p["x0"]):
            z = c(ki) * (lx - c(xi))
            with np.errstate(over="ignore"):    # exp(-z) -> inf: step is 0
                step = c(1.0) / (c(1.0) + np.exp(-z))
            y = y + c(ci) * step
    else:
        raise ValueError(f"model kind {kind!r} is not in the reference")
    return np.maximum(c(y), c(0.0))


def price(records: Sequence[Record], hw: Dict[str, float],
          dtype=np.float64) -> float:
    """Sum of count x model(size) over ``records``, in ``dtype``."""
    zoo = models(hw)
    total = np.asarray(0.0, dtype=dtype)
    for name, size, count in records:
        y = _predict(zoo[name], size, dtype)
        total = np.asarray(total + np.asarray(count, dtype=dtype) * y,
                           dtype=dtype)
    return float(total)


def cost(design: Design, wl: Workload, hw: Dict[str, float],
         mix: Dict[str, float], dtype=np.float64) -> float:
    """Seconds the mix's operations take on ``design`` under ``hw``."""
    return price(mix_records(design, wl, mix), hw, dtype)
