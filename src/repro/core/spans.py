"""Named host spans: the program's only tracing code.

``with span(name, **ids):`` does two things at once:

* it opens a :class:`jax.profiler.TraceAnnotation`, so a running
  profiler records the span on the thread's host line, on the clock of
  the device trace, with ``ids`` (``rid``, ``designs``, ...) as the
  event's stats;
* it adds its own elapsed ``time.perf_counter()`` seconds and a count of
  one to a process-wide table keyed by ``name``, which :func:`totals`
  snapshots whether or not a profiler runs.

Spans are leaves: no span opens inside another on one thread (a
search's inner ``SUBMIT`` inside ``SEARCH_SCORE``, and ``GC``, which
interrupts whatever runs, excepted); causes link by ``rid``, not by
nesting.  With no profiler running a span costs the annotation's check,
two clock reads and one locked add: a few microseconds.

Beside the spans, :func:`add_records` counts the access records the
fused packer emits per operation, which a service's ``stats()`` carries
as ``records_n.<op>`` (:func:`record_counters`), and
:func:`add_grid_cells` counts the (design, point) cells a sweep packs as
one grid, carried as ``pack_grid_cells`` (:func:`grid_cells`).
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Iterable, Tuple

from jax.profiler import TraceAnnotation

#: one request's submission on the client's thread (``rid``, ``lane``,
#: ``parent``: the search a generation's sweep belongs to)
SUBMIT = "repro.submit"
#: the coalescing window, from its head's dequeue to its close
WINDOW = "repro.window"
#: packing one evaluation (``designs``; ``pinned`` 1 on a session hit)
PACK = "repro.pack"
#: splicing a (profile, axis) group of two or more evaluations into one
SPLICE = "repro.splice"
#: padding, placing and issuing one fused scoring call
DISPATCH = "repro.fused.dispatch"
#: waiting for and copying back one fused call's output
FETCH = "repro.fused.fetch"
#: resolving one request's future
FINALIZE = "repro.finalize"
#: a population search's phases, per generation (``rid`` of the search)
SEARCH_DECODE = "repro.search.decode"
SEARCH_SCORE = "repro.search.score"
SEARCH_VERIFY = "repro.search.verify"
SEARCH_REFINE = "repro.search.refine"
SEARCH_BREED = "repro.search.breed"
#: one Python garbage collection, on whichever thread triggered it
GC = "repro.gc"

NAMES = (SUBMIT, WINDOW, PACK, SPLICE, DISPATCH, FETCH, FINALIZE,
         SEARCH_DECODE, SEARCH_SCORE, SEARCH_VERIFY, SEARCH_REFINE,
         SEARCH_BREED, GC)

_LOCK = threading.Lock()
#: name -> [seconds, count]
_TOTALS: Dict[str, list] = {name: [0.0, 0] for name in NAMES if name != GC}
#: operation -> record slots the fused packer emitted with a nonzero
#: count, summed over workload points (``templatecost.emit_operation``)
_RECORDS: Dict[str, int] = {}
#: (design, point) cells packed by the sweep grid path
#: (``batchcost.pack_sweep``)
_GRID_CELLS = [0]
#: [seconds, count] of GC, written only by the collector's callback:
#: collections never overlap, and a callback taking ``_LOCK`` could
#: deadlock against a span that triggered the collection inside it
_GC_TOTAL = [0.0, 0]


class span:
    """Context manager: one profiler annotation and one timing count."""

    __slots__ = ("_name", "_note", "_t0")

    # the annotation stamps its start when it is made and its end first
    # thing on exit, before it records the event: the clock reads sit
    # just inside those stamps, so the count and the trace event agree
    def __init__(self, name: str, **ids) -> None:
        self._name = name
        self._note = TraceAnnotation(name, **ids)
        self._t0 = time.perf_counter()

    def __enter__(self) -> "span":
        self._note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        with _LOCK:
            entry = _TOTALS.setdefault(self._name, [0.0, 0])
            entry[0] += elapsed
            entry[1] += 1


def totals() -> Dict[str, Tuple[float, int]]:
    """``{name: (seconds, count)}`` summed over every span so far; every
    name of :data:`NAMES` is present, zero until first used."""
    with _LOCK:
        out = {name: (entry[0], entry[1]) for name, entry in _TOTALS.items()}
    out[GC] = (_GC_TOTAL[0], _GC_TOTAL[1])
    return out


def counters() -> Dict[str, float]:
    """:func:`totals` flattened as ``span_s.<name>`` (seconds) and
    ``span_n.<name>`` (count), the form a service's ``stats()`` carries."""
    out: Dict[str, float] = {}
    for name, (seconds, count) in totals().items():
        out[f"span_s.{name}"] = seconds
        out[f"span_n.{name}"] = count
    return out


def add_records(op: str, n: int) -> None:
    """Count ``n`` records emitted for one operation: one locked add."""
    with _LOCK:
        _RECORDS[op] = _RECORDS.get(op, 0) + n


def record_counters(ops: Iterable[str]) -> Dict[str, int]:
    """``records_n.<op>`` for every operation of ``ops``, zero until
    first emitted."""
    with _LOCK:
        return {f"records_n.{op}": _RECORDS.get(op, 0) for op in ops}


def add_grid_cells(n: int) -> None:
    """Count ``n`` (design, point) cells packed as a grid: one locked
    add."""
    with _LOCK:
        _GRID_CELLS[0] += n


def grid_cells() -> int:
    """The (design, point) cells packed as a grid so far."""
    with _LOCK:
        return _GRID_CELLS[0]


#: the collection in progress, as (annotation, start); install refcount
_GC_STATE: Dict[str, object] = {"open": None, "users": 0}


def _on_gc(phase: str, info: Dict) -> None:
    if phase == "start":
        note = TraceAnnotation(GC, generation=info["generation"])
        note.__enter__()
        _GC_STATE["open"] = (note, time.perf_counter())
        return
    opened, _GC_STATE["open"] = _GC_STATE["open"], None
    if opened is not None:
        note, t0 = opened
        _GC_TOTAL[0] += time.perf_counter() - t0
        _GC_TOTAL[1] += 1
        note.__exit__(None, None, None)


def install_gc_span() -> None:
    """Record every Python collection as a :data:`GC` span, until as many
    :func:`remove_gc_span` calls as installs have come."""
    with _LOCK:
        _GC_STATE["users"] += 1
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def remove_gc_span() -> None:
    with _LOCK:
        _GC_STATE["users"] = max(_GC_STATE["users"] - 1, 0)
        if not _GC_STATE["users"] and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
