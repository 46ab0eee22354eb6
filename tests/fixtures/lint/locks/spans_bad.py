"""Seeded span-table violations (parsed by the analyzer, never run)."""
from repro.core import spans
from repro.core.spans import _LOCK, _TOTALS


def add(name, seconds):
    _TOTALS[name][0] += seconds             # unlocked guarded-global write


def read_all():
    return dict(spans._TOTALS)              # unlocked dotted read


def add_under_lock(name, seconds):
    with _LOCK:
        _TOTALS[name][0] += seconds
