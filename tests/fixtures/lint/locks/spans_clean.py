"""The span table read and written under its lock (parsed, never run)."""
from repro.core import spans
from repro.core.spans import _LOCK, _TOTALS


def add(name, seconds):
    with _LOCK:
        _TOTALS.setdefault(name, [0.0, 0])[0] += seconds


def read_all():
    with spans._LOCK:
        return dict(spans._TOTALS)
