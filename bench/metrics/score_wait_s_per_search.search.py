"""Seconds a search spent waiting for its generations' sweeps to be
scored by the service (the program's ``repro.search.score`` span), per
search completed."""

SPAN = "span_s.repro.search.score"


def read(ctx):
    seconds, searches = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not searches:
        return None
    return seconds / searches
