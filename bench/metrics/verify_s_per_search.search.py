"""Seconds a search spent re-costing new incumbents with the scalar
oracle (the program's ``repro.search.verify`` span), per search
completed."""

SPAN = "span_s.repro.search.verify"


def read(ctx):
    seconds, searches = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not searches:
        return None
    return seconds / searches
