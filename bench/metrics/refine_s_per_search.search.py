"""Seconds a search spent in gradient refinement of its elite
(``relax.refine``, the program's ``repro.search.refine`` span), per
search completed."""

SPAN = "span_s.repro.search.refine"


def read(ctx):
    seconds, searches = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not searches:
        return None
    return seconds / searches
