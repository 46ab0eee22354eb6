"""A range read: descend to the low key, then sweep the qualifying pages.

The paper's range sweep (Fig. 10), restated from the plain inputs: the
point lookup's descent to the first qualifying page, then
``n_pages = max(ceil(selectivity x terminal pages), 1)`` pages, reached
by ``n_pages - 1`` random-access hops and each scanned whole.  A hop
stays inside the terminal's region when its pages are linked to their
neighbours (an ordered data page's forward links) or when there is one
page; otherwise it re-descends over the whole structure.
"""
import math

from bench.reference import geometry, get_records

#: terminals whose pages link to their neighbours
LINKED = ("ODP",)


def records(design, wl):
    nodes = geometry(design, wl)
    term = nodes[-1]
    n_pages = max(math.ceil(max(wl.selectivity, 0.0) * term.n_nodes), 1)
    if term.level.name in LINKED or term.n_nodes == 1:
        hop_region = term.region
    else:
        hop_region = sum(node.n_nodes * node.node_bytes for node in nodes)
    recs = get_records(design, wl)
    if n_pages > 1:
        recs.append(("random_memory_access", hop_region, n_pages - 1.0))
    recs.append(("scalar_scan_columnstore_range",
                 max(term.entries, 1.0) * wl.key_bytes, float(n_pages)))
    return recs
