"""A put: an insert of a new key (YCSB's insert).

Priced on the structure as populated for the workload's ``n_entries``:
a run's few puts do not grow it.  ``C`` is the terminal page's capacity
(its level's ``n``), ``E`` its entries per page as the lookup uses them,
``P`` a key-value pair's bytes.

1. The search: the point lookup's descent with the Zipf constant taken
   as 0 (YCSB's ``insertorder=hashed`` places new keys uniformly, off
   the hot paths), the leaf search a miss -- an ordered page's binary
   search over its ``E`` keys, an unordered page scanned whole -- and no
   value fetch.
2. The in-page write: an ordered page shifts its upper half, ``E / 2``
   pairs; an unordered page appends one pair.
3. Amortised splits: a page splits once every ``C / 2`` puts (count
   ``s = 2 / C``) and writes the half it moves, ``C / 2`` pairs.  Its
   parent takes one new entry with count ``s``:

   * sorted fences (B+, CSB+, fanout ``F``): ``(F - 1) / 2`` fences and
     pointers shift; the node then splits with count ``s x 2 / F``,
     writing ``F / 2`` fences and pointers, and that count carries on to
     its own parent by the same rule;
   * a page list (LL, SL): two pointers link the new page in;
   * fixed partitioning (Hash, Range, Trie): one pointer.

   The cascade stops at a level that is not sorted.  A terminal with no
   parent writes its moved half and nothing more.

Departures from a structure that really grows: a root split adds no
level; CSB+ splits like B+ and does not copy its node group; a leaf
bloom filter's bit writes are not priced.
"""
from bench.reference import (FENCE_BYTES, PARTITIONING, PTR_BYTES,
                             UNLIMITED, geometry, get_records)


def records(design, wl):
    nodes = geometry(design, wl)
    term = nodes[-1]
    entries = max(term.entries, 1.0)
    pair = wl.key_bytes + wl.value_bytes
    # the lookup's records end with the leaf search and the value fetch
    recs = get_records(design, wl._replace(zipf_alpha=0.0))[:-1]
    if term.level.name == "ODP":
        write = entries / 2 * pair
    else:
        recs[-1] = ("scalar_scan_columnstore_equal",
                    entries * wl.key_bytes, 1.0)
        write = pair
    recs.append(("serial_write", max(float(write), 1.0), 1.0))
    cap = term.level.n or 256
    count = 2.0 / cap
    recs.append(("serial_write", max(cap / 2 * pair, 1.0), count))
    for node in reversed(nodes[:-1]):
        level = node.level
        if level.name in UNLIMITED:
            recs.append(("serial_write", 2.0 * PTR_BYTES, count))
            break
        if PARTITIONING[level.name] != "sorted":
            recs.append(("serial_write", float(PTR_BYTES), count))
            break
        fanout = level.n or 2
        recs.append(("serial_write",
                     max((fanout - 1) / 2 * (FENCE_BYTES + PTR_BYTES), 1.0),
                     count))
        count = count * 2 / fanout
        recs.append(("serial_write",
                     max(fanout / 2 * (FENCE_BYTES + PTR_BYTES), 1.0),
                     count))
    return recs
