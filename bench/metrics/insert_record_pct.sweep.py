"""Inserts' share of the access records the window's packing emitted
(the program's ``records_n.<op>`` counters), in %: how much of packing
and scoring the put's search, writes and split cascade are.  A program
without the counters gives nothing."""

OP = "put"


def read(ctx):
    counts = {k: v for k, v in ctx["counters"].items()
              if k.startswith("records_n.")}
    total = sum(counts.values())
    if not total:
        return None
    return 100.0 * counts.get(f"records_n.{OP}", 0) / total
