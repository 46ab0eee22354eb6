"""Share of the window's ``packed_spec`` segment-cache lookups that hit:
how much host packing the sweep traffic could reuse."""


def read(ctx):
    c = ctx["counters"]
    lookups = c["packed_spec_hits"] + c["packed_spec_misses"]
    return 100.0 * c["packed_spec_hits"] / lookups if lookups else None
