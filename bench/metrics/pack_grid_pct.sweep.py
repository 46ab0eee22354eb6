"""Share of the window's packed (design, point) cells that the sweep grid
path packed (the program's ``pack_grid_cells`` counter), against those
that went through the ``packed_spec`` segment cache, in %.  A program
without the counter gives nothing."""


def read(ctx):
    c = ctx["counters"]
    grid = c.get("pack_grid_cells")
    if grid is None:
        return None
    total = grid + c["packed_spec_hits"] + c["packed_spec_misses"]
    return 100.0 * grid / total if total else None
