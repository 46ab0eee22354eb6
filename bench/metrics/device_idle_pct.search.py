"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (profiler trace, see bench/trace.py)."""


def read(ctx):
    trace = ctx["trace"]
    return trace["idle_pct"] if trace else None
