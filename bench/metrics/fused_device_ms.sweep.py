"""Device time of the fused scoring programs per sweep answered, summed
over the cell's chips (profiler trace, by XLA module name)."""


def read(ctx):
    trace, sweeps = ctx["trace"], ctx["answered"]
    if not trace or not trace["fused_calls"] or not sweeps:
        return None
    return 1e3 * trace["fused_s"] / sweeps
