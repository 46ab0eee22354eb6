"""Host time waiting for and copying back fused calls' outputs (the
program's ``repro.fused.fetch`` span) per sweep answered: device time
plus the transfer, as the host sees it."""

SPAN = "span_s.repro.fused.fetch"


def read(ctx):
    seconds, sweeps = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not sweeps:
        return None
    return 1e3 * seconds / sweeps
