"""Host time padding, placing and issuing fused scoring calls (the program's
``repro.fused.dispatch`` span) per sweep answered."""

SPAN = "span_s.repro.fused.dispatch"


def read(ctx):
    seconds, sweeps = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not sweeps:
        return None
    return 1e3 * seconds / sweeps
