"""Host time packing evaluations (the program's ``repro.pack`` span) per
sweep answered: building the records of fresh designs."""

SPAN = "span_s.repro.pack"


def read(ctx):
    seconds, sweeps = ctx["counters"].get(SPAN), ctx["answered"]
    if seconds is None or not sweeps:
        return None
    return 1e3 * seconds / sweeps
