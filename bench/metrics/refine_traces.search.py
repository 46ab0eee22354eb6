"""Traces of the compiled refinement loop (``relax.refine``) inside the
window: the service's ``refine_traces`` counter, read before and after.
The search's rehearsal compiles every template it refines, so this
reads 0; a program without the counter reports nothing."""

COUNTER = "refine_traces"


def read(ctx):
    return ctx["counters"].get(COUNTER)
