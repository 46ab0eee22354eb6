"""Fused scoring calls the service made per search completed: one per
generation that found designs it had not yet costed."""


def read(ctx):
    c, searches = ctx["counters"], ctx["answered"]
    return c["score_calls"] / searches if searches else None
