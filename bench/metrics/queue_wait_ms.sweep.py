"""Mean time a request waited between its submission and the serving
worker taking it into a batch (``ServiceStats.queue_wait_s`` over
``dequeued``): in the sweep cell, mostly the other client's sweep being
served first."""


def read(ctx):
    c = ctx["counters"]
    waited, dequeued = c.get("queue_wait_s"), c.get("dequeued")
    if waited is None or not dequeued:
        return None
    return 1e3 * waited / dequeued
