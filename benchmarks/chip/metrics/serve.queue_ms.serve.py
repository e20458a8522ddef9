"""Milliseconds per answered query from its submit to a serve worker
taking it off the queue: the ``serve_queue`` spans (obs/trace, built by
serve/engine.py from the request's ``t_submit`` and ``t_taken``
stamps) summed over the window and divided by the queries answered in
it. Moves ``query_p95_ms``."""

SPAN = "serve_queue"


def read(ctx):
    ns = [e - s for name, s, e, _ in ctx.spans if name == SPAN]
    if not ns or not ctx.steps:
        return None
    return sum(ns) * 1e-6 / ctx.steps
