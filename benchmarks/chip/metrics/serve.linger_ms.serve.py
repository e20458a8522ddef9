"""Milliseconds per answered query from a worker taking the request to
the start of its dispatch, the coalescing linger included: the
``serve_linger`` spans (obs/trace, built by serve/engine.py from the
request's ``t_taken`` and ``t_dispatch`` stamps) summed over the window
and divided by the queries answered in it. Moves ``query_p95_ms``."""

SPAN = "serve_linger"


def read(ctx):
    ns = [e - s for name, s, e, _ in ctx.spans if name == SPAN]
    if not ns or not ctx.steps:
        return None
    return sum(ns) * 1e-6 / ctx.steps
