"""Milliseconds per answered query from the worker resolving its future
to the caller's thread running again: the ``serve_wake`` spans
(obs/trace, recorded by serve/future.py from the future's
``t_resolved`` stamp to the return of ``EvalFuture.result``) summed
over the window and divided by the queries answered in it. Moves
``query_p95_ms``."""

SPAN = "serve_wake"


def read(ctx):
    ns = [e - s for name, s, e, _ in ctx.spans if name == SPAN]
    if not ns or not ctx.steps:
        return None
    return sum(ns) * 1e-6 / ctx.steps
