"""Milliseconds per step (``host.gc_ms.step``, moves ``step_ms``) or per
answered query (``host.gc_ms.serve``, moves ``query_p95_ms``) in
Python's garbage collections: the union of the ``gc`` spans (obs/trace,
one per collection, from ``gc.callbacks``) over the window, divided by
the steps or queries completed in it. A traced window with no
collection reads 0; None only where the program recorded no span at
all."""

from devtrace import union


def read(ctx):
    if not ctx.spans or not ctx.steps:
        return None
    iv = [(s, e) for name, s, e, _ in ctx.spans if name == "gc"]
    return sum(e - s for s, e in union(iv)) * 1e-6 / ctx.steps
