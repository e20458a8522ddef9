"""Host milliseconds per query in the program's plan spans (``build``,
``sign``, ``optimize``, ``compile``, ``dispatch``, ``fetch``; obs/trace,
recorded in the traced run), each thread's spans merged so nested ones
count once. ``dispatch`` is the enqueue, not device time. Moves
``query_p95_ms``."""

from devtrace import union

PLAN_SPANS = {"build", "sign", "optimize", "compile", "dispatch", "fetch"}


def read(ctx):
    per_thread = {}
    for name, s, e, tid in ctx.spans:
        if name in PLAN_SPANS:
            per_thread.setdefault(tid, []).append((s, e))
    if not per_thread or not ctx.steps:
        return None
    ns = sum(_length(iv) for iv in per_thread.values())
    return ns * 1e-6 / ctx.steps


def _length(intervals):
    return sum(e - s for s, e in union(intervals))
