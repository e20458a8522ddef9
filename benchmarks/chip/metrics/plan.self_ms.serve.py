"""Plan host milliseconds per answered query that are not a wait for
the device: per thread, the union of the spans ``plan.host_ms.serve``
reads, less the part of it that ``fetch_wait`` spans cover (the wait
for the computation inside ``fetch``, array/distarray.fetch_to_host).
Moves ``query_p95_ms``."""

from devtrace import union
from harness import load_reader

WAIT = "fetch_wait"


def read(ctx):
    plan_spans = load_reader("plan.host_ms.serve").PLAN_SPANS
    plan, wait = {}, {}
    for name, s, e, tid in ctx.spans:
        if name in plan_spans:
            plan.setdefault(tid, []).append((s, e))
        elif name == WAIT:
            wait.setdefault(tid, []).append((s, e))
    if not plan or not ctx.steps:
        return None
    # |plan - wait| = |plan u wait| - |wait|
    ns = sum(_length(iv + wait.get(tid, [])) - _length(wait.get(tid, []))
             for tid, iv in plan.items())
    return ns * 1e-6 / ctx.steps


def _length(intervals):
    return sum(e - s for s, e in union(intervals))
