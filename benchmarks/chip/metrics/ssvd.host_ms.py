"""Host milliseconds per call inside the ``ssvd`` span (obs/trace,
``examples/ssvd.ssvd``) that the ``fetch_wait`` spans do not cover:
drawing and uploading the sketch, building, signing and dispatching the
plan, and the copy of the results to the host once the device is
done. The benchmark calls from one thread. Moves ``step_ms``."""

from devtrace import union

CALL = "ssvd"
WAIT = "fetch_wait"


def read(ctx):
    calls = [(s, e) for name, s, e, _ in ctx.spans if name == CALL]
    wait = [(s, e) for name, s, e, _ in ctx.spans if name == WAIT]
    if not calls or not ctx.steps:
        return None
    # |calls - wait| = |calls u wait| - |wait|
    ns = _length(calls + wait) - _length(wait)
    return ns * 1e-6 / ctx.steps


def _length(intervals):
    return sum(e - s for s, e in union(intervals))
