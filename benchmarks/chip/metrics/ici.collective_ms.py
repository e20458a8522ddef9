"""Device milliseconds per step during which a collective (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute, with their
asynchronous start/done halves) runs or is in flight on device 0: the
union of those ops' intervals on the ``XLA Ops`` and ``Async XLA Ops``
lines of the traced window. Moves ``step_ms``."""

import re

from devtrace import union

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)")


def read(ctx):
    ops = [o for o in (ctx.trace.ops.get(0, [])
                       + ctx.trace.async_ops.get(0, []))
           if COLLECTIVE.match(o.kind)]
    if not ops or not ctx.steps:
        return None
    return _length([(o.start, o.end) for o in ops]) * 1e-6 / ctx.steps


def _length(intervals):
    return sum(e - s for s, e in union(intervals))
