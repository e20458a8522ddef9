"""The logistic-regression step's share of its roofline: the least time
one full-batch step's work can take on this chip (costs/logreg.py: one
pass over X and y; operations at the bf16 peak, bytes at the HBM peak,
whichever is longer) over device 0's busy time per iteration in the
plans the expr layer compiles (``jit_traced``): a job's ``st.loop``
program holds all of its iterations. Moves ``step_ms``."""

from devtrace import covered

PROGRAM = "jit_traced"


def read(ctx):
    ops = [o for o in ctx.trace.ops.get(0, []) if o.module == PROGRAM]
    if not ops or not ctx.steps:
        return None
    busy = covered([(o.start, o.end) for o in ops], ctx.trace.lo,
                   ctx.trace.hi) * 1e-9
    w = ctx.costs("logreg").iteration(ctx.config)
    least = max(w["flops"] / ctx.peak["bf16_flops_per_s"],
                w["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (busy / ctx.steps)
