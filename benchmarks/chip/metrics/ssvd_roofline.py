"""The randomized SVD's share of its roofline: the least time a call's
passes over A can take on this chip (costs/ssvd.py: 2q + 2 passes, each
at the longer of its operations at the bf16 peak and its bytes at the
HBM peak) over device 0's busy time per call in the call's program
(the union of the program's op intervals). The program is the plan the
expr layer compiles for the call (``jit_traced``); it holds the
passes, the QRs, the small SVD and U = Q U_B. Moves ``step_ms``."""

from devtrace import covered

PROGRAM = "jit_traced"


def program_ops(ctx):
    return [o for o in ctx.trace.ops.get(0, []) if o.module == PROGRAM]


def least_s(ctx):
    """The least seconds of one call's passes over A."""
    w = ctx.costs("ssvd").passes(ctx.config)
    return w["passes"] * max(w["flops"] / ctx.peak["bf16_flops_per_s"],
                             w["bytes"] / ctx.peak["hbm_bytes_per_s"])


def read(ctx):
    ops = program_ops(ctx)
    if not ops or not ctx.steps:
        return None
    busy = covered([(o.start, o.end) for o in ops], ctx.trace.lo,
                   ctx.trace.hi) * 1e-9
    return 100.0 * least_s(ctx) / (busy / ctx.steps)
