"""The PageRank iteration's share of its roofline: the least time one
power iteration's bytes take at the HBM peak (costs/pagerank.py) over
the device time per iteration of the fused power-iteration program
(``examples/pagerank._pagerank_loop``: the gather, the windowed
segment-sum kernel and the teleport) on device 0. Moves ``step_ms``."""

PROGRAM = "jit__pagerank_loop"


def read(ctx):
    busy = ctx.trace.module_seconds(0, PROGRAM)
    if not busy or not ctx.steps:
        return None
    w = ctx.costs("pagerank").power_iteration(ctx.config)
    least = max(w["flops"] / ctx.peak["bf16_flops_per_s"],
                w["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (busy / ctx.steps)
