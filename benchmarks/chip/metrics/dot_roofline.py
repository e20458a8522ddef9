"""The product's share of its roofline on one chip: the least time of
one chip's share of the product (costs/dot.py: 2 n^3 / chips operations
at the bf16 peak, or its bytes at the HBM peak, whichever is longer)
over the device time per step of device 0's matrix-product ops (HLO
``convolution`` / ``dot`` ops and output fusions, ``fusion:kOutput``,
which is how XLA:TPU fuses a product). Moves ``step_ms``."""

PRODUCT = {"convolution", "dot", "fusion:kOutput"}


def read(ctx):
    ops = [o for o in ctx.trace.ops.get(0, []) if o.kind in PRODUCT]
    if not ops or not ctx.steps:
        return None
    per_step = sum(o.end - o.start for o in ops) * 1e-9 / ctx.steps
    w = ctx.costs("dot").product_per_chip(ctx.config)
    least = max(w["flops"] / ctx.peak["bf16_flops_per_s"],
                w["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / per_step
