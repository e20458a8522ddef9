"""The k-means kernel's share of its roofline: the least time one Lloyd
iteration's work can take on this chip (costs/kmeans.py: operations at
the bf16 peak, bytes at the HBM peak, whichever is longer) over the
kernel's device time per iteration on device 0. The kernel is the
Mosaic custom call inside ``kernels/kmeans.run``'s program (``jit_run``);
its ``pallas_call`` has no stable name yet. Moves ``step_ms``."""

PROGRAM = "jit_run"


def read(ctx):
    ops = [o for o in ctx.trace.ops.get(0, [])
           if o.module == PROGRAM and o.kind == "tpu_custom_call"]
    if not ops or not ctx.steps:
        return None
    per_iter = sum(o.end - o.start for o in ops) * 1e-9 / ctx.steps
    w = ctx.costs("kmeans").lloyd_iteration(ctx.config)
    least = max(w["flops"] / ctx.peak["bf16_flops_per_s"],
                w["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / per_iter
