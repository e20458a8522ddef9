"""Host milliseconds per answered query in host->device copies: the
``upload`` spans (obs/trace, around ``jax.device_put`` in
array/distarray.from_numpy) summed over the window and divided by the
queries answered in it. Moves ``query_p95_ms``."""

SPAN = "upload"


def read(ctx):
    ns = [e - s for name, s, e, _ in ctx.spans if name == SPAN]
    if not ns or not ctx.steps:
        return None
    return sum(ns) * 1e-6 / ctx.steps
