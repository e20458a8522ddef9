"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips (1 - busy / window; busy is the union of the
device's op intervals). The reader of ``device.idle_share.step`` (moves
``step_ms``) and ``device.idle_share.serve`` (moves ``queries_per_s``)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.trace.window_s)
