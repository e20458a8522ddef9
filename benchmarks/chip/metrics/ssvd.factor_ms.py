"""Device milliseconds per call in the randomized SVD's program
(``ssvd_roofline``'s) outside its 2q + 2 products with A: the panel
QRs, the small SVD and U = Q U_B, on device 0.

A product with A shows in the capture as one matrix-product op (an
output fusion, ``fusion:kOutput``, which is how XLA:TPU fuses a
product, or a bare ``convolution`` / ``dot``) that reads all of A, so
it cannot take less than A's bytes at the HBM peak; every other op of
the program reads at most an (m, l) panel, l / n of A. So the products
are the program's product ops that last at least half that least
time. Where their count is not 2q + 2 a call, the identification has
failed and the reader gives nothing. Moves ``step_ms``."""

from devtrace import covered
from harness import load_reader

PRODUCT = {"convolution", "dot", "fusion:kOutput"}


def products(ctx, ops):
    """The ops of ``ops`` that are passes over A, or None."""
    w = ctx.costs("ssvd").passes(ctx.config)
    floor_ns = 0.5 * w["bytes"] / ctx.peak["hbm_bytes_per_s"] * 1e9
    got = [o for o in ops
           if o.kind in PRODUCT and o.end - o.start >= floor_ns]
    return got if len(got) == w["passes"] * ctx.steps else None


def read(ctx):
    ops = load_reader("ssvd_roofline").program_ops(ctx)
    if not ops or not ctx.steps:
        return None
    passes = products(ctx, ops)
    if passes is None:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    busy = covered([(o.start, o.end) for o in ops], lo, hi)
    used = covered([(o.start, o.end) for o in passes], lo, hi)
    return (busy - used) * 1e-6 / ctx.steps
