"""Loop iterations per plan evaluated over the window's jobs: the
metrics registry's ``loop_iterations`` (``expr/loop.py``, the static
count of each ``st.loop`` evaluated) over its ``evaluations``, as the
cell's system path adds them up around each job (its ``COUNTS``). A
job of n iterations in one ``st.loop`` program reads n; a job that
dispatches a plan an iteration reads 1 or less. Nothing to read where
the path keeps no counts or the program counts no loop iterations.
Moves ``step_ms``."""

import importlib


def read(ctx):
    sut = importlib.import_module(f"sut.{ctx.traffic['sut']}")
    counts = getattr(sut, "COUNTS", None) or {}
    if not counts.get("loop_iterations") or not counts.get("evaluations"):
        return None
    return counts["loop_iterations"] / counts["evaluations"]
