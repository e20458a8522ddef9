"""Reduce a JAX profiler capture to device busy time, per-op device
time and idle gaps labelled by what the host was doing.

The capture is the profiler's own ``*.xplane.pb``, read with
``jax.profiler.ProfileData``. As a v5e writes it (JAX 0.9): device
planes are named ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per program run (``jit_run(<fingerprint>)``), their
``XLA Ops`` line one event per HLO op run, named by the op's HLO text
(``%body.6 = (...) custom-call(...), custom_call_target=...``), and
their ``Async XLA Ops`` line the in-flight spans of asynchronous ops.
A ``while`` op's event spans the ops of its body. The host plane
``/host:CPU`` holds the ``TraceAnnotation`` ranges the harness writes
(``bench.window`` around the measured window). All times share the
capture's clock, in nanoseconds.

Busy time is the union of the ``XLA Ops`` intervals (the TensorCore at
work); asynchronous copies and collectives in flight count only where
an op runs beside them.

The interval arithmetic (:func:`union`, :func:`covered`, :func:`gaps`,
:func:`label_gaps`) takes plain ``(start, end)`` pairs so the tests
can check it against hand counts.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
# "%name = <shape> kind(...": the op kind is the first lower-case word
# after a space and before "(" (shapes hold "T(8,128)", never " word(")
_HLO = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(")
_FUSION_KIND = re.compile(r", kind=(k[A-Za-z]+)")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
CONTAINERS = {"while", "conditional", "call"}

Interval = Tuple[float, float]


@dataclass(frozen=True)
class Op:
    """One op run: its HLO name (``body.6``), its kind (``copy-done``,
    ``all-gather-start``; a fusion is ``fusion:kLoop`` and the like; a
    Mosaic kernel is ``tpu_custom_call``), the program it ran in
    (``jit_run``), start and end in ns."""
    name: str
    kind: str
    module: str
    start: float
    end: float


def parse_op(text: str) -> Tuple[str, str]:
    """(name, kind) of an ``XLA Ops`` event's HLO text."""
    m = _HLO.match(text)
    if not m:
        return text, text
    kind = m.group(2)
    if KERNEL_TARGET in text:
        kind = "tpu_custom_call"
    elif kind == "fusion":
        f = _FUSION_KIND.search(text)
        kind = f"fusion:{f.group(1)}" if f else kind
    return m.group(1), kind


def module_name(text: str) -> str:
    """``jit_run(6287802397132685784)`` -> ``jit_run``."""
    return text.split("(", 1)[0]


def attach(ops: Sequence[Tuple[str, float, float]],
           modules: Sequence[Tuple[str, float, float]]) -> List[Op]:
    """Parse each op and name its module: the module run whose interval
    holds the op's start. Both lists are sorted by start."""
    out, j = [], 0
    for text, s, e in ops:
        while j + 1 < len(modules) and modules[j + 1][1] <= s:
            j += 1
        mod = (modules[j][0] if modules and modules[j][1] <= s
               <= modules[j][2] else "")
        name, kind = parse_op(text)
        out.append(Op(name, kind, mod, s, e))
    return out


@dataclass
class Reduction:
    """One traced window: ``ops[d]`` / ``async_ops[d]`` are device
    ``d``'s ops that start inside it, ``modules[d]`` its program runs
    (name, start, end), ``spans`` the host ranges (name, start, end)."""
    lo: float
    hi: float
    ops: Dict[int, List[Op]] = field(default_factory=dict)
    async_ops: Dict[int, List[Op]] = field(default_factory=dict)
    modules: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, device: int) -> float:
        return covered([(o.start, o.end) for o in self.ops.get(device, [])],
                       self.lo, self.hi) * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        return sum(self.busy_s(d) for d in self.ops) / max(len(self.ops), 1)

    def op_seconds(self, device: int) -> Dict[str, float]:
        """Device seconds per ``module:kind:name`` on one device; a
        ``while`` or other container is left out and its body's ops
        counted instead."""
        out: Dict[str, float] = defaultdict(float)
        for o in self.ops.get(device, []):
            if o.kind not in CONTAINERS:
                out[f"{o.module}:{o.kind}:{o.name}"] += (
                    o.end - o.start) * 1e-9
        return dict(out)

    def module_seconds(self, device: int, name: str) -> float:
        """Device seconds of the runs of the program ``name``."""
        return sum(e - s for n, s, e in self.modules.get(device, [])
                   if n == name) * 1e-9

    def idle_gaps(self, device: int) -> List[Interval]:
        return gaps([(o.start, o.end) for o in self.ops.get(device, [])],
                    self.lo, self.hi)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in union(intervals))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals):
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gaps(gap_list: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Total length of the gaps per label: the innermost (shortest)
    span that covers a gap's midpoint names it, ``none`` if no span
    does."""
    out: Dict[str, float] = defaultdict(float)
    ordered = sorted(spans, key=lambda sp: sp[2] - sp[1])
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        label = next((name for name, a, b in ordered if a <= mid <= b),
                     "none")
        out[label] += e - s
    return dict(out)


def find_capture(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(path: str, window: str = WINDOW) -> Reduction:
    """Read a capture and cut it to the host range named ``window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    lines: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            got = lines.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    got[line.name] = sorted(
                        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events), key=lambda x: x[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith("bench."))
    wins = [sp for sp in spans if sp[0] == window]
    if not wins:
        raise ValueError(f"no {window!r} range in {path}")
    _, lo, hi = max(wins, key=lambda sp: sp[2] - sp[1])
    red = Reduction(lo, hi)
    for d, got in sorted(lines.items()):
        mods = [(module_name(n), a, b)
                for n, a, b in got.get(MODULES_LINE, [])]

        def inside(name):
            return [x for x in got.get(name, []) if lo <= x[1] < hi]

        red.ops[d] = attach(inside(OPS_LINE), mods)
        red.async_ops[d] = attach(inside(ASYNC_LINE), mods)
        red.modules[d] = [x for x in mods if lo <= x[1] < hi]
    red.spans = [sp for sp in spans if sp[2] >= lo and sp[1] <= hi]
    return red


def breakdown(red: Reduction, top: int = 10,
              extra_spans: Optional[Sequence[Tuple[str, float, float]]]
              = None) -> dict:
    """The contract's ``breakdown``: the ops that took most device time
    and the idle time by what the host was doing, averaged over the
    traced devices, in seconds."""
    n = max(len(red.ops), 1)
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    spans = list(red.spans) + list(extra_spans or [])
    spans = [sp for sp in spans if sp[0] != WINDOW]
    for d in red.ops:
        for k, v in red.op_seconds(d).items():
            ops[k] += v / n
        for k, v in label_gaps(red.idle_gaps(d), spans).items():
            idle[k] += v * 1e-9 / n

    def rank(m):
        return sorted(([k, v] for k, v in m.items()),
                      key=lambda kv: -kv[1])[:top]

    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
