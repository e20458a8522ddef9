"""Lowered-program introspection: parse compiled (post-SPMD) HLO text.

The plan auditor (analysis/plan_audit.py) works on the program XLA
will actually run — the partitioned module AFTER GSPMD propagation —
because that is where the framework's worst performance bugs live: an
innocuous expr op that GSPMD can only lower by whole-operand
``all-gather`` or by materializing a replicated intermediate (the PR 16
traced-start dynamic-slice class). Nothing in the raw StableHLO shows
those; the compiled text does, instruction by instruction.

This module is pure text analysis: given ``compiled.as_text()`` it
extracts

* every collective instruction (``all-reduce``, ``all-gather``,
  ``all-to-all``, ``collective-permute``, ``reduce-scatter``, plus
  their async ``-start`` halves) with its result/operand shapes,
  participant group size, a modeled per-chip wire-byte figure, and the
  ``__sg_<digest>`` scope mark (obs/profile.py naming sessions) its
  ``metadata.op_name`` carries — the join key back to the expr node;
* the module's ``input_output_alias`` header — which parameters XLA
  ACTUALLY aliased into outputs, so a requested-but-silently-dropped
  donation is machine-detectable.

The byte model is deliberately simple and stable (ring algorithms,
uniform links): per participant of a ``g``-way group moving ``B``
payload bytes, ``all-gather``/``reduce-scatter``/``all-to-all`` cost
``B*(g-1)/g``, ``all-reduce`` costs ``2*B*(g-1)/g`` (reduce-scatter +
all-gather), ``collective-permute`` costs ``B`` (one point-to-point
send per chip). Golden audits gate on these figures, so what matters
is that the model is deterministic, monotone in payload, and platform
independent — not that it matches a particular fabric's microseconds.

No jax import, no compilation, no execution happens here; callers hand
in text. Compiled-object cost/memory queries stay where lint rule 9
sanctions them (obs/explain.py).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

#: HLO shorthand dtype -> bytes per element (fractions for packed
#: 4-bit types round the product, not the element count).
_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "all-to-all",
                     "collective-permute", "reduce-scatter")

# `%name = <result> <opcode>(<operands>), ...` — result is either one
# `f32[8,64]{1,0}` or a tuple `(f32[...], f32[...])`; async halves
# appear as `<opcode>-start` (skip `-done`: same traffic, counted once)
_INSTR_RX = re.compile(
    r"=\s+(?P<result>\([^)]*\)|\S+)\s+"
    r"(?P<kind>all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter)(?:-start)?"
    r"\((?P<operands>.*?)\)(?P<tail>.*)$")

_SHAPE_RX = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# replica_groups={{0,1},{2,3}} (explicit) or [2,4]<=[8] (iota v2:
# ngroups x group_size)
_GROUPS_EXPLICIT_RX = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")
_GROUPS_IOTA_RX = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_PAIRS_RX = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")
_SCOPE_RX = re.compile(r"__sg_([0-9a-f]{4,16})")
_OPNAME_RX = re.compile(r'op_name="([^"]*)"')
_SOURCE_RX = re.compile(r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')
# JAX 0.9 metadata names a frame (stack_frame_id=N) of the module's
# StackFrames -> FileLocations -> FileNames tables instead
_FRAME_ID_RX = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RX = re.compile(r"^(\d+) (.*)$")
_FRAME_TABLES = ("FileNames", "FileLocations", "StackFrames")

# module-header donation record: input_output_alias={ {1}: (0, {},
# may-alias), ... } — the tuple's first element is the PARAMETER number
_ALIAS_BLOCK_RX = re.compile(r"input_output_alias=\{(.*?)\}\s*,?\s*entry",
                             re.DOTALL)
_ALIAS_PARAM_RX = re.compile(r"\(\s*(\d+)\s*,")


def shape_bytes(token: str) -> float:
    """Total bytes of one HLO shape token (``f32[8,64]``); tuples are
    handled by the caller summing elements. Scalars (``f32[]``) count
    one element; unknown dtypes assume 4 bytes."""
    m = _SHAPE_RX.search(token)
    if m is None:
        return 0.0
    dtype, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return float(n) * float(_DTYPE_BYTES.get(dtype, 4))


def _all_shape_bytes(text: str) -> float:
    """Sum the bytes of every shape token in a fragment (tuple results,
    multi-operand calls)."""
    total = 0.0
    for m in _SHAPE_RX.finditer(text):
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += float(n) * float(_DTYPE_BYTES.get(m.group(1), 4))
    return total


def _group_size(tail: str) -> int:
    """Participants per group of this collective, from either
    replica_groups spelling; 1 when unparseable (degenerate group —
    zero modeled traffic, still reported)."""
    m = _GROUPS_IOTA_RX.search(tail)
    if m is not None:
        return max(1, int(m.group(2)))
    m = _GROUPS_EXPLICIT_RX.search(tail)
    if m is not None:
        first = m.group(1).split("}")[0]
        return max(1, len([t for t in first.split(",") if t.strip()]))
    m = _PAIRS_RX.search(tail)
    if m is not None:  # collective-permute: pairs, not groups
        pairs = [p for p in m.group(1).split("}") if p.strip(", {")]
        return max(1, len(pairs))
    return 1


def modeled_bytes(kind: str, payload_bytes: float, group: int) -> float:
    """Per-chip modeled wire bytes (ring model; see module docstring)."""
    if group <= 1:
        return 0.0
    ring = payload_bytes * (group - 1) / group
    if kind == "all-reduce":
        return 2.0 * ring
    if kind == "collective-permute":
        return payload_bytes
    return ring  # all-gather / reduce-scatter / all-to-all


class CollectiveOp:
    """One collective instruction of a compiled module."""

    __slots__ = ("kind", "result_bytes", "operand_bytes", "group_size",
                 "bytes_moved", "scope_digest", "op_name", "source")

    def __init__(self, kind: str, result_bytes: float,
                 operand_bytes: float, group_size: int,
                 scope_digest: Optional[str], op_name: Optional[str],
                 source: Optional[str]):
        self.kind = kind
        self.result_bytes = result_bytes
        self.operand_bytes = operand_bytes
        self.group_size = group_size
        # payload: what each participant contributes — the operand
        # side for reducing/scattering ops, the (gathered) result for
        # all-gather, where the output is what travels
        payload = (result_bytes if kind == "all-gather"
                   else max(operand_bytes, result_bytes)
                   if kind == "all-to-all" else operand_bytes
                   or result_bytes)
        self.bytes_moved = modeled_bytes(kind, payload, group_size)
        self.scope_digest = scope_digest
        self.op_name = op_name
        self.source = source

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "result_bytes": self.result_bytes,
                "operand_bytes": self.operand_bytes,
                "group_size": self.group_size,
                "bytes_moved": self.bytes_moved,
                "scope_digest": self.scope_digest,
                "op_name": self.op_name, "source": self.source}

    def __repr__(self) -> str:
        who = f" @{self.scope_digest}" if self.scope_digest else ""
        return (f"<{self.kind} g={self.group_size} "
                f"~{self.bytes_moved:.0f}B{who}>")


def _frame_sources(hlo_text: str) -> Dict[int, str]:
    """``stack_frame_id`` -> ``"file:line"`` of that (innermost) frame,
    from the module's frame tables."""
    tables: Dict[str, Dict[int, str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s in _FRAME_TABLES:
            cur = tables.setdefault(s, {})
            continue
        m = _TABLE_ROW_RX.match(s) if cur is not None else None
        if m is None:
            cur = None
            continue
        cur[int(m.group(1))] = m.group(2)

    def ref(row: str, field: str) -> int:
        m = re.search(rf"\b{field}=(\d+)", row)
        return int(m.group(1)) if m else -1

    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locs = {k: f"{files.get(ref(v, 'file_name_id'), '?')}:{ref(v, 'line')}"
            for k, v in tables.get("FileLocations", {}).items()}
    return {k: locs[ref(v, "file_location_id")]
            for k, v in tables.get("StackFrames", {}).items()
            if ref(v, "file_location_id") in locs}


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Every collective instruction of a compiled module, in program
    order. ``-done`` halves are skipped (their ``-start`` was counted);
    computation definitions (``to_apply`` bodies) contain no collective
    opcodes, so a line scan is exact."""
    out: List[CollectiveOp] = []
    frames = _frame_sources(hlo_text)
    for line in hlo_text.splitlines():
        m = _INSTR_RX.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        tail = m.group("tail")
        result_bytes = _all_shape_bytes(m.group("result"))
        operand_bytes = _all_shape_bytes(m.group("operands"))
        scope = None
        op_name = None
        source = None
        nm = _OPNAME_RX.search(tail)
        if nm is not None:
            op_name = nm.group(1)
            sm = _SCOPE_RX.search(op_name)
            if sm is not None:
                scope = sm.group(1)
        srcm = _SOURCE_RX.search(tail)
        if srcm is not None:
            source = srcm.group(1)
            if srcm.group(2):
                source += f":{srcm.group(2)}"
        else:
            fm = _FRAME_ID_RX.search(tail)
            if fm is not None:
                source = frames.get(int(fm.group(1)))
        out.append(CollectiveOp(kind, result_bytes, operand_bytes,
                                _group_size(tail), scope, op_name,
                                source))
    return out


def parse_input_output_alias(hlo_text: str) -> Tuple[int, ...]:
    """Parameter numbers the compiled module ACTUALLY aliases into
    outputs (the executable's donation verdict). Empty when the header
    carries no ``input_output_alias`` — every requested donation was
    dropped."""
    head = hlo_text[:4096]
    m = _ALIAS_BLOCK_RX.search(head)
    if m is None:
        return ()
    return tuple(sorted({int(p) for p in
                         _ALIAS_PARAM_RX.findall(m.group(1))}))


def collective_multiset(ops: List[CollectiveOp]) -> Dict[str, int]:
    """``{kind: count}`` over the module — the golden-audit shape
    committed in benchmarks/thresholds.json."""
    out: Dict[str, int] = {}
    for op in ops:
        out[op.kind] = out.get(op.kind, 0) + 1
    return out
