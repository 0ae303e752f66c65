"""Post-SPMD HLO analysis: collective bytes + schedule for §Roofline.

Parses ``compiled.as_text()`` (the per-device program).  For every
``all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute``
op we take the result shapes (tuple-aware), the replica-group size N, and a
ring wire factor:

    all-reduce:          2 (N-1)/N x bytes   (reduce-scatter + all-gather)
    all-gather:            (N-1)/N x bytes   (bytes = full output)
    reduce-scatter:        (N-1)/N x bytes   (bytes = full input ~ N x out)
    all-to-all:            (N-1)/N x bytes
    collective-permute:              1 x bytes

Collectives inside ``while`` bodies (e.g. a microbatch scan) are multiplied
by the loop trip count when it is statically parseable; the dry-run unrolls
layers so in practice whiles only appear when explicitly requested.

``loop_weight_copies`` / ``loop_stack_readers`` read how a layer loop's
body touches its stacked weights: in place by the dots, or through a
per-step copy of the layer.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.M)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COMP_RE = re.compile(r"^(?:%?([\w.\-]+))\s*(?:\([^)]*\))?\s*->.*\{\s*$", re.M)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes: int              # result bytes (per device)
    group_size: int
    wire_bytes: float       # ring-model bytes on the wire per device
    computation: str
    count: int = 1          # trip-count multiplier
    wire_bytes_bf16: float = 0.0   # bf16-equivalent (TPU target) wire bytes


def _wire_factor(kind: str, n: int, op_bytes: int) -> float:
    if kind == "collective-permute":
        return float(op_bytes)   # pairwise; no replica_groups attribute
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n * op_bytes
    if kind in ("all-gather", "all-to-all"):
        return (n - 1) / n * op_bytes
    if kind == "reduce-scatter":
        return (n - 1) * op_bytes        # result is the scattered shard
    if kind == "collective-permute":
        return float(op_bytes)
    return float(op_bytes)


def _shape_bytes_bf16_equiv(type_str: str) -> int:
    """Bytes if every f32 tensor were bf16.

    The CPU backend has no native bf16 dot, so XLA float-normalises model
    matmuls (and the all-reduces fed by them) to f32; on the TPU target
    these run in bf16.  Large f32 collectives in a bf16 model are therefore
    counted at half size for the TPU roofline (DESIGN.md §6).
    """
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = _DTYPE_BYTES[dt]
        if dt == "f32" and n * b >= 1 << 20:
            b = 2
        total += n * b
    return total


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    # map line offset -> computation name
    comp_spans: List[Tuple[int, str]] = []
    for m in re.finditer(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->[^{]*\{",
                         hlo_text, re.M):
        comp_spans.append((m.start(), m.group(1)))
    comp_spans.sort()

    def comp_at(pos: int) -> str:
        name = "?"
        for start, n in comp_spans:
            if start <= pos:
                name = n
            else:
                break
        return name

    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind, started = m.group(1), m.group(2), m.group(3)
        if started and kind != "collective-permute":
            pass  # -start ops carry the real shape; -done is aliasing
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end]
        nbytes = _shape_bytes(type_str)
        gm = _GROUPS_RE.search(line)
        if gm:
            group = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            group = int(gi.group(2)) if gi else 1
        nbytes16 = _shape_bytes_bf16_equiv(type_str)
        ops.append(CollectiveOp(
            kind=kind, bytes=nbytes, group_size=group,
            wire_bytes=_wire_factor(kind, group, nbytes),
            computation=comp_at(m.start()),
            wire_bytes_bf16=_wire_factor(kind, group, nbytes16)))
    # drop the "-done" halves of async pairs (zero-arg matches won't occur;
    # -done ops don't match _COLL_RE since they are "<kind>-done")
    return _apply_while_counts(hlo_text, ops)


def _apply_while_counts(hlo_text: str, ops: List[CollectiveOp]
                        ) -> List[CollectiveOp]:
    """Multiply collectives inside while bodies by parsed trip counts."""
    bodies: Dict[str, int] = {}
    for m in re.finditer(
            r"while\([^)]*\), condition=%?([\w.\-]+), body=%?([\w.\-]+)",
            hlo_text):
        cond, body = m.group(1), m.group(2)
        trip = _parse_trip_count(hlo_text, cond)
        if trip:
            bodies[body] = trip
    if not bodies:
        return ops
    out = []
    for op in ops:
        count = bodies.get(op.computation, 1)
        if count != 1:
            op = dataclasses.replace(op, count=count,
                                     wire_bytes=op.wire_bytes * count,
                                     wire_bytes_bf16=op.wire_bytes_bf16 * count)
        out.append(op)
    return out


def _parse_trip_count(hlo_text: str, cond_name: str) -> Optional[int]:
    m = re.search(re.escape(cond_name) + r"[\s\S]{0,2000}?"
                  r"compare\([^)]*\), direction=LT", hlo_text)
    if not m:
        return None
    window = hlo_text[m.start():m.end() + 200]
    cm = re.findall(r"constant\((\d+)\)", window)
    if cm:
        return int(cm[-1])
    return None


def summarize(ops: List[CollectiveOp]) -> Dict:
    by_kind: Dict[str, Dict] = {}
    for op in ops:
        d = by_kind.setdefault(op.kind, {"count": 0, "bytes": 0.0,
                                         "wire_bytes": 0.0,
                                         "wire_bytes_bf16": 0.0})
        d["count"] += op.count
        d["bytes"] += op.bytes * op.count
        d["wire_bytes"] += op.wire_bytes
        d["wire_bytes_bf16"] += op.wire_bytes_bf16
    total_wire = sum(d["wire_bytes"] for d in by_kind.values())
    total_16 = sum(d["wire_bytes_bf16"] for d in by_kind.values())
    return {"by_kind": by_kind, "total_wire_bytes_per_device": total_wire,
            "total_wire_bytes_bf16_per_device": total_16, "n_ops": len(ops)}


# ------------------------------------------------- stacked weights in loops
# A layer loop indexes each step's weights out of stacks of shape
# (L, ...).  A dot can read its layer in place, the index fused into it;
# anything else that touches a stack writes a layer-sized buffer (a slice,
# a copy, a relayout) every step.  These read the compiled text.
_PLUMBING = frozenset(("bitcast", "get-tuple-element", "tuple", "parameter",
                       "constant"))


def _group_end(s: str, i: int) -> int:
    """Index just past the parenthesised group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return j + 1
    return len(s)


def _instructions(lines: List[str]):
    """(name, result type, opcode, operand names, attributes) per line."""
    for line in lines:
        m = re.match(r"\s+(?:ROOT\s+)?%([\w.\-]+) = ", line)
        if not m:
            continue
        rest = line[m.end():]
        end = _group_end(rest, 0) if rest.startswith("(") else rest.find(" ")
        result, rest = rest[:end], rest[end:].lstrip()
        op = re.match(r"([\w\-]+)\(", rest)
        if not op:
            continue
        close = _group_end(rest, op.end() - 1)
        yield (m.group(1), result, op.group(1),
               re.findall(r"%([\w.\-]+)", rest[op.end():close]), rest[close:])


def computations(hlo_text: str) -> Dict[str, list]:
    """Each computation's instructions, by name: its own, not those of
    the computations it fuses or calls."""
    comps: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            current = m.group(1)
            comps[current] = []
        elif current is not None and line.startswith("}"):
            current = None
        elif current is not None:
            comps[current].append(line)
    return {n: list(_instructions(ls)) for n, ls in comps.items()}


def while_bodies(hlo_text: str) -> Dict[str, list]:
    """Each while loop's body: the instructions of its own computation,
    not of the computations it fuses or calls."""
    names = set(re.findall(r"while\([^)]*\), condition=%?[\w.\-]+, "
                           r"body=%?([\w.\-]+)", hlo_text))
    return {n: body for n, body in computations(hlo_text).items()
            if n in names}


def _elements(result: str) -> Optional[int]:
    """Element count of the first array in a result type."""
    m = _SHAPE_RE.search(result)
    if not m:
        return None
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n


def _is_dot(op: str, attrs: str) -> bool:
    return op in ("dot", "convolution") or (
        op == "fusion" and "kind=kOutput" in attrs)


def loop_weight_copies(hlo_text: str, stacks) -> List[str]:
    """Instructions of a while body that copy a stacked weight.

    ``stacks``: the shapes ``(L, ...)`` of the stacked weights.  Follows
    each loop-carried stack through the body and returns every
    instruction, other than a dot and plumbing (bitcasts, tuples), that
    writes a buffer of one layer's or of the whole stack's size from it:
    a per-step slice, copy or relayout.  A loop whose dots read their
    layer in place returns none."""
    layer = {math.prod(s[1:]) for s in stacks}
    whole = {math.prod(s) for s in stacks}
    copies = []
    for body in while_bodies(hlo_text).values():
        derived = set()
        for name, result, op, operands, attrs in body:
            n = _elements(result)
            if op == "get-tuple-element" and n in whole:
                derived.add(name)
            elif derived.intersection(operands) and n in layer | whole \
                    and not _is_dot(op, attrs):
                derived.add(name)
                if op not in _PLUMBING:
                    copies.append(name)
    return copies


def loop_stack_readers(hlo_text: str, stacks) -> List[Tuple[str, str]]:
    """``(instruction, kind)`` for each instruction of a while body that
    takes a loop-carried stacked weight as an operand: a fusion's kind
    (``kOutput``: a dot with the layer index fused in; ``kLoop``: a slice
    written out) or else the opcode.  The loop's own carry is left out."""
    whole = {math.prod(s) for s in stacks}
    out = []
    for body in while_bodies(hlo_text).values():
        carried = {name for name, result, op, _, _ in body
                   if op == "get-tuple-element" and _elements(result) in whole}
        for name, _, op, operands, attrs in body:
            if op != "tuple" and carried.intersection(operands):
                kind = re.search(r"kind=(\w+)", attrs)
                out.append((name, kind.group(1) if kind else op))
    return out
