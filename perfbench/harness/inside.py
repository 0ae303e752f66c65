"""What the program's own spans and scopes say about one traced window.

The program marks its work in two ways, both on the profiler's clock:

- host spans (``jax.profiler.TraceAnnotation``) whose names start with
  ``roboecc/``: ``roboecc/tick/forecast``, ``roboecc/tick/adjust`` and
  ``roboecc/tick/price`` inside the controller's tick, and
  ``roboecc/serve/edge``, ``roboecc/serve/cloud`` and ``roboecc/serve/wait``
  inside the served call;
- ``jax.named_scope`` names around each tier's device work (``SCOPES``),
  which the compiled program keeps as ``op_name`` metadata.  The device
  trace names an operation by its HLO instruction alone, so an
  operation's scope is read from the tier's compiled text
  (``scope_map``).

``harness/trace.py``'s ``Trace`` keeps neither, so this module reads the
host spans from the profile itself and takes the compiled texts as
input.  A program without spans or scopes gives empty readings here,
never an error: the metrics below then return ``None``.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from harness.trace import (HOST_PLANE, HOST_SPANS, MODULES_LINE, Interval,
                           _events, clip, union)

PREFIX = "roboecc/"
SCOPES = ("vision", "trunk", "encode", "decode", "head")
OTHER = "other"
CONTROL = re.compile(r"(while|conditional|call)\b")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


# ------------------------------------------------------------ host spans
def program_spans(pd, lo: float, hi: float) -> Dict[str, List[Interval]]:
    """The program's ``roboecc/`` host spans inside ``[lo, hi]``, by name
    with the prefix taken off (``tick/forecast``, ``serve/edge``, ...)."""
    out: Dict[str, List[Interval]] = defaultdict(list)
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name.startswith(PREFIX) and a >= lo and b <= hi:
                    out[name[len(PREFIX):]].append((a, b))
    return {k: sorted(v) for k, v in out.items()}


# --------------------------------------------------------- device scopes
def _scope_of(op_name: str) -> Optional[str]:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for one compiled program's text.

    An instruction takes the scope its own ``op_name`` names; failing
    that, the one scope of the computations it calls (a fusion whose
    metadata sits on the fused instructions); failing that, the one scope
    of the computation it sits in or, for a loop body, of the instruction
    that runs it.  Instructions left without a scope are not in the map
    (on the chip, 0.01–0.2 % of a tier's operation time)."""
    # computation -> [(instruction, its own scope, computations it calls)]
    comps: Dict[str, List[Tuple[str, Optional[str], List[str]]]] = {}
    cur: Optional[List] = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            rhs = m.group(2)
            op = _OP_NAME.search(rhs)
            callees = _CALLEE.findall(rhs)
            for group in _BRANCHES.findall(rhs):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            cur.append((m.group(1), _scope_of(op.group(1)) if op else None,
                        callees))

    caller: Dict[str, Tuple[str, str]] = {}      # callee -> (comp, instr)
    for comp, instrs in comps.items():
        for name, _, callees in instrs:
            for c in callees:
                caller.setdefault(c, (comp, name))

    memo: Dict[str, frozenset] = {}

    def inner(comp: str) -> frozenset:
        """Scopes named inside ``comp`` and what it calls."""
        if comp not in memo:
            memo[comp] = frozenset()
            found = set()
            for _, own, callees in comps.get(comp, []):
                if own:
                    found.add(own)
                for c in callees:
                    found |= inner(c)
            memo[comp] = frozenset(found)
        return memo[comp]

    first: Dict[str, str] = {}
    for comp, instrs in comps.items():
        for name, own, callees in instrs:
            called = frozenset().union(*(inner(c) for c in callees))
            if own or len(called) == 1:
                first[name] = own or next(iter(called))

    def enclosing(comp: str, depth: int = 0) -> Optional[str]:
        here = inner(comp)
        if len(here) == 1:
            return next(iter(here))
        if comp in caller and depth < 16:
            up_comp, up_instr = caller[comp]
            return first.get(up_instr) or enclosing(up_comp, depth + 1)
        return None

    out = dict(first)
    for comp, instrs in comps.items():
        for name, _, _ in instrs:
            s = out.get(name) or enclosing(comp)
            if s:
                out[name] = s
    return out


def instruction(op_event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names."""
    return op_event_name.split(" = ")[0].lstrip("%")


def op_scopes(ops: Sequence[Tuple[str, float, float]],
              executions: Sequence[Interval], scopes: Dict[str, str]
              ) -> List[List[Tuple[str, float, float]]]:
    """Per execution of one program: its non-control-flow operations as
    ``(scope, start, end)``, clipped to the execution, ``other`` where
    the map has no scope.  ``ops`` sorted by start."""
    starts = [a for _, a, _ in ops]
    out = []
    for lo, hi in executions:
        j = bisect.bisect_left(starts, lo)
        mine = []
        while j < len(ops) and ops[j][1] < hi:
            name, a, b = ops[j]
            j += 1
            inst = instruction(name)
            if CONTROL.match(inst) or b <= lo:
                continue
            mine.append((scopes.get(inst, OTHER), max(a, lo), min(b, hi)))
        out.append(mine)
    return out


def scope_seconds(call_ops: Sequence[Tuple[str, float, float]],
                  scope: str) -> float:
    """Device seconds of one call under ``scope``: the union of its
    operations' intervals."""
    return sum(b - a for a, b in union([(a, b) for s, a, b in call_ops
                                        if s == scope]))


# --------------------------------------------------------------- gaps
def idle_by_span(busy: Sequence[Interval], lo: float, hi: float,
                 spans: Dict[str, Sequence[Interval]],
                 fallback: Optional[Dict[str, Sequence[Interval]]] = None
                 ) -> Dict[str, float]:
    """Idle device seconds in ``[lo, hi]`` by the innermost span of
    ``spans`` that covers each idle instant; where none does, by the
    innermost of ``fallback``; else ``other``.  A gap that crosses span
    boundaries is cut at them, so each span gets only its own part."""
    tables = [spans, fallback or {}]
    cuts = sorted({t for table in tables for ivs in table.values()
                   for a, b in ivs for t in (a, b) if lo < t < hi})

    def owner(t: float) -> str:
        for table in tables:
            best = min(((b - a, name) for name, ivs in table.items()
                        for a, b in ivs if a <= t <= b), default=None)
            if best:
                return best[1]
        return OTHER

    tot: Dict[str, float] = defaultdict(float)
    t = lo
    for a, b in list(busy) + [(hi, hi)]:
        if a > t:
            pts = [t] + cuts[bisect.bisect_right(cuts, t):
                             bisect.bisect_left(cuts, a)] + [a]
            for p, q in zip(pts, pts[1:]):
                tot[owner(0.5 * (p + q))] += q - p
        t = max(t, b)
    return dict(tot)


def idle_inside(busy: Sequence[Interval], within: Sequence[Interval]
                ) -> float:
    """Seconds of ``within`` (a union of host spans) with no device
    operation running."""
    total = 0.0
    for lo, hi in union(within):
        covered = sum(b - a for a, b in clip(busy, lo, hi))
        total += (hi - lo) - covered
    return total


def group_by(outer: Sequence[Interval], inner: Iterable[Interval]
             ) -> List[List[Interval]]:
    """``inner`` intervals grouped by the ``outer`` interval that holds
    their start."""
    out: List[List[Interval]] = [[] for _ in outer]
    for a, b in inner:
        for k, (lo, hi) in enumerate(outer):
            if lo <= a <= hi:
                out[k].append((a, b))
                break
    return out


# ------------------------------------------------------------- window
class Inside:
    """The program's spans and scopes over one traced window: ``tr`` is the
    benchmark's ``Trace``, ``pd`` the same profile as ``ProfileData``,
    ``programs`` maps the tiers to their function names
    (``{"edge": "_edge_fwd", ...}``) and ``scopes`` the tiers to their
    ``scope_map`` (a tier left out has no scopes)."""

    def __init__(self, tr, pd, programs: Dict[str, str],
                 scopes: Dict[str, Dict[str, str]], n_calls: int):
        self.tr = tr
        self.n_calls = n_calls
        self._programs = programs
        self.spans = program_spans(pd, tr.lo, tr.hi)
        ops = sorted(tr.ops(), key=lambda e: e[1])
        self._calls = {p: op_scopes(ops, tr.modules(fn), scopes.get(p, {}))
                       for p, fn in programs.items()}

    # -- device scopes
    def scope_s(self, prog: str, scope: str) -> List[float]:
        """Device seconds under ``scope`` in each traced call of
        ``prog``."""
        return [scope_seconds(c, scope) for c in self._calls[prog]]

    def device_scopes(self) -> Dict[str, float]:
        """Device seconds by ``<program>/<scope>`` over the window, summed
        over non-control-flow operations; ``other`` is the rest."""
        tot: Dict[str, float] = defaultdict(float)
        for prog, calls in self._calls.items():
            for c in calls:
                for s, a, b in c:
                    tot[f"{prog}/{s}"] += b - a
        return dict(sorted(tot.items(), key=lambda kv: -kv[1]))

    def attributed(self, prog: str) -> float:
        """Share of ``prog``'s non-control-flow operation time that has a
        scope (0 where the program ran nothing)."""
        tot = sum(b - a for c in self._calls[prog] for _, a, b in c)
        other = sum(b - a for c in self._calls[prog] for s, a, b in c
                    if s == OTHER)
        return (tot - other) / tot if tot else 0.0

    # -- host spans
    def program_span_s(self, name: str) -> List[float]:
        """Durations of each occurrence of the host span
        ``roboecc/<name>``."""
        return [b - a for a, b in self.spans.get(name, [])]

    def serve_gaps_s(self) -> List[float]:
        """Per served call (one benchmark ``serve`` span each): device idle
        inside the union of that call's ``roboecc/serve/*`` spans."""
        mine = [iv for k, v in self.spans.items() if k.startswith("serve/")
                for iv in v]
        busy = self.tr.busy()
        return [idle_inside(busy, g) for g in
                group_by(self.tr.host.get("serve", []), mine) if g]

    def edge_after_dispatch_s(self) -> List[float]:
        """Per call: the edge program's start on the device less the start
        of the ``roboecc/serve/edge`` span that dispatches it.  Below 0
        the profile's host and device clocks disagree by at least that
        much, and so does every host-span attribution of device time."""
        starts = sorted(a for a, _ in self.tr.modules(self._programs["edge"]))
        out = []
        for lo, _ in self.spans.get("serve/edge", []):
            j = bisect.bisect_left(starts, lo - 0.01)    # 10 ms of slack
            if j < len(starts):
                out.append(starts[j] - lo)
        return out

    def tick_cover(self) -> Optional[float]:
        """The ``roboecc/tick/*`` spans' seconds over the benchmark's
        ``tick`` spans' (``None`` where the benchmark took none)."""
        ticks = sum(b - a for a, b in self.tr.host.get("tick", []))
        mine = sum(sum(self.program_span_s(k)) for k in self.spans
                   if k.startswith("tick/"))
        return mine / ticks if ticks else None

    # -- breakdowns
    def idle_gaps_in_program(self) -> Dict[str, float]:
        """Idle device seconds in the window by the innermost ``roboecc/``
        span over each idle instant, else the benchmark's span, else
        ``other``."""
        named = {PREFIX + k: v for k, v in self.spans.items()}
        bench = {k: self.tr.host.get(k, []) for k in HOST_SPANS}
        gaps = idle_by_span(self.tr.busy(), self.tr.lo, self.tr.hi, named,
                            bench)
        return dict(sorted(gaps.items(), key=lambda kv: -kv[1]))

    def programs_per_call(self) -> Dict[str, float]:
        """``XLA Modules`` executions in the window per traced call, by
        program."""
        n = Counter(re.sub(r"\(\d+\)$", "", name)
                    for name, a, b in self.tr.lines.get(MODULES_LINE, [])
                    if a >= self.tr.lo and b <= self.tr.hi)
        return {k: v / self.n_calls for k, v in n.most_common()}


# ------------------------------------------------------------ metrics
def _mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def head_ms(w: Inside) -> Optional[float]:
    """Action head (final norm + detok unembed, or the DiT chain): mean
    device time per call under ``head`` in the cloud program."""
    t = w.scope_s("cloud", "head")
    return _ms(_mean(t)) if any(t) else None


def codec_ms(w: Inside) -> Optional[float]:
    """The cut's codec: mean device time per call under ``encode`` in the
    edge program plus under ``decode`` in the cloud program."""
    enc, dec = w.scope_s("edge", "encode"), w.scope_s("cloud", "decode")
    if not (any(enc) and any(dec)):
        return None
    return _ms(_mean(enc) + _mean(dec))


def _median_span_ms(w: Inside, name: str) -> Optional[float]:
    t = w.program_span_s(name)
    return _ms(statistics.median(t)) if t else None


def forecast_ms(w: Inside) -> Optional[float]:
    """Median host time per tick of ``roboecc/tick/forecast``."""
    return _median_span_ms(w, "tick/forecast")


def adjust_ms(w: Inside) -> Optional[float]:
    """Median host time per tick of ``roboecc/tick/adjust``."""
    return _median_span_ms(w, "tick/adjust")


def price_ms(w: Inside) -> Optional[float]:
    """Median host time per tick of ``roboecc/tick/price``."""
    return _median_span_ms(w, "tick/price")


def serve_gap_ms(w: Inside) -> Optional[float]:
    """Median per call of device idle inside the call's
    ``roboecc/serve/*`` spans."""
    g = w.serve_gaps_s()
    return _ms(statistics.median(g)) if g else None


METRICS = {"head_ms": head_ms, "codec_ms": codec_ms,
           "forecast_ms": forecast_ms, "adjust_ms": adjust_ms,
           "price_ms": price_ms, "serve_gap_ms": serve_gap_ms}
