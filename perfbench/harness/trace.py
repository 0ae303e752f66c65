"""Reduction of a profiler trace (an XSpace) to the facts the per-layer
metrics read.

The device plane of chip ``k`` is ``/device:TPU:k``.  On it, the line
``XLA Modules`` holds one event per program execution, named by the
program JAX compiled and a fingerprint, e.g.
``jit__cloud_fwd(9130525350998532809)``, the same whether the program
was compiled in the process or loaded from the persistent cache; the
line ``XLA Ops`` holds one event per device operation (a ``while`` op's
event spans the operations of its body).  Device and host events share
one clock.  The host plane ``/host:CPU`` holds the benchmark's
own ``TraceAnnotation`` spans: ``window`` around the traced steps and
``tick``, ``upload``, ``serve`` and ``fetch`` inside each step.
"""
from __future__ import annotations

import bisect
import gzip
import re
from collections import Counter, defaultdict
from typing import Dict, List, Sequence, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
HOST_SPANS = ("tick", "upload", "serve", "fetch")

Interval = Tuple[float, float]          # (start_s, end_s)


class TraceError(RuntimeError):
    """The trace lacks what a declared metric needs."""


def load(path: str):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def module_pattern(function: str) -> re.Pattern:
    """``jit_<function>`` with any suffix that is not part of a name."""
    return re.compile(rf"^jit_{re.escape(function)}(?![A-Za-z0-9_])")


class Trace:
    """Device and host facts of one traced window on one chip."""

    def __init__(self, pd, device: int = 0):
        planes = {p.name: p for p in pd.planes}
        self.plane_names = sorted(planes)
        dev = planes.get(f"/device:TPU:{device}")
        self.lines: Dict[str, List] = {}
        if dev is not None:
            for ln in dev.lines:
                self.lines[ln.name] = _events(ln)
        self.host: Dict[str, List[Interval]] = defaultdict(list)
        host = planes.get(HOST_PLANE)
        if host is not None:
            for ln in host.lines:
                for name, a, b in _events(ln):
                    if name == WINDOW or name in HOST_SPANS:
                        self.host[name].append((a, b))
        if len(self.host[WINDOW]) != 1:
            raise TraceError(self.describe(
                f"{len(self.host[WINDOW])} '{WINDOW}' host spans, want 1"))
        self.lo, self.hi = self.host[WINDOW][0]
        self.window_s = self.hi - self.lo

    def describe(self, why: str) -> str:
        """What the trace held, for a loud failure."""
        names = Counter()
        for n, _, _ in self.lines.get(MODULES_LINE, []):
            names[re.sub(r"\(\d+\)$", "", n)] += 1
        return (f"{why}; planes {self.plane_names}; device lines "
                f"{ {k: len(v) for k, v in self.lines.items()} }; "
                f"modules {dict(names.most_common(12))}")

    def modules(self, function: str) -> List[Interval]:
        """Executions of the program compiled from ``function``, inside
        the window."""
        pat = module_pattern(function)
        return [(a, b) for n, a, b in self.lines.get(MODULES_LINE, [])
                if pat.match(n) and a >= self.lo and b <= self.hi]

    def ops(self) -> List[Tuple[str, float, float]]:
        return [(n, a, b) for n, a, b in self.lines.get(OPS_LINE, [])
                if b > self.lo and a < self.hi]

    def busy(self) -> List[Interval]:
        """Union of device operation intervals inside the window."""
        ops = self.ops()
        if not ops:
            raise TraceError(self.describe("no device operation in the "
                                           "window"))
        return union(clip([(a, b) for _, a, b in ops], self.lo, self.hi))

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy())

    def top_ops(self, n: int = 10) -> List[List]:
        """Device seconds by operation, named ``<program>/<instruction>``;
        control-flow ops (``while``, ``conditional``, ``call``), whose
        events span the operations inside them, are left out."""
        mods = sorted((a, b, re.sub(r"\(\d+\)$", "", name))
                      for name, a, b in self.lines.get(MODULES_LINE, []))
        starts = [a for a, _, _ in mods]
        tot: Dict[str, float] = defaultdict(float)
        for name, a, b in self.ops():
            inst = name.split(" = ")[0].lstrip("%")
            if re.match(r"(while|conditional|call)\b", inst):
                continue
            j = bisect.bisect_right(starts, a) - 1
            prog = mods[j][2] if j >= 0 and a <= mods[j][1] else "?"
            tot[f"{prog}/{inst}"] += min(b, self.hi) - max(a, self.lo)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device time in the window, summed by the host span that
        covers the middle of each gap (``other`` where none does)."""
        spans = [(a, b, name) for name in HOST_SPANS
                 for a, b in self.host.get(name, [])]
        tot: Dict[str, float] = defaultdict(float)
        t = self.lo
        for a, b in self.busy() + [(self.hi, self.hi)]:
            if a > t:
                mid = 0.5 * (a + t)
                owner = [s for s in spans if s[0] <= mid <= s[1]]
                name = min(owner, key=lambda s: s[1] - s[0])[2] if owner \
                    else "other"
                tot[name] += a - t
            t = max(t, b)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]


def capture_options():
    """Profiler options for a traced window: device activity and the
    benchmark's own host spans, no Python tracer, no HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts
