"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers metrics read.

What a trace holds, as read on a TPU v5e with JAX 0.9: one plane per chip
named ``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per
launched program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO operation, named by its HLO text); and a
plane ``/host:CPU`` whose ``python`` line carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans, all named ``bench.<what>``. Device
and host events share one clock, in nanoseconds from the trace's start.

Busy time is the union of the module intervals of a chip, averaged over
the chips in the trace. An idle gap is a stretch of the window in which no
module runs; it is charged to the innermost benchmark span around its
midpoint, which says what the host was doing meanwhile.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OUTSIDE = "outside bench spans"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Trace:
    #: per chip: (module name, start ns, end ns), sorted by start
    modules: List[List[Tuple[str, int, int]]]
    #: per chip: (op short name, start ns, end ns), sorted by start
    ops: List[List[Tuple[str, int, int]]]
    #: benchmark host spans: (name, start ns, end ns), sorted by start
    spans: List[Tuple[str, int, int]]
    #: per chip: the union of its module intervals
    busy: List[List[Interval]] = dataclasses.field(init=False)

    def __post_init__(self):
        self.busy = [union([(s, e) for _, s, e in mods])
                     for mods in self.modules]

    def window(self) -> Interval:
        """The ``bench.window`` span, else the extent of device work."""
        w = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if w:
            return w[0][1], w[0][2]
        evs = [m for chip in self.modules for m in chip]
        if not evs:
            raise ValueError("the trace holds no device module")
        return min(e[1] for e in evs), max(e[2] for e in evs)


def _op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, oper = [], []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    mods = [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                elif line.name == OPS_LINE:
                    oper = [(_op_name(e.name), int(e.start_ns),
                             int(e.end_ns)) for e in line.events]
            modules.append(sorted(mods, key=lambda e: e[1]))
            ops.append(sorted(oper, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(modules, ops, sorted(spans, key=lambda e: e[1]))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Interval], a: int, b: int) -> int:
    """Nanoseconds of the merged intervals inside [a, b]."""
    return sum(max(0, min(y, b) - max(x, a)) for x, y in merged)


def busy_ns(trace: Trace, a: int, b: int) -> float:
    """Device-busy nanoseconds inside [a, b], averaged over the chips."""
    if not trace.busy:
        return 0.0
    return sum(covered(m, a, b) for m in trace.busy) / len(trace.busy)


def module_time(trace: Trace, pattern: str, a: int, b: int
                ) -> Tuple[float, int]:
    """(nanoseconds, launches) of modules whose name matches ``pattern``
    and that start inside [a, b), averaged over the chips."""
    rx = re.compile(pattern)
    if not trace.modules:
        return 0.0, 0
    ns, n = 0, 0
    for mods in trace.modules:
        for name, s, e in mods:
            if a <= s < b and rx.search(name):
                ns += e - s
                n += 1
    k = len(trace.modules)
    return ns / k, n // k


def idle_gaps(trace: Trace, a: int, b: int, chip: int = 0
              ) -> List[Interval]:
    """Stretches of [a, b] in which no module runs on ``chip``."""
    if not trace.busy:
        return []
    gaps, t = [], a
    for s, e in trace.busy[chip]:
        if e <= a or s >= b:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < b:
        gaps.append((t, b))
    return gaps


def innermost_span(trace: Trace, t: int, skip: str = WINDOW_SPAN
                   ) -> Optional[str]:
    best = None
    for name, s, e in trace.spans:
        if s > t:
            break
        if e >= t and name != skip and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def gaps_by_span(trace: Trace, a: int, b: int, top: int = 10
                 ) -> List[List]:
    """Idle seconds inside [a, b] by the host span they fall in."""
    acc: Dict[str, int] = defaultdict(int)
    for s, e in idle_gaps(trace, a, b):
        acc[innermost_span(trace, (s + e) // 2) or OUTSIDE] += e - s
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(trace: Trace, a: int, b: int, top: int = 10) -> List[List]:
    """Device self-seconds inside [a, b] by ``<module>/<op>``, largest
    first, on the first chip. An op that encloses others (a ``while`` over
    the layers) is charged only the time none of them runs."""
    if not trace.modules:
        return []
    mods = [(m.split("(", 1)[0], s, e) for m, s, e in trace.modules[0]]
    acc: Dict[str, int] = defaultdict(int)
    stack: List[List] = []              # open ops: [key, end]
    i = 0
    for op, s, e in trace.ops[0]:
        if not (a <= s < b):
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:                       # nested: not the parent's own time
            acc[stack[-1][0]] -= min(e, stack[-1][1]) - s
        while i + 1 < len(mods) and mods[i][2] <= s:
            i += 1
        owner = mods[i][0] if mods and mods[i][1] <= s < mods[i][2] else "?"
        key = f"{owner}/{op}"
        acc[key] += e - s
        stack.append([key, e])
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def spans_named(trace: Trace, name: str, a: int, b: int
                ) -> List[Interval]:
    return [(s, e) for n, s, e in trace.spans if n == name and a <= s < b]
