"""The serving engine's own spans and timestamps, read beside the trace.

While a profile is on, the program keeps its spans in memory
(``repro.serving.telemetry.spans()``, on ``time.perf_counter_ns``); the
trace reduction keeps only the harness's ``bench.*`` spans. Each
``engine.step`` record is paired with the ``bench.step`` trace span around
the same call, the last records with the traced steps in order, and the
offset between the two clocks is the median over the pairs of the
difference of their midpoints. If an aligned ``engine.step`` then sticks
out of its ``bench.step`` by more than ``TOLERANCE_NS``, nothing is read.

A program without the telemetry module or the request timestamps (one
older than them) gives every reader ``None``.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

import numpy as np

import trace_reduce
from trace_reduce import Interval

STEP = "engine.step"
TRACE_STEP = "bench.step"
TOLERANCE_NS = 20_000


def records() -> Optional[List[Dict]]:
    """The program's span records, or None where it keeps none."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    return telemetry.spans()


@dataclasses.dataclass
class Step:
    """One traced ``step()`` call on the trace's clock: the harness's span
    around it, the engine's own span (clipped to the harness's) and its
    ``engine.admit`` and ``engine.decode`` children (clipped to that)."""
    bench: Interval
    engine: Interval
    admit: List[Interval]
    decode: List[Interval]
    prefills: int


def _clip(iv: Interval, to: Interval) -> Interval:
    a, b = max(iv[0], to[0]), min(iv[1], to[1])
    return (a, max(a, b))


def clock_offset(outer: Sequence[Interval], steps: Sequence[Dict]) -> int:
    """Trace clock minus the program's: the median over the pairs of
    (``bench.step``, ``engine.step`` record) of their midpoints' gap."""
    return int(np.median([(s + e - r["start_ns"] - r["end_ns"]) / 2
                          for (s, e), r in zip(outer, steps)]))


def align(trace: trace_reduce.Trace, recs: Optional[Sequence[Dict]],
          a: int, b: int) -> Optional[List[Step]]:
    """The traced steps inside [a, b] with the engine's spans moved onto the
    trace's clock; None where there are no records or they do not fit."""
    outer = trace_reduce.spans_named(trace, TRACE_STEP, a, b)
    closed = [r for r in recs or () if r["end_ns"] is not None]
    steps = [r for r in closed if r["name"] == STEP]
    if not outer or len(steps) < len(outer):
        return None
    steps = steps[-len(outer):]
    offset = clock_offset(outer, steps)
    kids: Dict[int, List[Dict]] = defaultdict(list)
    for r in closed:
        if r["parent"] is not None:
            kids[r["parent"]].append(r)

    def moved(r: Dict) -> Interval:
        return r["start_ns"] + offset, r["end_ns"] + offset

    out = []
    for (s, e), r in zip(outer, steps):
        es, ee = moved(r)
        if es < s - TOLERANCE_NS or ee > e + TOLERANCE_NS:
            return None
        eng = _clip((es, ee), (s, e))
        admits = [c for c in kids[r["index"]] if c["name"] == "engine.admit"]
        out.append(Step(
            bench=(s, e), engine=eng,
            admit=[_clip(moved(c), eng) for c in admits],
            decode=[_clip(moved(c), eng) for c in kids[r["index"]]
                    if c["name"] == "engine.decode"],
            prefills=sum(1 for c in admits for p in kids[c["index"]]
                         if p["name"] == "engine.prefill")))
    return out


def idle_ns(trace: trace_reduce.Trace, spans: Sequence[Interval]) -> float:
    """Nanoseconds of ``spans`` in which the device runs nothing."""
    return sum((e - s) - trace_reduce.busy_ns(trace, s, e) for s, e in spans)


def _gaps(outer: Interval, inner: Sequence[Interval]) -> List[Interval]:
    """The parts of ``outer`` that no interval of ``inner`` covers."""
    out, t = [], outer[0]
    for s, e in trace_reduce.union(inner):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < outer[1]:
        out.append((t, outer[1]))
    return out


def split(trace: trace_reduce.Trace, st: Step) -> Dict[str, float]:
    """A step's device-idle nanoseconds by where the host was: admission,
    the decode's round trip, the rest of ``engine.step``, and the
    harness's ``bench.step`` outside ``engine.step``."""
    return {"admit": idle_ns(trace, st.admit),
            "decode": idle_ns(trace, st.decode),
            "engine_rest": idle_ns(trace, _gaps(st.engine,
                                                st.admit + st.decode)),
            "bench_rest": idle_ns(trace, _gaps(st.bench, [st.engine]))}


def traced_steps(run, recs: Optional[Sequence[Dict]] = None
                 ) -> Optional[List[Step]]:
    """The run's traced steps aligned, or None; ``recs`` defaults to the
    program's records."""
    if run.trace is None or not run.trace.modules:
        return None
    a, b = run.traced_window()
    return align(run.trace, records() if recs is None else recs, a, b)


def admit_idle_ms(run, recs=None) -> Optional[float]:
    """Device-idle milliseconds inside ``engine.admit`` per prefill."""
    steps = traced_steps(run, recs)
    n = sum(st.prefills for st in steps or ())
    if not n:
        return None
    return sum(idle_ns(run.trace, st.admit) for st in steps) / n / 1e6


def decode_idle_ms(run, recs=None) -> Optional[float]:
    """Device-idle milliseconds inside ``engine.decode`` per decode."""
    steps = traced_steps(run, recs)
    n = sum(len(st.decode) for st in steps or ())
    if not n:
        return None
    return sum(idle_ns(run.trace, st.decode) for st in steps) / n / 1e6


def queue_waits_ms(run) -> Optional[np.ndarray]:
    """Per request sent by the end of the traced stretch, milliseconds from
    ``submit`` into the engine to the start of its prefill. Later requests
    are left out: stopping the profile holds the client's loop for seconds,
    and what falls due meanwhile arrives in one burst that no untraced run
    sees. A request never admitted waited at least until the last
    timestamp of the run, and counts so."""
    traced = run.traced_steps()
    if not traced:
        return None
    reqs = [s.ereq for s in run.sent if s.req.send_s <= traced[-1].t1]
    if not reqs or getattr(reqs[0], "submit_ns", None) is None:
        return None
    end = max(max([r.submit_ns, *r.token_ns]) for r in reqs)
    return np.asarray([((end if r.admit_ns is None else r.admit_ns)
                        - r.submit_ns) / 1e6 for r in reqs])
