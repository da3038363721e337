"""Host spans of the serving engine, on the profiler's clock.

``span(name, **args)`` always enters a ``jax.profiler.TraceAnnotation``, so
while a profiler session is on the span lands on the trace's host plane,
beside the device's programs, where xprof or Perfetto show it. While a
session is on it also appends a record to a bounded in-process buffer, so
code in the same process (a benchmark's readers) can read the spans
without parsing the trace. The profiler session is the only switch: with
none, a span costs one ``is_enabled()`` call and an idle annotation.

A record is a dict with ``index`` (its sequence number in the process),
``name``, ``start_ns``, ``end_ns`` (``time.perf_counter_ns``; ``None``
while open), ``parent`` (the ``index`` of the enclosing open span on the
same thread, else ``None``) and ``args``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List

from jax.profiler import StepTraceAnnotation, TraceAnnotation

#: records kept; older ones are dropped first, and counted
LIMIT = 1 << 20


class _Buffer:
    """The process-wide store of records, as the profiler is process-wide;
    the stack of open spans is per thread."""

    def __init__(self, limit: int):
        self.seq = itertools.count()
        self.local = threading.local()
        self.reset(limit)

    def reset(self, limit: int) -> None:
        self.records: collections.deque = collections.deque(maxlen=limit)
        self.appended = 0

    @property
    def dropped(self) -> int:
        return self.appended - len(self.records)


_buffer = _Buffer(LIMIT)


class _Recorded:
    """An annotation whose span is also kept in the buffer."""

    __slots__ = ("ann", "name", "args", "rec")

    def __init__(self, ann: TraceAnnotation, name: str, args: Dict):
        self.ann, self.name, self.args = ann, name, args

    def __enter__(self) -> "_Recorded":
        self.ann.__enter__()
        stack = _buffer.local.__dict__.setdefault("stack", [])
        self.rec = {"index": next(_buffer.seq), "name": self.name,
                    "start_ns": time.perf_counter_ns(), "end_ns": None,
                    "parent": stack[-1]["index"] if stack else None,
                    "args": self.args}
        _buffer.records.append(self.rec)
        _buffer.appended += 1
        stack.append(self.rec)
        return self

    def __exit__(self, *exc) -> None:
        self.rec["end_ns"] = time.perf_counter_ns()
        _buffer.local.stack.pop()
        self.ann.__exit__(*exc)


def span(name: str, **args):
    """``with span("engine.decode", rows=4): ...``: a
    ``TraceAnnotation``, recorded too while a profile is on."""
    ann = TraceAnnotation(name, **args)
    if TraceAnnotation.is_enabled():
        return _Recorded(ann, name, args)
    return ann


def step_span(name: str, step: int, **args):
    """A span that is one step of a loop: the profiler shows it as step
    ``step`` (``StepTraceAnnotation``); its record's args hold ``step``."""
    ann = StepTraceAnnotation(name, step_num=step, **args)
    if TraceAnnotation.is_enabled():
        return _Recorded(ann, name, {"step": step, **args})
    return ann


def spans() -> List[Dict]:
    """The records held, oldest first."""
    return list(_buffer.records)


def dropped() -> int:
    """Records dropped for the bound since the process started or the last
    ``clear()``."""
    return _buffer.dropped


def clear() -> None:
    """Forget every record, and the drop count."""
    _buffer.reset(LIMIT)
