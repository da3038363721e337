"""The trace reduction on a small trace recorded on a TPU v5e: three launches
of one jitted program, each inside a ``bench.step`` span, 2 ms apart."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

TINY = BENCH / "tests" / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(TINY))


def test_it_finds_one_chip_its_modules_and_the_benchmark_spans(trace):
    assert len(trace.modules) == 1
    names = [m[0] for m in trace.modules[0]]
    assert len(names) == 3 and all(n.startswith("jit__lambda(") for n in names)
    assert [s[0] for s in trace.spans] == ["bench.step"] * 3
    assert len(trace.ops[0]) == 9


def test_busy_time_is_the_union_of_module_intervals(trace):
    a, b = trace.window()
    mods = trace.modules[0]
    assert (a, b) == (mods[0][1], mods[-1][2])
    total = sum(e - s for _, s, e in mods)
    assert tr.busy_ns(trace, a, b) == total
    ns, n = tr.module_time(trace, r"^jit__lambda\(", a, b)
    assert (ns, n) == (total, 3)
    assert tr.module_time(trace, r"^jit__prefill", a, b) == (0, 0)


def test_idle_gaps_fill_the_window_and_are_charged_to_host_spans(trace):
    a, b = trace.window()
    gaps = tr.idle_gaps(trace, a, b)
    assert len(gaps) == 2
    assert sum(e - s for s, e in gaps) + tr.busy_ns(trace, a, b) == b - a
    by_span = dict(tr.gaps_by_span(trace, a, b))
    # between launches the host sleeps outside any bench span
    assert sum(by_span.values()) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)


def test_top_ops_name_module_and_op(trace):
    a, b = trace.window()
    ops = tr.top_ops(trace, a, b)
    assert ops and all(k.startswith("jit__lambda/") for k, _ in ops)
    assert sum(v for _, v in ops) <= tr.busy_ns(trace, a, b) / 1e9 + 1e-12


def test_union_and_cover_on_synthetic_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.covered([(0, 3), (5, 8)], 2, 6) == 2
