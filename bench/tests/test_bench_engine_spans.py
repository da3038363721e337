"""The engine-span readers on a recording made on a TPU v5e: the tiny chat
cell's 2-layer engine served through the harness's ``Engine`` (each
``step()`` in a ``bench.step`` span), 11 steps profiled, 8 prefills and 13
decodes, written on the TPU host by

    python3 bench/tests/record_engine_trace.py

as ``data/engine.xplane.pb`` (trimmed to the chip's module line and the
host's annotations) and ``data/engine_spans.json`` (the program's
in-memory span records of the profiled stretch)."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402
from benchkit import engine_spans as es  # noqa: E402
from benchkit.record import Run, engine_host_ms  # noqa: E402

DATA = HERE / "data"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(DATA / "engine.xplane.pb"))


@pytest.fixture(scope="module")
def recs():
    return json.loads((DATA / "engine_spans.json").read_text())


def traced_run(trace, sent=()):
    return Run(config={}, peaks={}, setup_s=0.0, window_s=0.0,
               sent=list(sent), trace=trace)


def test_the_recording_holds_a_device_and_the_engine_tree(trace, recs):
    assert trace.modules and trace.modules[0]
    names = {r["name"] for r in recs}
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.decode", "engine.decode.pull"} <= names
    a, b = trace.window()
    steps = [r for r in recs if r["name"] == "engine.step"]
    assert len(steps) == len(tr.spans_named(trace, "bench.step", a, b))


def test_each_engine_step_aligns_inside_its_bench_step_within_20us(trace,
                                                                   recs):
    a, b = trace.window()
    outer = tr.spans_named(trace, "bench.step", a, b)
    steps = [r for r in recs if r["name"] == "engine.step"]
    off = es.clock_offset(outer, steps)
    for (s, e), r in zip(outer, steps):
        assert r["start_ns"] + off >= s - es.TOLERANCE_NS
        assert r["end_ns"] + off <= e + es.TOLERANCE_NS
    aligned = es.align(trace, recs, a, b)
    assert aligned is not None and len(aligned) == len(outer)
    assert sum(st.prefills for st in aligned) == sum(
        1 for r in recs if r["name"] == "engine.prefill")


def test_a_record_off_by_more_than_the_tolerance_reads_nothing(trace, recs):
    a, b = trace.window()
    moved = [dict(r) for r in recs]
    step = next(r for r in moved if r["name"] == "engine.step")
    step["end_ns"] += 3 * (step["end_ns"] - step["start_ns"]) \
        + 2 * es.TOLERANCE_NS
    assert es.align(trace, moved, a, b) is None


def test_the_idle_split_adds_up_to_the_idle_engine_host_ms_reads(trace,
                                                                 recs):
    """Per step: admission + decode + the rest of ``engine.step`` + the
    rest of ``bench.step`` is the ``bench.step`` span's idle time."""
    a, b = trace.window()
    aligned = es.align(trace, recs, a, b)
    idle = []
    for st in aligned:
        s, e = st.bench
        whole = (e - s) - tr.busy_ns(trace, s, e)
        parts = es.split(trace, st)
        assert sum(parts.values()) == pytest.approx(whole, abs=1000)
        assert all(v >= 0 for v in parts.values())
        idle.append(whole)
    assert engine_host_ms(traced_run(trace)) == pytest.approx(
        sum(idle) / len(idle) / 1e6, abs=1e-3)


def test_the_readers_give_numbers_on_the_recording(trace, recs):
    run = traced_run(trace)
    admit, decode = es.admit_idle_ms(run, recs), es.decode_idle_ms(run, recs)
    assert admit is not None and admit > 0
    assert decode is not None and decode > 0


def test_every_reader_gives_none_without_spans_or_timestamps(
        trace, monkeypatch):
    run = traced_run(trace)
    assert es.admit_idle_ms(run, []) is None
    assert es.decode_idle_ms(run, []) is None
    # a program older than the telemetry module
    import repro.serving
    monkeypatch.delattr(repro.serving, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert es.records() is None
    assert es.admit_idle_ms(run) is None
    assert es.decode_idle_ms(run) is None
    old = sent(0.0, SimpleNamespace(tokens=[1, 2]))
    assert es.queue_waits_ms(client([old], [0.5])) is None
    assert es.queue_waits_ms(client([], [0.5])) is None
    assert es.queue_waits_ms(client([old], [])) is None


def sent(send_s, ereq):
    return SimpleNamespace(req=SimpleNamespace(send_s=send_s), ereq=ereq)


def stamps(submit, admit, tokens):
    return SimpleNamespace(submit_ns=submit, admit_ns=admit, token_ns=tokens)


def client(sent_reqs, traced_ends):
    """A run's client side: requests sent and the traced steps' ends."""
    steps = [SimpleNamespace(t1=t, traced=True) for t in traced_ends]
    return Run(config={}, peaks={}, setup_s=0.0, window_s=0.0,
               sent=sent_reqs, steps=steps)


def test_queue_waits_count_the_traced_stretch_and_the_never_admitted():
    run = client([sent(0.1, stamps(0, 2_000_000, [3_000_000, 9_000_000])),
                  sent(0.2, stamps(5_000_000, None, [])),
                  sent(0.9, stamps(6_000_000, 90_000_000, [91_000_000]))],
                 [0.3, 0.5])
    # the third was due after the traced stretch; the second never got in
    assert es.queue_waits_ms(run).tolist() == [2.0, 4.0]
