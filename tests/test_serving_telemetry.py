"""The serving engine's spans and per-request timestamps
(``repro.serving.telemetry``, ``ServeRequest.submit_ns/admit_ns/token_ns``)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduce_for_smoke
from repro.dist.sharding import unbox
from repro.models import model
from repro.serving import telemetry
from repro.serving.engine import ServeRequest, ServingEngine
from repro.serving.telemetry import span

#: each span's parent in the engine's tree
TREE = {"engine.step": None,
        "engine.admit": "engine.step",
        "engine.schedule": "engine.admit",
        "engine.prefill": "engine.admit",
        "engine.prefill.launch": "engine.prefill",
        "engine.prefill.pull": "engine.prefill",
        "engine.slot_write": "engine.prefill",
        "engine.decode": "engine.step",
        "engine.decode.inputs": "engine.decode",
        "engine.decode.launch": "engine.decode",
        "engine.decode.pull": "engine.decode",
        "engine.decode.emit": "engine.decode"}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("gemma-7b")),
                              dtype="float32")
    return cfg, unbox(model.init(cfg, jax.random.PRNGKey(0)))


@pytest.fixture
def clean():
    telemetry.clear()
    yield
    telemetry.clear()


def requests(cfg, n=5):
    rng = np.random.default_rng(0)
    return [ServeRequest(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, 4 + 2 * (i % 2)).astype(np.int32),
        max_new_tokens=3 + i % 3) for i in range(n)]


def serve(tiny, traced_dir=None):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    reqs = requests(cfg)
    for r in reqs:
        eng.submit(r)
    if traced_dir is None:
        eng.run()
    else:
        with jax.profiler.trace(str(traced_dir)):
            eng.run()
    return eng, reqs


def test_nothing_is_kept_without_a_profile_and_all_is_kept_under_one(
        tiny, clean, tmp_path):
    with span("outside"):
        pass
    serve(tiny)
    assert telemetry.spans() == []
    with jax.profiler.trace(str(tmp_path)):
        with span("inside", k=1):
            pass
    recs = telemetry.spans()
    assert [r["name"] for r in recs] == ["inside"]
    assert recs[0]["args"] == {"k": 1}
    assert recs[0]["start_ns"] <= recs[0]["end_ns"]


def test_the_engine_spans_nest_as_its_tree_and_match_its_counters(
        tiny, clean, tmp_path):
    eng, _ = serve(tiny, tmp_path)
    recs = telemetry.spans()
    by_index = {r["index"]: r for r in recs}
    assert {r["name"] for r in recs} == set(TREE)
    for r in recs:
        parent = by_index.get(r["parent"])
        assert (parent and parent["name"]) == TREE[r["name"]], r
        if parent is not None:
            assert parent["start_ns"] <= r["start_ns"] <= r["end_ns"] \
                <= parent["end_ns"]
    names = [r["name"] for r in recs]
    assert names.count("engine.prefill") == eng.prefill_count
    assert names.count("engine.decode") == eng.decode_count
    steps = [r for r in recs if r["name"] == "engine.step"]
    assert [r["args"]["step"] for r in steps] == \
        list(range(1, eng.step_count + 1))
    assert {r["args"]["engine"] for r in steps} == {eng.index}
    rows = [r["args"]["rows"] for r in recs if r["name"] == "engine.decode"]
    assert max(rows) == 2 and min(rows) >= 1
    decodes = [r["args"] for r in recs if r["name"] == "engine.decode"]
    assert all(a["rows"] <= a["batch"] and a["batch"] in eng.ladder
               for a in decodes)
    assert sum(a["batch"] for a in decodes) == eng.decode_rows_launched
    prefills = [r for r in recs if r["name"] == "engine.prefill"]
    assert sorted(r["args"]["rid"] for r in prefills) == list(range(5))
    assert all(r["args"]["tokens"] in (4, 6) for r in prefills)
    assert telemetry.dropped() == 0


def test_every_request_is_stamped_in_order_once_per_token(tiny):
    _, reqs = serve(tiny)
    for r in reqs:
        assert len(r.token_ns) == len(r.tokens) == r.max_new_tokens
        assert r.submit_ns <= r.admit_ns <= r.token_ns[0]
        assert r.token_ns == sorted(r.token_ns)
        assert r.ttft_step is not None and r.done_step is not None


def test_the_bound_drops_the_oldest_records_and_counts_them(
        clean, tmp_path, monkeypatch):
    monkeypatch.setattr(telemetry, "_buffer", telemetry._Buffer(4))
    with jax.profiler.trace(str(tmp_path)):
        for i in range(10):
            with span("s", i=i):
                pass
    recs = telemetry.spans()
    assert [r["args"]["i"] for r in recs] == [6, 7, 8, 9]
    assert telemetry.dropped() == 6
    telemetry.clear()
    assert telemetry.spans() == [] and telemetry.dropped() == 0


def test_served_tokens_are_the_same_with_the_profiler_on_and_off(
        tiny, clean, tmp_path):
    _, off = serve(tiny)
    eng, on = serve(tiny, tmp_path)
    assert [r.tokens for r in on] == [r.tokens for r in off]
    assert len(telemetry.spans()) > 0
