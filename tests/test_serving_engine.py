"""Continuous-batching engine vs direct model rollout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduce_for_smoke
from repro.dist.sharding import unbox
from repro.models import model
from repro.serving.engine import ServeRequest, ServingEngine
import dataclasses


def greedy_rollout(cfg, params, prompt, n_new):
    """Reference: full re-forward greedy decoding."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _, _ = model.forward(
            cfg, params, {"tokens": jnp.asarray(toks, jnp.int32)[None]})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_rollout_single():
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("gemma-7b")),
                              dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    prompt = np.asarray([5, 9, 2, 7, 11, 3], np.int32)
    want = greedy_rollout(cfg, params, prompt, 8)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    r = ServeRequest(rid=0, prompt=prompt, max_new_tokens=8)
    eng.submit(r)
    eng.run()
    assert r.tokens == want


def test_engine_multi_request_batched():
    cfg = dataclasses.replace(reduce_for_smoke(get_arch("qwen2-72b")),
                              dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(rid=i,
                         prompt=rng.integers(0, cfg.vocab_size, 6).astype(
                             np.int32),
                         max_new_tokens=5) for i in range(5)]
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.done_step is not None
        assert len(r.tokens) == 5
        want = greedy_rollout(cfg, params, r.prompt, 5)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_serve_launcher_completes_mixed_lengths(capsys):
    """`python -m repro.launch.serve` on the reduced model: every request
    completes, one prefill per request, decode launches counted."""
    from repro.launch import serve
    args = serve.parse_args(["--requests", "5", "--max-new", "4"])
    cfg, _, eng, reqs = serve.build(args)
    serve.serve(eng, reqs)
    assert [r.prompt_tokens for r in reqs] == [256, 1024, 256, 1024, 256]
    assert all(len(r.tokens) == 4 for r in reqs)
    assert eng.prefill_count == 5
    assert 3 <= eng.decode_count <= eng.step_count
    assert serve.main(["--requests", "2", "--max-new", "2"]) == 0
    assert "served 2 requests" in capsys.readouterr().out


def _prefix_requests(cfg):
    """Four requests for four slots: slots 1 and 2 finish after one
    decode, slot 3 after five and slot 0 after nine, so the live slots
    are {0, 3} (the top rung) and then {0} (the lowest)."""
    rng = np.random.default_rng(5)
    return [ServeRequest(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, 4 + i).astype(np.int32), max_new_tokens=n)
        for i, n in enumerate((10, 2, 2, 6))]


#: backend compiles in this process (programs built)
_BUILT = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: _BUILT.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def _serve_recording_batches(eng, reqs):
    """Serve ``reqs``; (live slots, rows launched, programs built) of every
    decode."""
    launched, decode_one = [], eng._decode_one

    def recorded(b):
        live = {i for i, s in enumerate(eng.slots) if s.req is not None}
        before = len(_BUILT)
        decode_one(b)
        launched.append((live, b, len(_BUILT) - before))

    eng._decode_one = recorded
    for r in reqs:
        eng.submit(r)
    eng.run()
    return launched


@pytest.mark.parametrize("name", ["qwen2-72b", "zamba2-7b"])
def test_prefix_decodes_serve_what_a_full_batch_engine_serves(name):
    """With max_batch 4 the ladder is 1, 2, 4: live slots {0, 3} launch 4
    rows and {0} alone 1, and every request gets the tokens an engine that
    always launches all four rows gives it. Every rung's program exists
    once the engine does: no decode builds one while the live rows cross
    rungs."""
    cfg = dataclasses.replace(reduce_for_smoke(get_arch(name)),
                              dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, max_batch=4, max_seq=32)
    assert eng.ladder == [1, 2, 4]
    built = eng._decode._cache_size()
    assert built == len(eng.ladder)
    prefix = _prefix_requests(cfg)
    launched = _serve_recording_batches(eng, prefix)
    assert ({0, 3}, 4, 0) in launched and ({0}, 1, 0) in launched
    assert all(b == min(r for r in eng.ladder if r > max(live))
               for live, b, _ in launched)
    assert sum(n for _, _, n in launched) == 0
    assert eng._decode._cache_size() == built
    assert eng.decode_rows_launched == sum(b for _, b, _ in launched)

    full = ServingEngine(cfg, params, max_batch=4, max_seq=32)
    full.ladder = [4]
    whole = _prefix_requests(cfg)
    assert {b for _, b, _ in _serve_recording_batches(full, whole)} == {4}
    assert [r.tokens for r in prefix] == [r.tokens for r in whole]
    assert all(r.done_step is not None for r in prefix)


def test_decode_rows_launched_is_the_sum_of_the_batch_args(tmp_path):
    from repro.serving import telemetry

    cfg = dataclasses.replace(reduce_for_smoke(get_arch("gemma-7b")),
                              dtype="float32")
    params = unbox(model.init(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, max_batch=4, max_seq=32)
    for r in _prefix_requests(cfg):
        eng.submit(r)
    telemetry.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run()
        args = [r["args"] for r in telemetry.spans()
                if r["name"] == "engine.decode"]
    finally:
        telemetry.clear()
    assert len(args) == eng.decode_count
    assert eng.decode_rows_launched == sum(a["batch"] for a in args)
    assert {a["batch"] for a in args} == {1, 4}
