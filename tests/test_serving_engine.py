"""Continuous-batching engine vs direct model rollout."""
import re

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
    decode launch (each ``_decode_one`` also pulls the previous launch's
    tokens)."""
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


@pytest.fixture(scope="module", params=["qwen2-72b", "zamba2-7b"])
def smoke(request):
    """A dense and a hybrid smoke model, in float32."""
    cfg = dataclasses.replace(reduce_for_smoke(get_arch(request.param)),
                              dtype="float32")
    return cfg, unbox(model.init(cfg, jax.random.PRNGKey(0)))


def test_the_decode_pipeline_serves_greedy_rollouts_tokens(smoke):
    """Two slots of 16 positions; each decode is launched before the
    previous one's tokens are pulled. Requests a (5 tokens), b (1: its
    prefill's), c (2), d (a 13-token prompt that reaches position
    max_seq - 1 after 3 tokens of its 10) and e, sent after step 2. c, d
    and e are admitted with a decode in flight; d takes c's slot, and e
    a's, in the step after their last decode was launched, while that
    decode's token is still on the device. Every request gets
    ``greedy_rollout``'s tokens and is done in the step that emitted its
    last token; ``has_work`` holds while a token is unpulled, and
    ``run()`` ends with every request done, no decode in flight and no
    decode program built while serving."""
    cfg, params = smoke
    rng = np.random.default_rng(7)
    shape = {"a": (4, 5), "b": (5, 1), "c": (3, 2), "d": (13, 10),
             "e": (6, 3)}
    reqs = {k: ServeRequest(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, S).astype(np.int32), max_new_tokens=n)
        for i, (k, (S, n)) in enumerate(shape.items())}
    served = {k: min(n, 16 - S) for k, (S, n) in shape.items()}
    assert served["d"] == 3
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=16)
    built = eng._decode._cache_size()
    assert built == len(eng.ladder) == 2

    admitted, prefill_into = [], eng._prefill_into

    def recording_admission(slot, req):
        pending = eng._pending
        admitted.append((eng.step_count, slot, req.rid, pending is not None,
                         pending is not None and any(
                             i == slot and last for i, _, last in pending[1])))
        prefill_into(slot, req)

    step, decode_one, compiles = eng.step, eng._decode_one, []

    def counted_decode(b):
        n = len(_BUILT)
        decode_one(b)
        compiles.append(len(_BUILT) - n)

    def checked_step():
        before = {k: len(r.tokens) for k, r in reqs.items()}
        step()
        k = eng.step_count
        for key, r in reqs.items():
            done = len(r.tokens) == served[key]
            assert (r.done_step is not None) == done, (k, key)
            if r.done_step == k:
                assert before[key] < len(r.tokens), (k, key)
        if eng._pending is not None:
            assert eng.has_work
        if k == 2:
            eng.submit(reqs["e"])

    eng._prefill_into, eng.step = recording_admission, checked_step
    eng._decode_one = counted_decode
    for key in "abcd":
        eng.submit(reqs[key])
    eng.run()

    assert all(r.done_step is not None for r in reqs.values())
    assert eng._pending is None and not eng.has_work
    for key, r in reqs.items():
        assert len(r.tokens) == len(r.token_ns) == served[key]
        assert r.tokens == greedy_rollout(cfg, params, r.prompt,
                                          served[key]), key
    rid = {r.rid: k for k, r in reqs.items()}
    by_req = {rid[r]: (k, slot, ahead, reused)
              for k, slot, r, ahead, reused in admitted}
    assert by_req["b"][0] == reqs["b"].done_step == reqs["b"].ttft_step
    assert by_req["c"][2] and by_req["d"][2] and by_req["e"][2]
    assert by_req["d"][1] == by_req["c"][1] and by_req["d"][3]
    assert by_req["e"][1] == by_req["a"][1] and by_req["e"][3]
    assert by_req["d"][0] == reqs["c"].done_step
    assert eng.decodes_ahead == eng.decode_count - 1
    assert len(compiles) == eng.decode_count and sum(compiles) == 0
    assert eng._decode._cache_size() == built


def test_a_step_with_a_token_unpulled_has_work(smoke):
    """After a request's last decode is launched its slot is free and the
    queue empty, yet its token is on the device: ``has_work`` holds until
    the drain step pulls it."""
    cfg, params = smoke
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=16)
    r = ServeRequest(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                     max_new_tokens=2)
    eng.submit(r)
    eng.step()
    assert eng.active == 0 and not eng.queue and eng._pending is not None
    assert eng.has_work and r.done_step is None and len(r.tokens) == 1
    eng.step()
    assert not eng.has_work and r.done_step == 2 and len(r.tokens) == 2
    assert eng.decode_count == 1 and eng.decodes_ahead == 0


def test_the_decode_is_the_engines_one_lambda(smoke):
    """The profile finds the decode by its module, ``jit__lambda``: the
    decode lowers to it, and no other program of the engine does."""
    cfg, params = smoke
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=16)
    rows = jnp.zeros((2, 2), jnp.int32)

    def module(lowered):
        return re.search(r"^module @(\S+)", lowered.as_text(), re.M)[1]

    assert module(eng._decode.lower(eng.params, eng._tokens, rows,
                                    eng.cache)) == "jit__lambda"
    batch = eng._prompt_batch(np.arange(1, 5, dtype=np.int32))
    _, pcache = eng._prefill(cfg, params, batch)
    assert module(eng._prefill.lower(cfg, params, batch)) \
        == "jit__prefill_last"
    assert module(eng._write.lower(eng.cache, pcache, jnp.int32(0))) \
        == "jit__write_slot"
    jits = {k for k, v in vars(eng).items() if hasattr(v, "lower")}
    assert jits == {"_prefill", "_decode", "_write"}
