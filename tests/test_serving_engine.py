"""Continuous-batching engine vs direct model rollout."""
import jax
import jax.numpy as jnp
import numpy as np

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
