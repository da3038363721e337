"""Standing backlog: the engine never runs short of waiting requests.

Before every step the queue is topped up to the mix's ``waiting``
requests (twice the engine's slots), from a pool of the mix's sizes in an
order drawn from the seed, renewed in another order when it runs out. The
window ends at the first step that ends ``seconds`` after it opened; what
the client counts is the tokens prefilled and generated in it.
"""
from __future__ import annotations

import time

from benchkit.record import Run
from benchkit.serving import Engine, check  # noqa: F401  (run.py calls this module's check)


def prepare(cell, seed: int, seconds: float, gen) -> dict:
    vocab = cell.config["config"]["vocab_size"]
    eng = Engine(cell, seed)
    eng.warm(gen.warmup(cell.traffic, vocab))
    pool = cell.traffic["arrivals"]["pool"]

    def batches():
        stream = 0
        while True:
            yield from gen.requests(cell.traffic, pool, seed, vocab,
                                    stream=stream, first_rid=stream * pool)
            stream += 1

    return {"engine": eng, "requests": batches()}


def window(state: dict, cell, seconds: float, tracer, run: Run) -> None:
    eng, reqs = state["engine"], state["requests"]
    waiting = cell.traffic["arrivals"]["waiting"]
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    while True:
        now = clock()
        if now >= seconds:
            break
        traced = tracer.tick(now)
        while eng.waiting < waiting:
            eng.submit(next(reqs))
        eng.step(clock, traced=traced)
    tracer.stop()
    run.window_s = eng.steps[-1].t1
    run.sent = list(eng.sent)
    run.steps = list(eng.steps)
    state["attempted"] = len(eng.sent)
    state["failed"] = 0
