"""Open loop: requests sent on a schedule, whether or not earlier ones are done.

Arrivals are Poisson at the mix's ``rate_per_s`` over the window (the
generator's fixed multiset of gaps, ordered by the seed). A request is
submitted when its send time has passed and timed from that scheduled
time, so a stall of the engine delays the requests behind it. After the
window closes no request is sent, and the engine runs on until every sent
request is done, at most ``drain_s`` more seconds; one still unfinished
then has failed.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

from benchkit.record import Run
from benchkit.serving import Engine, check  # noqa: F401  (run.py calls this module's check)


def prepare(cell, seed: int, seconds: float, gen) -> dict:
    vocab = cell.config["config"]["vocab_size"]
    eng = Engine(cell, seed)
    eng.warm(gen.warmup(cell.traffic, vocab))
    return {"engine": eng,
            "requests": gen.open_loop(cell.traffic, seconds, seed, vocab)}


def window(state: dict, cell, seconds: float, tracer, run: Run) -> None:
    eng, reqs = state["engine"], state["requests"]
    drain = cell.traffic.get("drain_s", 60)
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    i = 0
    while True:
        now = clock()
        traced = tracer.tick(now)
        while i < len(reqs) and reqs[i].send_s <= now:
            eng.submit(reqs[i])
            i += 1
        if eng.has_work:
            eng.step(clock, traced=traced)
        elif i < len(reqs):
            with TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, reqs[i].send_s - clock()))
        else:
            break
        if now > seconds + drain:
            break
    tracer.stop()
    run.window_s = clock()
    run.sent = list(eng.sent)
    run.steps = list(eng.steps)
    state["attempted"] = len(eng.sent)
    state["failed"] = sum(1 for s in eng.sent if not s.done)
