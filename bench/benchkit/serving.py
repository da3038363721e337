"""Drive the program's ``ServingEngine`` and record what a client sees.

The benchmark makes the weights (with the configuration's reference
module), builds the engine from the configuration's ``model_config``,
submits requests, calls ``step()`` and stamps each output token with the
host clock when ``step()`` has returned it. Each call into the program is
wrapped in a ``bench.*`` span for the profiler. After the window it
compares served tokens with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Callable, Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

from benchkit import costs


@dataclasses.dataclass
class Sent:
    """One request as the client sees it."""
    req: object                          # generator.Request
    ereq: object                         # the engine's ServeRequest
    times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.req.prompt)


@dataclasses.dataclass
class Step:
    """One ``step()`` call: its host times and the work it did."""
    t0: float
    t1: float
    traced: bool = False
    #: prompt length of each request prefilled in the step
    prefill_lens: List[int] = dataclasses.field(default_factory=list)
    #: positions attended by each row the step decoded
    decode_rows: List[int] = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> int:
        """Prompt tokens prefilled plus output tokens made."""
        return (sum(self.prefill_lens) + len(self.prefill_lens)
                + len(self.decode_rows))

    def flops(self, c: Dict) -> int:
        return (sum(costs.prefill_flops(c, S) for S in self.prefill_lens)
                + costs.decode_flops(c, self.decode_rows))


class Engine:
    """The program's serving engine with the benchmark's weights."""

    def __init__(self, cell, seed: int):
        import jax
        from repro.configs.base import ModelConfig
        from repro.serving.engine import ServingEngine

        self.c = cell.config["config"]
        self.ref = cell.reference()
        self.weights = jax.block_until_ready(
            self.ref.make_weights(self.c, seed))
        e = cell.traffic["engine"]
        self.max_seq = e["max_seq"]
        self.eng = ServingEngine(ModelConfig(**cell.config["model_config"]),
                                 self.weights, max_batch=e["max_batch"],
                                 max_seq=e["max_seq"],
                                 scheduler=e["scheduler"])
        self.sent: List[Sent] = []
        self.inflight: List[Sent] = []
        self.steps: List[Step] = []

    # ------------------------------------------------------------ serving
    def submit(self, req) -> Sent:
        from repro.serving.engine import ServeRequest

        if len(req.prompt) + req.max_new > self.max_seq:
            raise ValueError(f"request {req.rid} needs "
                             f"{len(req.prompt) + req.max_new} positions of "
                             f"{self.max_seq}")
        s = Sent(req, ServeRequest(rid=req.rid, prompt=req.prompt,
                                   max_new_tokens=req.max_new))
        with TraceAnnotation("bench.submit"):
            self.eng.submit(s.ereq)
        self.sent.append(s)
        self.inflight.append(s)
        return s

    @property
    def has_work(self) -> bool:
        return self.eng.has_work

    @property
    def waiting(self) -> int:
        return len(self.eng.queue)

    def step(self, clock: Callable[[], float], traced: bool = False) -> Step:
        t0 = clock()
        with TraceAnnotation("bench.step"):
            self.eng.step()
        now = clock()
        st = Step(t0=t0, t1=now, traced=traced)
        with TraceAnnotation("bench.collect"):
            k = self.eng.step_count
            keep = []
            for s in self.inflight:
                n = len(s.ereq.tokens)
                new = n - len(s.times)
                if new:
                    s.times.extend([now] * new)
                    if s.ereq.ttft_step == k:
                        st.prefill_lens.append(s.prompt_len)
                        new -= 1
                    if new:
                        # the decode wrote the previous token at position
                        # S + n - 2 and attended to every position up to it
                        st.decode_rows.append(s.prompt_len + n - 1)
                if s.ereq.done_step is not None:
                    s.done = True
                else:
                    keep.append(s)
            self.inflight = keep
        self.steps.append(st)
        return st

    def warm(self, reqs) -> None:
        for r in reqs:
            self.submit(r)
        while self.has_work:
            self.step(time.perf_counter)
        self.sent.clear()
        self.steps.clear()

    # ---------------------------------------------------------- the check
    def free(self) -> None:
        """Drop the engine and its cache; the weights stay for the
        reference."""
        self.eng = None
        gc.collect()

    def sample(self, seed: int, tokens: int, max_requests: int
               ) -> List[Sent]:
        """Finished requests drawn from the seed, the longest first, until
        ``tokens`` served tokens or ``max_requests`` requests."""
        done = [s for s in self.sent if s.done]
        if not done:
            return []
        rng = np.random.default_rng([seed, 3])
        longest = max(done, key=lambda s: (len(s.ereq.tokens), s.prompt_len))
        rest = [s for s in done if s is not longest]
        out = [longest]
        for i in rng.permutation(len(rest)):
            if (len(out) >= max_requests
                    or sum(len(s.ereq.tokens) for s in out) >= tokens):
                break
            out.append(rest[i])
        return out

    def gaps(self, sample: List[Sent], fp8_control: bool = False
             ) -> Dict[str, float]:
        """Widest gap by which a served token's reference logit lies below
        the reference's best, over the sample; with ``fp8_control`` also
        the same gap for the token the float8 control puts first."""
        import jax.numpy as jnp

        ref = self.ref.token_stats_fn(self.c)
        ctl = self.ref.token_stats_fn(self.c, fp8=True) if fp8_control \
            else None
        T = self.max_seq
        worst = {"program": 0.0, "control": 0.0, "tokens": 0}
        for s in sample:
            S, served = s.prompt_len, np.asarray(s.ereq.tokens, np.int32)
            n = len(served)
            seq = np.zeros(T, np.int32)
            seq[:S] = s.req.prompt
            seq[S:S + n - 1] = served[:-1]
            chosen = np.zeros((1, T), np.int32)
            chosen[0, S - 1:S - 1 + n] = served
            if ctl is not None:
                _, carg, _ = ctl(self.weights, jnp.asarray(seq),
                                 jnp.asarray(chosen))
                chosen = np.concatenate([chosen,
                                         np.asarray(carg)[None]], 0)
            best, _, picked = ref(self.weights, jnp.asarray(seq),
                                  jnp.asarray(chosen))
            gap = (np.asarray(best)[None] - np.asarray(picked)
                   )[:, S - 1:S - 1 + n]
            worst["program"] = max(worst["program"], float(gap[0].max()))
            if ctl is not None:
                worst["control"] = max(worst["control"],
                                       float(gap[1].max()))
            worst["tokens"] += n
        return worst


def device_record(chips: int) -> Dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def compare(state: Dict, cell, seed: int, fp8_control: bool = False
            ) -> Dict[str, float]:
    """The widest served-token gaps of a seeded sample of the finished
    requests against the plain reference (see ``Engine.gaps``)."""
    eng, lim = state["engine"], cell.traffic["check"]
    sample = eng.sample(seed, lim["sample_tokens"], lim["max_requests"])
    g = eng.gaps(sample, fp8_control) if sample else \
        {"program": 0.0, "control": 0.0, "tokens": 0}
    g["requests"] = len(sample)
    print(f"[check] sampled_requests={len(sample)} "
          f"sampled_tokens={g['tokens']}", file=sys.stderr, flush=True)
    return g


def limits(g: Dict[str, float], state: Dict, cell,
           who: str = "program") -> Dict[str, tuple]:
    """(value, limit) of each number compared, with the served-token gaps
    of ``who``: the program, or its float8 control in the program's
    place."""
    return {"widest_logit_gap": (g[who],
                                 cell.traffic["check"]["widest_logit_gap"]),
            "unfinished_requests": (state["failed"], 0),
            "requests_unsampled": (0 if g["requests"] else 1, 0)}


def check(state: Dict, cell, seed: int) -> Dict[str, tuple]:
    """Compare a seeded sample of the finished requests with the plain
    reference; (value, limit) of each number compared."""
    return limits(compare(state, cell, seed), state, cell)
