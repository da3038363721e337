"""The one traffic generator: reads a mix's parameters, returns requests.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps, drawn at fixed quantiles of the mix's distributions; the
seed draws their order, uniformly at random and independently for each,
and the prompt tokens. So two seeds ask for the same work, in another
order. A uniform order of a sample is how an i.i.d. sample comes, so the
gaps cluster as a Poisson process's do: short gaps run together and long
requests can pile up.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    send_s: float            # scheduled send time, from the window's start
    prompt: np.ndarray       # (S,) int32
    max_new: int


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(median: float, sigma: float, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in quantiles(n)])
    return median * np.exp(sigma * z)


def round_up(x: np.ndarray, buckets: Sequence[int]) -> np.ndarray:
    """Each value to the smallest bucket at or above it, the largest
    bucket for values beyond it."""
    b = np.sort(np.asarray(buckets))
    return b[np.minimum(np.searchsorted(b, x, side="left"), len(b) - 1)]


def sizes(mix: Dict, n: int) -> tuple:
    """(prompt lengths, output lengths) of n requests, in quantile order."""
    p, o = mix["prompt"], mix["output"]
    prompts = round_up(lognormal(p["median"], p["sigma"], n), p["buckets"])
    outs = np.clip(np.rint(lognormal(o["median"], o["sigma"], n)),
                   o["min"], o["max"]).astype(int)
    return prompts.astype(int), outs


def requests(mix: Dict, n: int, seed: int, vocab: int,
             send_s: Sequence[float] = (), stream: int = 0,
             first_rid: int = 0) -> List[Request]:
    """n requests of the mix, sizes and order drawn from ``seed``; each
    ``stream`` of one seed is another order of the same sizes."""
    rng = np.random.default_rng([seed, 1, stream])
    prompts, outs = sizes(mix, n)
    pi, oi = rng.permutation(n), rng.permutation(n)
    sends = list(send_s) or [0.0] * n
    return [Request(rid=first_rid + i, send_s=float(sends[i]),
                    prompt=rng.integers(0, vocab, int(prompts[pi[i]]),
                                        dtype=np.int32),
                    max_new=int(outs[oi[i]]))
            for i in range(n)]


def open_loop(mix: Dict, seconds: float, seed: int, vocab: int
              ) -> List[Request]:
    """Poisson arrivals at ``rate_per_s`` over ``seconds``: the gaps are
    the exponential distribution's quantiles, in an order drawn from the
    seed, scaled to span the window."""
    rate = mix["arrivals"]["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-quantiles(n)) / rate
    rng = np.random.default_rng([seed, 2])
    gaps = gaps[rng.permutation(n)]
    gaps *= seconds / gaps.sum()
    sends = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return requests(mix, n, seed, vocab, sends)


def warmup(mix: Dict, vocab: int, new_tokens: int = 3) -> List[Request]:
    """One request per prompt bucket, so every shape the window uses is
    compiled before it opens."""
    rng = np.random.default_rng(0)
    return [Request(rid=-1 - i, send_s=0.0,
                    prompt=rng.integers(0, vocab, int(S), dtype=np.int32),
                    max_new=new_tokens)
            for i, S in enumerate(sorted(mix["prompt"]["buckets"]))]
