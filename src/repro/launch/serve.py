"""Serving launcher: ``python -m repro.launch.serve [--arch <id>] [--full]``.

Drives the continuous-batching :class:`ServingEngine` with a mixed
IW-F/IW-N request stream and a SageServe scheduler (default DPA),
printing TTFT/E2E step counts, and each request's queue wait and TTFT in
milliseconds from its host timestamps — the single-instance slice of the full
SageServe stack (the cluster-level behaviour lives in the simulator;
see examples/serve_cluster.py).

Without ``--full`` the architecture is the reduced (smoke) variant, which
runs anywhere.  With ``--full`` it keeps its published widths and depth
on one device: StarCoder2-7B's bf16 weights (14.8 GB) plus a 4 x 2048
decode cache fit one 16 GB TPU v5e.  Weights are random, drawn from
``--seed``; prompts are random tokens of ``PROMPT_LENS`` lengths.
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Tuple

import jax
import numpy as np

from repro.configs import get_arch, reduce_for_smoke
from repro.configs.base import ModelConfig
from repro.dist.sharding import unbox
from repro.models import model as model_mod
from repro.serving.engine import ServeRequest, ServingEngine

#: decode-cache length per slot
MAX_SEQ = 2048
#: prompt lengths the requests alternate over; each compiles the prefill
#: once
PROMPT_LENS = (256, 1024)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth (one device must "
                         "hold the weights)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--scheduler", default="dpa",
                    choices=["fcfs", "edf", "pf", "dpa"])
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def make_requests(cfg: ModelConfig, n: int, max_new: int,
                  seed: int) -> List[ServeRequest]:
    """Every third request is IW-F (tight TTFT deadline), the rest IW-N;
    prompt lengths alternate over ``PROMPT_LENS``."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        tier = "IW-F" if i % 3 == 0 else "IW-N"
        S = PROMPT_LENS[i % len(PROMPT_LENS)]
        reqs.append(ServeRequest(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, S).astype(np.int32),
            max_new_tokens=max_new, tier=tier, arrival=float(i),
            ttft_deadline=float(i) + (2 if tier == "IW-F" else 20)))
    return reqs


def build(args: argparse.Namespace
          ) -> Tuple[ModelConfig, dict, ServingEngine, List[ServeRequest]]:
    """(config, params, engine, requests) for the parsed arguments.

    Parameters are initialized under ``jit``, so they are created on
    the device and the layer stacks are drawn one layer at a time."""
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduce_for_smoke(cfg)
    params = jax.jit(lambda k: unbox(model_mod.init(cfg, k)))(
        jax.random.PRNGKey(args.seed))
    if max(PROMPT_LENS) + args.max_new > MAX_SEQ:
        raise ValueError(f"{max(PROMPT_LENS)} prompt + {args.max_new} new "
                         f"tokens exceed the {MAX_SEQ}-token cache")
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=MAX_SEQ, scheduler=args.scheduler)
    reqs = make_requests(cfg, args.requests, args.max_new, args.seed)
    return cfg, params, eng, reqs


def serve(eng: ServingEngine, reqs: List[ServeRequest]) -> None:
    """Submit every request and run the engine until all are done."""
    for r in reqs:
        eng.submit(r)
    eng.run()
    undone = [r.rid for r in reqs if r.done_step is None]
    if undone:
        raise RuntimeError(f"requests {undone} did not complete")


def main(argv=None):
    args = parse_args(argv)
    cfg, _, eng, reqs = build(args)
    serve(eng, reqs)
    for r in reqs:
        print(f"req {r.rid} [{r.tier}] prompt={r.prompt_tokens} "
              f"ttft_step={r.ttft_step} done_step={r.done_step} "
              f"tokens={len(r.tokens)} "
              f"queue_wait_ms={(r.admit_ns - r.submit_ns) / 1e6:.3f} "
              f"ttft_ms={(r.token_ns[0] - r.submit_ns) / 1e6:.3f}")
    print(f"served {len(reqs)} requests on {cfg.name} "
          f"({cfg.num_layers} layers) in {eng.step_count} engine steps "
          f"with {args.scheduler.upper()} scheduling")
    return 0


if __name__ == "__main__":
    sys.exit(main())
