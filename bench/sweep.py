"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.2,1.5,1.8 [--out sweep.jsonl]

One process: the weights are made and the shapes warmed once, then each
rate runs the cell's open-loop window with the engine drained between
rates. Per rate it prints the TTFT and inter-token-gap percentiles, the
requests still waiting when sending stopped and how late the last ones
finished. The knee is the highest rate whose TTFT p90 stays under the
mix's limit with no growing backlog; the cell then runs at a fixed
fraction of it (see PERF.md).
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--traffic-seeds", default="",
                    help="traffic seeds to run at each rate (default: --seed)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import run as bench_run
    from benchkit.spec import load_cell, load_module

    cell = load_cell(ROOT, args.workload)
    bench_run.configure_cache(ROOT)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2

    from benchkit.record import Run, percentile
    from benchkit.tracing import Tracer

    gen = load_module(BENCH / "traffic" / "generator.py")
    driver = cell.driver()
    state = driver.prepare(cell, args.seed, args.seconds, gen)
    eng = state["engine"]
    vocab = cell.config["config"]["vocab_size"]
    out = open(args.out, "a") if args.out else None
    tseeds = [int(x) for x in args.traffic_seeds.split(",") if x] \
        or [args.seed]
    for rate, tseed in [(float(r), t) for r in args.rates.split(",")
                        for t in tseeds]:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        eng.sent.clear()
        eng.steps.clear()
        state["requests"] = gen.open_loop(mix, args.seconds, tseed, vocab)
        run = Run(config=cell.config["config"], peaks={}, setup_s=0.0,
                  window_s=args.seconds)
        driver.window(state, cell, args.seconds,
                      Tracer(False, 0, 0, ""), run)
        ttft, gaps = run.ttfts(), run.token_gaps()
        row = {"rate_per_s": rate, "traffic_seed": tseed,
               "sent": len(run.sent),
               "failed": state["failed"], "window_s": run.window_s,
               "drain_s": run.window_s - args.seconds,
               "tokens": int(sum(st.tokens for st in run.steps))}
        for q in (50, 90, 95):
            row[f"ttft_p{q}_ms"] = percentile(ttft, q) * 1e3
        for q in (50, 90, 95, 99):
            row[f"itl_p{q}_ms"] = percentile(gaps, q) * 1e3
        row["long_gap_share"] = float(np.mean(gaps > 0.05)) if len(gaps) else 0
        # TTFT of the last fifth of requests against the first: a backlog
        # that grows through the window shows as a ratio well above 1
        n5 = max(1, len(ttft) // 5)
        row["ttft_last_over_first"] = float(np.mean(ttft[-n5:])
                                            / np.mean(ttft[:n5]))
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps({"workload": args.workload, **row}) + "\n")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
