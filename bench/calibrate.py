"""Readings that set a serving cell's limit: the program and its control.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--out readings.jsonl]

One process, one seed after another: make the seed's weights, serve the
cell's own traffic for a short window (drained, so the mix's longest
requests finish), free the engine, then read on the same seeded sample of
finished requests

- ``program``: the widest gap by which a served token's reference logit
  lies below the reference's best, as a run's check reads it;
- ``control``: the same gap for the token the float8 control (the
  reference with float8 e4m3 matmul operands) puts first, position by
  position over the same prompts and served tokens.

Each is put through the comparison that decides a run's ``correct``
(``run.correct`` over ``serving.limits``), the control in the program's
place: ``correct`` has to read true for the program and false for the
control. The limit in the cell's traffic file lies between the largest
``program`` over a dozen seeds or more and the smallest ``control``
(see PERF.md).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import run as bench_run
    from benchkit.spec import load_cell, load_module

    cell = load_cell(ROOT, args.workload)
    bench_run.configure_cache(ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2

    from benchkit import serving
    from benchkit.record import Run
    from benchkit.tracing import Tracer

    gen = load_module(BENCH / "traffic" / "generator.py")
    driver = cell.driver()
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        state = driver.prepare(cell, seed, args.seconds, gen)
        run = Run(config=cell.config["config"], peaks={}, setup_s=0.0,
                  window_s=args.seconds)
        driver.window(state, cell, args.seconds, Tracer(False, 0, 0, ""),
                      run)
        state["engine"].free()
        g = serving.compare(state, cell, seed, fp8_control=True)
        row = {"workload": args.workload, "seed": seed, **g,
               "correct": bench_run.correct(serving.limits(g, state, cell)),
               "control_correct": bench_run.correct(
                   serving.limits(g, state, cell, "control")),
               "limit": cell.traffic["check"]["widest_logit_gap"],
               "sent": state["attempted"], "failed": state["failed"],
               "wall_s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
        del state
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
