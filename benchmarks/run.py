"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV.  ``--quick`` shrinks traces for CI;
``--smoke`` runs a <60 s strategy sweep over a tiny trace through the
declarative experiment runner — enough to catch control-plane
regressions without the full workloads (wired into scripts/check.sh).
``--jobs N`` fans event-loop variants out over N worker processes
(default: the CPU count on a CPU backend, one process on an
accelerator); ``--out PATH`` persists the smoke sweep's JSON result
artifact.
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import time


def smoke(jobs=None, out=None, engine="event") -> int:
    """Tiny end-to-end sweep: every strategy through the experiment
    runner (one declarative spec, parallel variants, fresh request
    copies per run).  Completion and drop counts derive from the
    returned Reports — the shared trace is never re-scanned.
    ``engine="vector"`` runs the same sweep on the bucketed engine."""
    from benchmarks.common import (BenchSpec, STRATEGIES, bench_experiment,
                                   csv_line)
    from repro.api.experiment import run_experiment
    spec = BenchSpec(days=0.1, scale=0.02, initial_instances=3,
                     spot_spare=8)
    exp = bench_experiment("smoke", spec, STRATEGIES, engine=engine)
    results = run_experiment(exp, jobs=jobs, out=out)
    print("name,value,derived", flush=True)
    n = results.results[0].n_requests
    csv_line("smoke.requests", n, "trace size")
    hours = {}
    for strat in STRATEGIES:
        res = results.get(strategy=strat)
        frac = res.completion
        hours[strat] = res.total_instance_hours
        csv_line(f"smoke.completion.{strat}", round(frac, 4), "fraction")
        csv_line(f"smoke.instance_hours.{strat}",
                 round(hours[strat], 1),
                 f"{res.wall_s:.1f}s wall, {res.engine}")
        if frac < 0.9:
            print(f"FAILED smoke: {strat} completed only {frac:.1%}",
                  file=sys.stderr)
            return 1
        if res.report["retry_dropped"] > 0.01 * n:
            print(f"FAILED smoke: {strat} dropped "
                  f"{res.report['retry_dropped']} requests on retry",
                  file=sys.stderr)
            return 1
    if hours["reactive"] > hours["siloed"] * 1.05:
        print("FAILED smoke: unified reactive used more instance-hours "
              "than siloed", file=sys.stderr)
        return 1
    print("# smoke ok", flush=True)
    return 0


def week(engine="vector", jobs=None, quick=False, out=None,
         bench_out=None, bench_check=None) -> int:
    """A simulated week, 7 strategies × 4 stress scenarios × 3 seeds —
    the sweep the vector engine exists for (docs/PERF.md).  One
    declarative experiment per scenario: the scenario's outage windows
    ride on the stacks, its popularity shifts on the workloads, and the
    seed axis becomes three workload variants, so the vector runner can
    batch every compatible (strategy, seed) replica into one vmapped
    scan.  ``--engine event`` runs the identical sweep on the event
    loop (hours, not minutes, at full scale).

    Batched runs carry per-boundary control-plane timings
    (``forecast_s`` / ``ilp_s`` / ``transfer_s`` / ``apply_s``, see
    docs/PERF.md "control plane at sweep scale"); they are aggregated
    into a ``control_week`` section, written into ``bench_out`` (a
    BENCH_sim.json) when given, and gated against a committed
    ``bench_check`` file (>2× ``boundary_s_mean`` regression fails)."""
    import dataclasses
    import json
    from benchmarks.common import BenchSpec, STRATEGIES, csv_line, stack_spec
    from benchmarks.fig_placement import scenario_inputs
    from repro.api.experiment import ExperimentSpec, run_experiment
    scenarios = ("baseline", "outage", "popshift", "combined")
    seeds = (0,) if quick else (0, 1, 2)
    scale = 0.01 if quick else 0.05
    days = 7.0
    spec = BenchSpec(days=days, scale=scale)
    print("name,value,derived", flush=True)
    t_start = time.time()
    agg = {"batches": 0, "boundaries": 0, "plans": 0, "forecast_s": 0.0,
           "ilp_s": 0.0, "transfer_s": 0.0, "apply_s": 0.0}
    counters = {}
    seen_batches = set()
    for scen in scenarios:
        workloads, scen_spec = {}, None
        for seed in seeds:
            wl, scen_spec = scenario_inputs(scen, days, scale, seed)
            workloads[f"s{seed}"] = wl
        strat_axis = {
            s: dataclasses.replace(stack_spec(spec, s), scenario=scen_spec)
            for s in STRATEGIES}
        exp = ExperimentSpec(name=f"week-{scen}", strategies=strat_axis,
                             workloads=workloads, engine=engine)
        results = run_experiment(
            exp, jobs=jobs, out=f"{out}.{scen}.json" if out else None)
        for r in results.results:
            csv_line(f"week.{scen}.{r.strategy}.{r.workload}.completion",
                     round(r.completion, 4),
                     f"{round(r.total_instance_hours, 1)} inst-h, "
                     f"{r.wall_s:.1f}s wall, {r.engine}")
            if r.completion < 0.9:
                print(f"FAILED week: {scen}/{r.strategy}/{r.workload} "
                      f"completed only {r.completion:.1%}",
                      file=sys.stderr)
                return 1
            ctl = (r.extras or {}).get("control")
            bid = (scen, ctl.get("batch")) if ctl else None
            if ctl and bid not in seen_batches:  # one entry per batch
                seen_batches.add(bid)
                agg["batches"] += 1
                for k in ("boundaries", "plans"):
                    agg[k] += int(ctl.get(k, 0))
                for k in ("forecast_s", "ilp_s", "transfer_s", "apply_s"):
                    agg[k] += float(ctl.get(k, 0.0))
                for k, v in ctl.items():
                    if k.startswith(("fleet_", "ilp_cache_",
                                     "fit_cache_", "seg_cache_")):
                        counters[k] = counters.get(k, 0) + v
    wall = time.time() - t_start
    csv_line("week.total_wall_s", round(wall, 1),
             f"{len(scenarios)}x{len(STRATEGIES)}x{len(seeds)} runs, "
             f"engine={engine}")
    control_week = None
    if agg["boundaries"]:
        control_s = (agg["forecast_s"] + agg["ilp_s"]
                     + agg["transfer_s"] + agg["apply_s"])
        control_week = {
            **{k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in agg.items()},
            **counters,
            "control_s_total": round(control_s, 3),
            "boundary_s_mean": round(control_s / agg["boundaries"], 5),
            "wall_s": round(wall, 1), "engine": engine,
            "quick": bool(quick), "seeds": len(seeds)}
        csv_line("week.control.boundary_s_mean",
                 control_week["boundary_s_mean"],
                 f"{agg['boundaries']} boundaries, "
                 f"{agg['plans']} plans, {agg['batches']} batches")
        csv_line("week.control.total_s", control_week["control_s_total"],
                 f"forecast {agg['forecast_s']:.1f}s + ilp "
                 f"{agg['ilp_s']:.1f}s + transfer "
                 f"{agg['transfer_s']:.1f}s + apply {agg['apply_s']:.1f}s")
    if bench_out and control_week:
        data = {}
        if os.path.exists(bench_out):
            with open(bench_out) as f:
                data = json.load(f)
        data["control_week"] = control_week
        with open(bench_out, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# control_week written to {bench_out}", flush=True)
    if bench_check and control_week:
        with open(bench_check) as f:
            committed = json.load(f).get("control_week", {})
        ref = committed.get("boundary_s_mean")
        if ref and control_week["boundary_s_mean"] > 2.0 * ref:
            print(f"FAILED week: control boundary_s_mean "
                  f"{control_week['boundary_s_mean']}s is >2x the "
                  f"committed {ref}s ({bench_check})", file=sys.stderr)
            return 1
        if ref:
            print(f"# control probe ok: boundary_s_mean "
                  f"{control_week['boundary_s_mean']}s vs committed "
                  f"{ref}s (gate 2x)", flush=True)
    return 0


def _call_run(mod, quick: bool, jobs):
    """Pass --jobs through to benchmarks whose run() takes it (the
    experiment-ported ones); legacy signatures get quick only."""
    if "jobs" in inspect.signature(mod.run).parameters:
        return mod.run(quick=quick, jobs=jobs)
    return mod.run(quick=quick)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny <60s strategy sweep for CI")
    ap.add_argument("--engine", default="event",
                    choices=("event", "vector"),
                    help="simulation engine for --smoke/--week sweeps")
    ap.add_argument("--week", action="store_true",
                    help="7-strategy x 4-scenario x 3-seed simulated "
                         "week (minutes on --engine vector)")
    ap.add_argument("--jobs", type=int, default=None, metavar="N",
                    help="worker processes for event-loop sweeps "
                         "(default: CPU count; always 1 on an "
                         "accelerator)")
    ap.add_argument("--out", default=None, metavar="RESULTS.json",
                    help="write the smoke sweep's result artifact here")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="run the placement study on one stress "
                         "scenario (outage | popshift | combined)")
    ap.add_argument("--bench-out", default=None, metavar="BENCH_sim.json",
                    help="also run the simulator perf benchmark "
                         "(benchmarks.perf_sim) and write its JSON here; "
                         "with --week, write the control_week section")
    ap.add_argument("--bench-check", default=None, metavar="BENCH_sim.json",
                    help="with --week: fail if control_week."
                         "boundary_s_mean regresses >2x vs this "
                         "committed file")
    args = ap.parse_args(argv)
    jobs = args.jobs
    if args.week:
        return week(engine=args.engine, jobs=jobs, quick=args.quick,
                    out=args.out, bench_out=args.bench_out,
                    bench_check=args.bench_check)
    if args.smoke:
        rc = smoke(jobs=jobs, out=args.out, engine=args.engine)
        if rc == 0 and args.bench_out:
            from benchmarks import perf_sim
            perf_sim.bench(repeats=1, out=args.bench_out)
        return rc
    if args.scenario:
        from benchmarks import fig_placement
        if args.scenario not in fig_placement.SCENARIOS:
            print(f"unknown scenario {args.scenario!r}; known: "
                  f"{', '.join(fig_placement.SCENARIOS)}",
                  file=sys.stderr)
            return 2
        print("name,value,derived", flush=True)
        fig_placement.run(quick=args.quick,
                          scenarios=(args.scenario,), jobs=jobs)
        return 0

    from benchmarks import (fig8_unified_vs_siloed, fig11_instance_hours,
                            fig14_scalability_moe, fig15_schedulers,
                            fig16_bursts_week, fig_ablation,
                            fig_placement, kernel_bench, perf_sim,
                            tab3_workload_characterization,
                            tab_ilp_solver)
    benches = {
        "tab3_workload_characterization": tab3_workload_characterization,
        "tab_ilp_solver": tab_ilp_solver,
        "kernel_bench": kernel_bench,
        "fig8_unified_vs_siloed": fig8_unified_vs_siloed,
        "fig11_instance_hours": fig11_instance_hours,
        "fig14_scalability_moe": fig14_scalability_moe,
        "fig15_schedulers": fig15_schedulers,
        "fig16_bursts_week": fig16_bursts_week,
        "fig_ablation": fig_ablation,
        "fig_placement": fig_placement,
        "perf_sim": perf_sim,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,value,derived", flush=True)
    failures = []
    for name, mod in benches.items():
        if only and name not in only:
            continue
        if name == "perf_sim" and args.bench_out and not only:
            continue  # --bench-out runs it below with the JSON output
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            _call_run(mod, args.quick, jobs)
        except Exception as e:
            failures.append((name, e))
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    if failures:
        for n, e in failures:
            print(f"FAILED {n}: {e}", file=sys.stderr)
        return 1
    if args.bench_out:
        from benchmarks import perf_sim as _ps
        _ps.bench(repeats=1 if args.quick else 3, out=args.bench_out)
    print("# all benchmarks complete", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
