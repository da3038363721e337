"""Simulator performance benchmark — the repo's tracked perf trajectory.

Times trace generation (columnar + object materialization) and
simulation (wall-clock, events/sec, peak RSS) on pinned reference
configs and writes ``BENCH_sim.json``.  Future PRs re-run this to catch
hot-path regressions; see docs/PERF.md for how to read the output.

Pinned configs
--------------
- ``reference``       1-day, 3-region, 4-model trace at ``scale=0.05``
                      through the fig8 unified stack (reactive scaler +
                      NIW queue manager) — the config named in ISSUE 2.
- ``reference_fleet`` same trace, but with the fleet floored at
                      ``FLEET_FLOOR`` instances per (model, region), the
                      paper's production deployment size (Fig. 11 shows
                      hundreds of instances per model-region).  This is
                      the config where the pre-refactor O(fleet)
                      per-arrival scans dominate — the super-linear term
                      this PR removed.
- ``full_scale``      (``--full``) the paper's native-scale evaluation:
                      1-day, 3-region, 4-model at ``scale=1.0``
                      (~4.9M requests).

Usage::

    python -m benchmarks.perf_sim --smoke            # <30s CI probe
    python -m benchmarks.perf_sim --out BENCH_sim.json
    python -m benchmarks.perf_sim --full --out BENCH_sim.json
    python -m benchmarks.perf_sim --baseline head.json --out BENCH_sim.json

``--baseline`` embeds a previously measured baseline (e.g. the pre-PR
HEAD, measured on the same machine) and records end-to-end speedups.
"""
from __future__ import annotations

import argparse
import json
import platform
import resource
import sys
import time


FLEET_FLOOR = 150          # instances per (model, region), paper-scale fleet
REFERENCE_SCALE = 0.05
REFERENCE_DAYS = 1.0


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _stack_spec(fleet_floor=None):
    from benchmarks.common import BenchSpec
    from repro.api import PolicySpec, StackSpec
    from repro.sim.workload import REGIONS
    spec = BenchSpec(days=REFERENCE_DAYS, scale=REFERENCE_SCALE)
    if fleet_floor is None:
        scaler = PolicySpec("reactive")
        initial, spare = spec.initial_instances, spec.spot_spare
    else:
        scaler = PolicySpec("reactive", {"min_instances": fleet_floor})
        initial, spare = fleet_floor, 4 * fleet_floor
    return StackSpec(models=tuple(spec.models), regions=tuple(REGIONS),
                     scaler=scaler, initial_instances=initial,
                     spot_spare=spare)


def time_generation(days: float, scale: float, seed: int = 0) -> dict:
    """Columnar generation + Request materialization timings."""
    from repro.sim.workload import WorkloadSpec, generate_trace
    t0 = time.perf_counter()
    trace = generate_trace(WorkloadSpec(days=days, scale=scale, seed=seed))
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    reqs = trace.to_requests()
    t_mat = time.perf_counter() - t0
    n = len(reqs)
    return {
        "n_requests": n,
        "generate_columnar_s": round(t_gen, 3),
        "materialize_s": round(t_mat, 3),
        "requests_per_s_columnar": int(n / max(t_gen, 1e-9)),
        "requests_per_s_end_to_end": int(n / max(t_gen + t_mat, 1e-9)),
        "_requests": reqs,   # stripped before serialization
        "_trace": trace,     # columnar view, fed to the vector engine
    }


def time_control(fit_steps: int = 150, history_days: float = 2.0) -> dict:
    """Control-plane probe: one hourly plan (batched forecast + ILP) on
    the 3-region × 4-model stack over two days of 60 s TPS history.

    Times the batched engine cold (includes the JIT trace), warm
    (steady-state hourly cost, parameters warm-started) and the serial
    per-series reference, plus the myopic and routing-aware ILPs —
    recorded in BENCH_sim.json so forecast-engine regressions are
    tracked like simulator throughput.
    """
    import numpy as np
    from repro.api import PolicySpec, resolve
    from repro.api.stack import BuildContext
    from repro.sim.perfmodel import PROFILES
    from repro.sim.workload import PAPER_MODELS, REGIONS

    ctx = BuildContext(tuple(PAPER_MODELS), tuple(REGIONS),
                       {m: PROFILES[m] for m in PAPER_MODELS})
    n_buckets = int(history_days * 1440)
    rng = np.random.default_rng(0)
    t = np.arange(n_buckets, dtype=float)
    history = {}
    for i, m in enumerate(PAPER_MODELS):
        for j, r in enumerate(REGIONS):
            history[(m, r)] = (1000 + 400 * np.sin(
                2 * np.pi * t / 1440 - i - j)
                + rng.normal(0, 30, t.shape)).clip(min=0)
    instances = {k: 5 for k in history}
    niw = {k: 50.0 for k in history}

    def plan_once(use_routing, batched):
        ctl = resolve("planner", PolicySpec(
            "sageserve", {"fit_steps": fit_steps, "batched": batched,
                          "use_routing": use_routing}), ctx)
        t0 = time.perf_counter()
        ctl.plan(3600.0, instances, history, niw)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        ctl.plan(7200.0, instances, history, niw)
        warm = time.perf_counter() - t0
        ilp = ctl.solve_history[-1]["ilp_s"]
        return cold, warm, ilp

    cold_b, warm_b, ilp_myopic = plan_once(False, batched=True)
    cold_s, warm_s, _ = plan_once(False, batched=False)
    _, _, ilp_routing = plan_once(True, batched=True)
    return {
        "stack": f"{len(REGIONS)}regions_x_{len(PAPER_MODELS)}models",
        "history_buckets": n_buckets,
        "fit_steps": fit_steps,
        "plan_batched_cold_s": round(cold_b, 3),
        "plan_batched_warm_s": round(warm_b, 3),
        "plan_serial_s": round(warm_s, 3),
        "forecast_speedup_vs_serial": round(warm_s / max(warm_b, 1e-9), 2),
        "ilp_s": round(ilp_myopic, 4),
        "ilp_routing_s": round(ilp_routing, 4),
    }


def time_control_sweep(days: float = 0.25, scale: float = 0.01) -> dict:
    """Sweep-scale control probe: a short multi-strategy batched
    vector sweep through the fleet-forecast + amortized-ILP boundary
    path.  Reports the per-boundary control cost and the dedupe
    counters — the CI-sized twin of the ``control_week`` section the
    week benchmark records into BENCH_sim.json (docs/PERF.md)."""
    from benchmarks.common import BenchSpec, stack_spec
    from repro.api.stack import build_stack
    from repro.control.amortize import clear_solve_cache
    from repro.control.forecast import clear_fit_cache
    from repro.sim.vector import VectorBatch
    from repro.sim.workload import WorkloadSpec, generate_trace

    clear_fit_cache()
    clear_solve_cache()
    strats = ["lt-u", "lt-ua", "lt-ua+plan"]
    spec = BenchSpec(days=days, scale=scale, initial_instances=3,
                     spot_spare=8)
    tr = generate_trace(WorkloadSpec(days=days, scale=scale, seed=0))
    stacks = [build_stack(stack_spec(spec, s)) for s in strats]
    vb = VectorBatch(tr, [st.sim_config() for st in stacks], strats,
                     models=list(stacks[0].spec.models),
                     regions=list(stacks[0].spec.regions),
                     profiles=stacks[0].profiles, batched=True)
    t0 = time.perf_counter()
    vb.run()
    wall = time.perf_counter() - t0
    cs = dict(vb.control_stats)
    control_s = (cs["forecast_s"] + cs["ilp_s"] + cs["transfer_s"]
                 + cs["apply_s"])
    boundaries = max(cs["boundaries"], 1)
    solves = cs["ilp_cache_hits"] + cs["ilp_cache_misses"]
    return {
        "replicas": len(strats),
        "wall_s": round(wall, 3),
        "boundaries": cs["boundaries"],
        "plans": cs["plans"],
        "control_s_total": round(control_s, 3),
        "boundary_s_mean": round(control_s / boundaries, 5),
        "forecast_s": round(cs["forecast_s"], 3),
        "ilp_s": round(cs["ilp_s"], 3),
        "fleet_batches": cs.get("fleet_batches", 0),
        "fleet_fits": cs.get("fleet_fits", 0),
        "fleet_dedup_hits": (cs.get("fleet_dedup_hits", 0)
                             + cs.get("fleet_cache_hits", 0)),
        "ilp_cache_hit_rate": round(
            cs["ilp_cache_hits"] / solves, 3) if solves else 0.0,
    }


def time_simulation(reqs, stack_spec, name: str, repeats: int = 3) -> dict:
    """Simulation wall-clock + events/sec on a built stack; records the
    best *and* the mean over repeats (the mean is what a sweep pays,
    the best is the noise-free trajectory number)."""
    from repro.api import build_stack
    from repro.sim.simulator import Simulation
    walls, events, report = [], 0, None
    for _ in range(max(repeats, 1)):
        stack = build_stack(stack_spec)
        sim = Simulation(reqs, stack.sim_config(),
                         models=list(stack_spec.models),
                         regions=list(stack_spec.regions),
                         profiles=stack.profiles, name=name)
        t0 = time.perf_counter()
        report = sim.run()
        dt = time.perf_counter() - t0
        if not walls or dt < min(walls):
            events = sim.events_processed
        walls.append(dt)
    best = min(walls)
    done = sum(report.completed.values())
    return {
        "engine": "event",
        "n_requests": len(reqs),
        "wall_s_best": round(best, 3),
        "wall_s_mean": round(sum(walls) / len(walls), 3),
        "repeats": repeats,
        "events_processed": events,
        "events_per_s": int(events / max(best, 1e-9)),
        "requests_per_s": int(len(reqs) / max(best, 1e-9)),
        "completed_fraction": round(done / max(len(reqs), 1), 5),
        "peak_rss_mb": round(_rss_mb(), 1),
    }


def time_vector_simulation(trace, stack_spec, name: str,
                           repeats: int = 3, batch: int = 8) -> dict:
    """Vector-engine timings on the same stack/workload.

    Measures the single-replica run cold (first call in this process:
    trace + compile, cheaper when ``.jax_cache`` is warm) and warm
    (best/mean of the remaining repeats), plus a batch of ``batch``
    identical replicas vmapped through one scan — ``wall_s_per_replica``
    is the number the ≥20× contract in docs/PERF.md is written against,
    because sweeps always run batched.
    """
    from repro.jaxconfig import configure_jax
    cache = configure_jax()
    from repro.api import build_stack
    from repro.sim.vector import VectorBatch
    walls, report = [], None
    for _ in range(max(repeats, 1) + 1):     # +1: first run is cold
        stack = build_stack(stack_spec)
        t0 = time.perf_counter()
        report = stack.simulate_vector(trace, name=name)
        walls.append(time.perf_counter() - t0)
    cold, warm = walls[0], walls[1:]
    batch_walls = []
    for _ in range(2):
        stacks = [build_stack(stack_spec) for _ in range(batch)]
        t0 = time.perf_counter()
        vb = VectorBatch(trace, [s.sim_config() for s in stacks],
                         [f"{name}{i}" for i in range(batch)],
                         models=list(stack_spec.models),
                         regions=list(stack_spec.regions),
                         profiles=stacks[0].profiles)
        vb.run()
        batch_walls.append(time.perf_counter() - t0)
    done = sum(report.completed.values())
    n = len(trace)
    return {
        "engine": "vector",
        "n_requests": n,
        "repeats": repeats,
        "wall_s_cold": round(cold, 3),
        "wall_s_best": round(min(warm), 3),
        "wall_s_mean": round(sum(warm) / len(warm), 3),
        "batch": batch,
        "batch_wall_s_best": round(min(batch_walls), 3),
        "wall_s_per_replica": round(min(batch_walls) / batch, 4),
        "completed_fraction": round(done / max(n, 1), 5),
        "compilation_cache_dir": cache,
        "peak_rss_mb": round(_rss_mb(), 1),
    }


def bench(full: bool = False, repeats: int = 3, out: str = None,
          baseline_path: str = None, fleet_floor: int = FLEET_FLOOR) -> dict:
    from benchmarks.common import csv_line
    result = {
        "machine": {"python": platform.python_version(),
                    "platform": platform.platform(),
                    "processor": platform.processor() or "unknown"},
        "config": {"days": REFERENCE_DAYS, "scale": REFERENCE_SCALE,
                   "fleet_floor": fleet_floor, "repeats": repeats},
    }

    gen = time_generation(REFERENCE_DAYS, REFERENCE_SCALE)
    reqs = gen.pop("_requests")
    trace = gen.pop("_trace")
    result["trace_gen"] = gen
    csv_line("perf.gen.requests_per_s", gen["requests_per_s_end_to_end"],
             f"{gen['n_requests']} requests")

    for name, floor in (("reference", None), ("reference_fleet",
                                              fleet_floor)):
        r = time_simulation(reqs, _stack_spec(floor), name, repeats)
        result[name] = r
        csv_line(f"perf.{name}.events_per_s", r["events_per_s"],
                 f"{r['wall_s_best']}s best of {repeats}")

    vec = time_vector_simulation(trace, _stack_spec(fleet_floor),
                                 "reference_fleet", repeats)
    ev = result["reference_fleet"]
    per_rep = max(vec["wall_s_per_replica"], 1e-9)
    vec["events_per_s"] = int(ev["events_processed"] / per_rep)
    vec["events_per_s_single"] = int(
        ev["events_processed"] / max(vec["wall_s_best"], 1e-9))
    vec["speedup_vs_event_per_replica"] = round(
        ev["wall_s_best"] / per_rep, 1)
    vec["speedup_vs_event_single"] = round(
        ev["wall_s_best"] / max(vec["wall_s_best"], 1e-9), 1)
    result["vector"] = vec
    csv_line("perf.vector.events_per_s", vec["events_per_s"],
             f"{vec['speedup_vs_event_per_replica']}x event loop "
             f"per replica (batch of {vec['batch']})")

    ctl = time_control()
    result["control"] = ctl
    csv_line("perf.control.plan_batched_warm_s",
             ctl["plan_batched_warm_s"],
             f"{ctl['forecast_speedup_vs_serial']}x vs serial")

    if full:
        gen_f = time_generation(REFERENCE_DAYS, 1.0)
        reqs_f = gen_f.pop("_requests")
        r = time_simulation(reqs_f, _stack_spec(None), "full_scale",
                            repeats=1)
        r["generate_columnar_s"] = gen_f["generate_columnar_s"]
        r["materialize_s"] = gen_f["materialize_s"]
        result["full_scale"] = r
        csv_line("perf.full_scale.events_per_s", r["events_per_s"],
                 f"{r['n_requests']} requests, {r['wall_s_best']}s")
        del reqs_f

    if baseline_path:
        with open(baseline_path) as f:
            base = json.load(f)
        result["baseline"] = base
        speed = {}
        for name in ("reference", "reference_fleet"):
            b = base.get(name, {})
            if "end_to_end_s" in b and name in result:
                new_e2e = (gen["generate_columnar_s"]
                           + gen["materialize_s"]
                           + result[name]["wall_s_best"])
                speed[name] = {
                    "baseline_end_to_end_s": b["end_to_end_s"],
                    "new_end_to_end_s": round(new_e2e, 3),
                    "speedup": round(b["end_to_end_s"] / new_e2e, 2),
                }
        result["speedup_vs_baseline"] = speed
        for name, s in speed.items():
            csv_line(f"perf.speedup.{name}", s["speedup"],
                     f"{s['baseline_end_to_end_s']}s -> "
                     f"{s['new_end_to_end_s']}s")

    if out:
        serializable = {k: v for k, v in result.items()}
        with open(out, "w") as f:
            json.dump(serializable, f, indent=1, sort_keys=True)
        print(f"# wrote {out}", flush=True)
    return result


def smoke() -> int:
    """<30 s probe for scripts/check.sh: fails on crash or a stalled
    simulator, prints events/sec."""
    from benchmarks.common import csv_line
    print("name,value,derived", flush=True)
    gen = time_generation(days=0.1, scale=0.02, seed=0)
    reqs = gen.pop("_requests")
    csv_line("perf_smoke.gen.requests_per_s",
             gen["requests_per_s_end_to_end"], f"{gen['n_requests']} reqs")
    r = time_simulation(reqs, _stack_spec(None), "perf_smoke", repeats=1)
    csv_line("perf_smoke.sim.events_per_s", r["events_per_s"],
             f"{r['wall_s_best']}s wall")
    if r["completed_fraction"] < 0.9:
        print(f"FAILED perf smoke: only {r['completed_fraction']:.1%} "
              f"completed", file=sys.stderr)
        return 1
    if r["events_per_s"] < 1000:
        print(f"FAILED perf smoke: {r['events_per_s']} events/s is "
              f"implausibly slow", file=sys.stderr)
        return 1
    print("# perf smoke ok", flush=True)
    return 0


def control_probe(fit_steps: int = 100) -> int:
    """CI probe for scripts/check.sh: one hourly plan on the paper
    stack; fails if the batched engine lost to the serial path or the
    ILP stalled."""
    from benchmarks.common import csv_line
    print("name,value,derived", flush=True)
    ctl = time_control(fit_steps=fit_steps)
    for k in ("plan_batched_cold_s", "plan_batched_warm_s",
              "plan_serial_s", "ilp_s", "ilp_routing_s"):
        csv_line(f"control.{k}", ctl[k])
    csv_line("control.forecast_speedup_vs_serial",
             ctl["forecast_speedup_vs_serial"])
    if ctl["forecast_speedup_vs_serial"] < 1.0:
        print("FAILED control probe: batched hourly plan slower than "
              "serial", file=sys.stderr)
        return 1
    if ctl["ilp_routing_s"] > 30.0:
        print("FAILED control probe: routing ILP implausibly slow",
              file=sys.stderr)
        return 1
    sweep = time_control_sweep()
    for k in ("boundary_s_mean", "control_s_total", "forecast_s",
              "ilp_s", "fleet_batches", "fleet_dedup_hits",
              "ilp_cache_hit_rate"):
        csv_line(f"control.sweep.{k}", sweep[k])
    if sweep["boundaries"] < 1 or sweep["plans"] < sweep["boundaries"]:
        print("FAILED control probe: batched sweep recorded no hourly "
              "boundaries", file=sys.stderr)
        return 1
    if sweep["fleet_batches"] > sweep["boundaries"]:
        print("FAILED control probe: fleet forecast dispatched more "
              "than one vmap batch per boundary", file=sys.stderr)
        return 1
    if sweep["fleet_dedup_hits"] + sweep["ilp_cache_hit_rate"] == 0:
        print("FAILED control probe: replicas sharing a trace never "
              "hit the fit/solve caches", file=sys.stderr)
        return 1
    print("# control probe ok", flush=True)
    return 0


def run(quick: bool = False):
    """benchmarks.run entry point."""
    return bench(full=False, repeats=1 if quick else 3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="run only the control-plane probe (one hourly "
                         "plan: batched forecast + ILP)")
    ap.add_argument("--full", action="store_true",
                    help="include the scale=1.0 (~4.9M request) run")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None, help="write BENCH_sim.json here")
    ap.add_argument("--baseline", default=None,
                    help="JSON with baseline timings to embed + compare")
    ap.add_argument("--fleet-floor", type=int, default=FLEET_FLOOR)
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    if args.control:
        return control_probe()
    print("name,value,derived", flush=True)
    bench(full=args.full, repeats=args.repeats, out=args.out,
          baseline_path=args.baseline, fleet_floor=args.fleet_floor)
    return 0


if __name__ == "__main__":
    sys.exit(main())
