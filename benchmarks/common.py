"""Shared benchmark scaffolding: calibrated strategy runs over the
synthetic production trace (see DESIGN.md §7 for the workload anchors).

Strategies are declarative: ``stack_spec`` maps a strategy name to a
``StackSpec`` and every run goes through ``repro.api.build_stack`` — the
same construction path as examples and tests.  Whole sweeps are
declarative too: ``bench_experiment`` lifts a ``BenchSpec`` plus a
strategy list into an ``repro.api.experiment.ExperimentSpec``, and the
fig/tab modules hand those to ``run_experiment`` (parallel across
variants, one trace generation per unique workload, fresh request
copies per run — no shared-mutable-trace resets anywhere).  Workload
subsampling: traffic is thinned by ``scale`` and the fleet's
instance-count knobs are scaled accordingly, preserving per-instance
dynamics (see sim/perfmodel.py).  All $-figures use the paper's
$98.32/h H100-cluster price.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

from repro.jaxconfig import configure_jax

configure_jax()

from repro.api import PolicySpec, StackSpec, build_stack          # noqa: E402
from repro.api.experiment import ExperimentSpec                   # noqa: E402
from repro.control.cost import DEFAULT_DOLLARS_PER_HOUR           # noqa: E402
from repro.sim.metrics import Report                              # noqa: E402
from repro.sim.perfmodel import PerfProfile                       # noqa: E402
from repro.sim.workload import (PAPER_MODELS, REGIONS,            # noqa: E402
                                WorkloadSpec, generate)

DOLLARS_PER_HOUR = DEFAULT_DOLLARS_PER_HOUR     # paper §7.2.1
THETA_HEADROOM = 0.7         # ILP capacity derating (keeps tail latency)

# "lt-ua+plan" is the fully co-optimized stack: LT-UA scaling plus the
# routing-aware ILP whose ω fractions drive a PlanAwareRouter.
STRATEGIES = ("siloed", "reactive", "lt-i", "lt-u", "lt-ua",
              "lt-ua+plan", "chiron")


@dataclasses.dataclass
class BenchSpec:
    days: float = 1.0
    scale: float = 0.15
    seed: int = 0
    initial_instances: int = 5
    spot_spare: int = 30
    scheduler: str = "fcfs"
    models: Sequence[str] = PAPER_MODELS
    burst_mult: float = 0.0
    burst_hours: Tuple[float, ...] = ()


def workload_spec(spec: BenchSpec) -> WorkloadSpec:
    """The declarative workload for one benchmark setting."""
    return WorkloadSpec(
        days=spec.days, scale=spec.scale, seed=spec.seed,
        models=tuple(spec.models), burst_mult=spec.burst_mult,
        burst_hours=spec.burst_hours)


def make_trace(spec: BenchSpec):
    return generate(workload_spec(spec))


def planner_spec(fit_steps: int = 150, routing: bool = False) -> PolicySpec:
    kw = {"min_instances": 2, "epsilon": 0.8, "fit_steps": fit_steps,
          "theta_headroom": THETA_HEADROOM}
    if routing:
        kw["use_routing"] = True
    return PolicySpec("sageserve", kw)


def stack_spec(spec: BenchSpec, strategy: str,
               scheduler: Optional[str] = None) -> StackSpec:
    """Declarative stack for one paper strategy."""
    common = dict(models=tuple(spec.models), regions=tuple(REGIONS),
                  scheduler=scheduler or spec.scheduler,
                  spot_spare=spec.spot_spare)
    if strategy == "siloed":
        return StackSpec(scaler="reactive", queue=None, siloed=True,
                         siloed_iw=max(spec.initial_instances - 1, 2),
                         siloed_niw=2,
                         initial_instances=spec.initial_instances, **common)
    if strategy == "chiron":
        return StackSpec(
            scaler=PolicySpec("chiron", {
                "theta": 0.6,
                "init_interactive": max(spec.initial_instances - 2, 2),
                "init_mixed": 1, "init_batch": 1}),
            initial_instances=None,   # Chiron sizes its own pools
            **common)
    if strategy == "lt-ua+plan":
        return StackSpec(scaler="lt-ua", planner=planner_spec(routing=True),
                         router="plan",
                         initial_instances=spec.initial_instances, **common)
    if strategy not in ("reactive", "lt-i", "lt-u", "lt-ua"):
        raise KeyError(f"unknown strategy {strategy!r}; "
                       f"known: {', '.join(STRATEGIES)}")
    planner = None if strategy == "reactive" else planner_spec()
    return StackSpec(scaler=strategy, planner=planner,
                     initial_instances=spec.initial_instances, **common)


def bench_experiment(name: str, spec: BenchSpec,
                     strategies: Sequence[str] = STRATEGIES,
                     schedulers: Optional[Sequence[str]] = None,
                     workloads: Optional[Dict[str, WorkloadSpec]] = None,
                     profiles: Optional[Dict[str, str]] = None,
                     engine: str = "event",
                     ) -> ExperimentSpec:
    """Lift a ``BenchSpec`` into a declarative sweep.

    Either a ``strategies`` axis, or — for the scheduler studies — a
    ``schedulers`` axis where every variant runs the same base strategy
    with a different admission order.  ``workloads`` overrides the
    single default workload derived from ``spec``; ``engine`` selects
    the event loop or the vectorized bucket engine (docs/PERF.md).
    """
    if schedulers is not None:
        strat_axis = {sched: stack_spec(spec, strategies[0], sched)
                      for sched in schedulers}
    else:
        strat_axis = {s: stack_spec(spec, s) for s in strategies}
    return ExperimentSpec(
        name=name, strategies=strat_axis,
        workloads=workloads or {"default": workload_spec(spec)},
        profiles=profiles or {}, engine=engine)


def run_strategy(trace, spec: BenchSpec, strategy: str,
                 scheduler: Optional[str] = None,
                 profiles: Optional[Dict[str, PerfProfile]] = None
                 ) -> Report:
    """One-off run of a single strategy over an existing request list.

    The simulator owns the request lifecycle (outcomes are reset at the
    start of every run), so the same trace can be handed to back-to-back
    runs without any caller-side reset; sweeps should prefer
    ``bench_experiment`` + ``run_experiment``, which hand every run
    fresh request copies.
    """
    stack = build_stack(stack_spec(spec, strategy, scheduler),
                        profiles=profiles)
    return stack.simulate(trace, name=strategy)


def csv_line(name: str, value, derived="") -> str:
    line = f"{name},{value},{derived}"
    print(line, flush=True)
    return line
