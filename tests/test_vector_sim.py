"""PR-7 vector engine: parity, determinism, batching and the control
boundary.

The tolerance contract (docs/PERF.md): on the same stack + trace the
vector engine must land within ±0.02 absolute on completion fraction
and within ±10% relative on instance-hours and gpu_dollars of the
event loop; repeats under a fixed seed are bit-identical; a vmapped
batch of one is exactly the unbatched path; hourly ``Plan``s cross the
host boundary into array state exactly (targets, forecasts, normalized
routing rows).
"""
import json
import pathlib

import numpy as np
import pytest

from repro.api.plan import Plan, RoutingPlan
from repro.core.queue_manager import QueueManager
from repro.core.scaling import make_policy
from repro.sim.metrics import report_to_dict
from repro.sim.simulator import SimConfig, Simulation
from repro.sim.vector import (VectorBatch, VectorSimulation,
                              VectorUnsupported)
from repro.sim.workload import WorkloadSpec, generate_trace, replay_csv

GOLDEN = pathlib.Path(__file__).parent / "golden"

# docs/PERF.md tolerance contract
COMPLETION_ABS_TOL = 0.02
HOURS_REL_TOL = 0.10


def _golden_cfg():
    # same stack as tests/test_perf_equivalence._golden_cfg
    return SimConfig(policy=make_policy("reactive"),
                     queue_manager=QueueManager(),
                     initial_instances=3, spot_spare=8,
                     drain_grace=3 * 3600.0)


@pytest.fixture(scope="module")
def golden_trace():
    return replay_csv(str(GOLDEN / "trace_small.csv.gz"))


@pytest.fixture(scope="module")
def small_trace():
    return generate_trace(WorkloadSpec(days=0.1, scale=0.01, seed=3))


# ----------------------------------------------------------------- parity
def test_vector_matches_event_loop_on_golden(golden_trace):
    """Completion fraction, instance-hours and gpu_dollars within the
    documented tolerance of the pinned event-loop report."""
    with open(GOLDEN / "report_small.json") as f:
        ev = json.load(f)
    rep = VectorSimulation(golden_trace, _golden_cfg(),
                           name="golden").run()
    vec = report_to_dict(rep)
    n = sum(ev["completed"].values()) + sum(ev["dropped"].values())
    ev_frac = sum(ev["completed"].values()) / n
    vec_frac = sum(vec["completed"].values()) / n
    assert abs(vec_frac - ev_frac) <= COMPLETION_ABS_TOL
    ev_ih = sum(ev["instance_hours"].values())
    vec_ih = sum(vec["instance_hours"].values())
    assert vec_ih == pytest.approx(ev_ih, rel=HOURS_REL_TOL)
    assert vec["gpu_dollars_total"] == pytest.approx(
        ev["gpu_dollars_total"], rel=HOURS_REL_TOL)


def test_vector_report_shape(golden_trace):
    """The vector Report walks and serializes like an event-loop one:
    same tiers, same keyed dicts, sane latency stats."""
    rep = VectorSimulation(golden_trace, _golden_cfg(), name="g").run()
    d = report_to_dict(rep)
    assert set(d["completed"]) == set(d["ttft"])
    for tier, q in d["ttft"].items():
        assert q["p50"] <= q["p95"], tier
        assert q["mean"] >= 0.0
    assert all(v >= 0 for v in d["instance_hours"].values())


# ------------------------------------------------------------ determinism
def test_repeats_bit_identical(golden_trace):
    a = report_to_dict(VectorSimulation(golden_trace, _golden_cfg(),
                                        name="r").run())
    b = report_to_dict(VectorSimulation(golden_trace, _golden_cfg(),
                                        name="r").run())
    assert a == b


# --------------------------------------------------------------- batching
def test_batch_of_one_matches_unbatched(small_trace):
    single = VectorBatch(small_trace, [_golden_cfg()], ["v"],
                         batched=False).run()[0]
    batched = VectorBatch(small_trace, [_golden_cfg()], ["v"],
                         batched=True).run()[0]
    assert report_to_dict(single) == report_to_dict(batched)


def test_batch_members_independent(small_trace):
    """Two replicas in one vmapped batch reproduce their solo runs."""
    cfgs = [_golden_cfg(), _golden_cfg()]
    reps = VectorBatch(small_trace, cfgs, ["a", "b"], batched=True).run()
    solo = VectorBatch(small_trace, [_golden_cfg()], ["a"],
                       batched=False).run()[0]
    da, db = report_to_dict(reps[0]), report_to_dict(reps[1])
    ds = report_to_dict(solo)
    da["name"] = db["name"] = ds["name"] = "x"
    assert da == db == ds


def test_siloed_lt_unsupported(small_trace):
    cfg = SimConfig(policy=make_policy("lt-ua"), siloed=True,
                    initial_instances=3, spot_spare=8)
    with pytest.raises(VectorUnsupported):
        VectorBatch(small_trace, [cfg], ["s"])


# ------------------------------------------------------- control boundary
class _StubController:
    """Deterministic hourly plan: fixed targets + routing split."""

    def __init__(self, targets, fractions=None):
        self.targets = targets
        self.fractions = fractions
        self.calls = 0

    def plan(self, now, instances, history, niw_last_hour_tps):
        self.calls += 1
        routing = (RoutingPlan(fractions=self.fractions)
                   if self.fractions else None)
        return Plan(t=now, targets=dict(self.targets),
                    forecasts={k: 100.0 for k in self.targets},
                    routing=routing)


def test_hourly_plan_crosses_into_array_state(small_trace):
    """The host boundary applies a Plan to array state exactly the way
    the event loop's ``_on_hour`` hands it to ``set_targets`` /
    ``update_plan``: targets and forecasts land in the home cells,
    routing fractions become normalized ω rows."""
    models = list(small_trace.models)
    regions = list(small_trace.regions)
    m0, r0, r1 = models[0], regions[0], regions[1]
    targets = {(m, r): 4 for m in models for r in regions}
    fracs = {(m0, r0): {r0: 0.5, r1: 0.5}}
    ctl = _StubController(targets, fracs)
    cfg = SimConfig(policy=make_policy("lt-i"), controller=ctl,
                    initial_instances=2, spot_spare=20)
    # a plan-aware router is what makes omega live (params lowers the
    # plan feed through the update_plan capability)
    from repro.api import PolicySpec, resolve
    from repro.api.stack import BuildContext
    from repro.sim.perfmodel import PROFILES
    ctx = BuildContext(tuple(models), tuple(regions),
                       {m: PROFILES[m] for m in models})
    cfg.router = resolve("router", PolicySpec("plan"), ctx)

    vb = VectorBatch(small_trace, [cfg], ["plan"], models=models,
                     regions=regions, batched=False)
    st = vb.st
    from repro.sim.vector.buckets import bucketize
    kv = {m: PROFILES[m].kv_capacity_tokens for m in models}
    horizon = float(small_trace.arrival[-1]) + cfg.drain_grace
    bk = bucketize(small_trace, st.dt, horizon, kv,
                   hist_window=cfg.tps_window)
    from repro.sim.vector.engine import _init_carry
    cv = {k: np.array(v) for k, v in
          _init_carry(st, vb.rps[0]).items()}
    heap = []
    vb._extra_si = [0.0]
    vb._apply_hour(0, cv, 3600.0, bk, heap)
    assert ctl.calls == 1
    for mi, m in enumerate(models):
        for ji, r in enumerate(regions):
            assert cv["tgt"][mi * st.P, ji] == 4.0, (m, r)
            assert cv["fc"][mi * st.P, ji] == 100.0, (m, r)
    # omega: the declared row normalized, every other row left off
    row = cv["omega"][0, 0, :]
    assert row[regions.index(r0)] == pytest.approx(0.5)
    assert row[regions.index(r1)] == pytest.approx(0.5)
    assert cv["has_om"][0, 0] == 1.0
    assert cv["has_om"][0, regions.index(r1)] == 0.0


def test_non_finite_plan_is_refused_at_the_boundary(small_trace):
    """A NaN forecast must not reach the carry: it would poison every
    later bucket of the scan without an error."""
    from repro.sim.vector.engine import _init_carry
    models, regions = list(small_trace.models), list(small_trace.regions)
    targets = {(m, r): 4 for m in models for r in regions}
    cfg = SimConfig(policy=make_policy("lt-i"),
                    controller=_StubController(targets),
                    initial_instances=2, spot_spare=20)
    vb = VectorBatch(small_trace, [cfg], ["nan"], models=models,
                     regions=regions, batched=False)
    cv = {k: np.array(v) for k, v in _init_carry(vb.st, vb.rps[0]).items()}
    forecasts = {k: 100.0 for k in targets}
    forecasts[(models[0], regions[0])] = float("nan")
    with pytest.raises(ValueError, match="non-finite"):
        vb._apply_plan(0, cv, 3600.0, Plan(t=3600.0, targets=targets,
                                           forecasts=forecasts), [])
    assert (cv["fc"] == 0.0).all()


def test_lt_targets_actuate_like_event_loop(small_trace):
    """End-to-end: the same stub plan drives both engines; the fleets
    they scale to agree (LT-I jumps straight to the hourly target)."""
    models = list(small_trace.models)
    regions = list(small_trace.regions)
    targets = {(m, r): 3 for m in models for r in regions}

    def mk_cfg():
        return SimConfig(policy=make_policy("lt-i"),
                         controller=_StubController(targets),
                         initial_instances=2, spot_spare=30)

    ev = Simulation(small_trace.to_requests(), mk_cfg(),
                    models=models, regions=regions, name="ev").run()
    vec = VectorSimulation(small_trace, mk_cfg(), models=models,
                           regions=regions, name="vec").run()
    ev_ih = sum(ev.instance_hours.values())
    vec_ih = sum(vec.instance_hours.values())
    assert vec_ih == pytest.approx(ev_ih, rel=HOURS_REL_TOL)
    ev_done = sum(ev.completed.values())
    vec_done = sum(vec.completed.values())
    n = len(small_trace)
    assert abs(vec_done - ev_done) / max(n, 1) <= COMPLETION_ABS_TOL
