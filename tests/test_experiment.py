"""The declarative experiment layer (repro.api.experiment):

- spec round-trips (ExperimentSpec / Variant / WorkloadSpec) with
  unknown-key rejection, mirroring StackSpec's contract;
- deterministic seed derivation and cartesian expansion;
- per-unique-WorkloadSpec trace memoization (one generate per workload,
  fresh Request copies per run);
- parallel runs field-identical to serial ones;
- back-to-back runs over one shared trace leak no request state
  (the reset_trace footgun is structurally gone);
- artifact save/load round-trip + baseline-comparison helpers.
"""
import dataclasses
import json
import math

import pytest

from repro.api import StackSpec
from repro.api import experiment as exp_mod
from repro.api.experiment import (ExperimentSpec, ResultSet, Variant,
                                  derive_seed, run_experiment, spec_hash)
from repro.sim.metrics import report_to_dict
from repro.sim.workload import (PAPER_MODELS, REGIONS, PopularityShift,
                                WorkloadSpec)

TINY_WL = dict(days=0.05, scale=0.01, seed=2)


def _stack(scaler="reactive", **kw):
    return StackSpec(models=PAPER_MODELS, regions=REGIONS, scaler=scaler,
                     initial_instances=3, spot_spare=8, **kw)


def _exp(strategies=("reactive",), name="exp", **kw):
    return ExperimentSpec(
        name=name, strategies={s: _stack(s if s != "siloed" else "reactive",
                                         siloed=(s == "siloed"))
                               for s in strategies},
        workloads={"tiny": WorkloadSpec(**TINY_WL)}, **kw)


# ------------------------------------------------------------------- specs
def test_workloadspec_roundtrip_with_pop_shifts():
    wl = WorkloadSpec(days=0.5, scale=0.02, seed=4,
                      burst_mult=8.0, burst_hours=(6.0,),
                      pop_shifts=(PopularityShift(
                          "bloom-176b", 4.0, 12.0, 0.0,
                          regions=("westus",)),))
    d = wl.to_dict()
    json.dumps(d)                                  # JSON-able
    assert WorkloadSpec.from_dict(d) == wl
    with pytest.raises(KeyError, match="unknown WorkloadSpec fields"):
        WorkloadSpec.from_dict({"days": 1.0, "bogus": 2})


def test_experiment_spec_roundtrip():
    spec = _exp(("reactive", "lt-ua"), seeds=(0, 1),
                profiles={"llama2-70b": "llama2-70b@a100"})
    d = spec.to_dict()
    json.dumps(d)
    again = ExperimentSpec.from_dict(d)
    assert again == spec
    assert again.validate() is again


def test_explicit_variant_roundtrip():
    v = Variant(name="combined/aware", stack=_stack(),
                workload=WorkloadSpec(**TINY_WL), strategy="aware",
                workload_name="combined")
    spec = ExperimentSpec(name="placement", variants=(v,))
    again = ExperimentSpec.from_dict(spec.to_dict())
    assert again == spec
    assert again.expand() == (v,)
    with pytest.raises(KeyError, match="unknown Variant fields"):
        Variant.from_dict({**v.to_dict(), "nope": 1})


def test_experiment_validation_errors():
    with pytest.raises(KeyError, match="unknown ExperimentSpec fields"):
        ExperimentSpec.from_dict({"name": "x", "bogus": 1})
    with pytest.raises(ValueError, match="strategies axis or an explicit"):
        ExperimentSpec(name="x").validate()
    with pytest.raises(ValueError, match="workloads must be non-empty"):
        ExperimentSpec(name="x",
                       strategies={"r": _stack()}).validate()
    with pytest.raises(ValueError, match="name must be non-empty"):
        _exp(name="").validate()
    with pytest.raises(ValueError, match="seeds must be ints"):
        _exp(seeds=("a",)).validate()
    with pytest.raises(KeyError, match="no perf profile named"):
        _exp(profiles={"llama2-70b": "nope"}).validate()
    # nested stack specs are validated too
    bad = _exp()
    bad.strategies["reactive"].scaler = None
    with pytest.raises(ValueError, match="scaler is required"):
        bad.validate()
    # duplicate variant names fail loud
    v = Variant(name="dup", stack=_stack(),
                workload=WorkloadSpec(**TINY_WL))
    with pytest.raises(ValueError, match="duplicate variant name"):
        ExperimentSpec(name="x", variants=(v, v)).validate()
    # axes + explicit variants would silently drop the axes: rejected
    with pytest.raises(ValueError, match="not both"):
        ExperimentSpec(name="x", strategies={"r": _stack()},
                       workloads={"w": WorkloadSpec(**TINY_WL)},
                       variants=(v,)).validate()


def test_derive_seed_deterministic_and_distinct():
    assert derive_seed(0, "wl", 1) == derive_seed(0, "wl", 1)
    assert derive_seed(0, "wl", 1) != derive_seed(0, "wl", 2)
    assert derive_seed(0, "a", 1) != derive_seed(0, "b", 1)
    assert 0 <= derive_seed(3, "x", 9) < 2 ** 32


def test_expand_cartesian_seed_semantics():
    # no seeds axis: the workload's own seed, shared by every strategy
    spec = _exp(("reactive", "lt-ua"))
    vs = spec.expand()
    assert [v.name for v in vs] == ["reactive/tiny", "lt-ua/tiny"]
    assert all(v.workload.seed == TINY_WL["seed"] for v in vs)
    # seeds axis: derived per (workload, seed), identical across
    # strategies so they always compare on the same trace
    spec = _exp(("reactive", "lt-ua"), seeds=(0, 1))
    vs = spec.expand()
    assert len(vs) == 4
    by_tag = {}
    for v in vs:
        by_tag.setdefault(v.name.split("/s")[-1], set()).add(
            v.workload.seed)
    assert all(len(s) == 1 for s in by_tag.values())       # shared
    assert by_tag["0"] != by_tag["1"]                      # distinct
    assert spec.expand() == vs                             # stable


def test_spec_hash_stable_and_sensitive():
    v = Variant(name="a", stack=_stack(),
                workload=WorkloadSpec(**TINY_WL))
    h = spec_hash(v.to_dict())
    assert h == spec_hash(v.to_dict()) and len(h) == 16
    v2 = dataclasses.replace(
        v, workload=WorkloadSpec(**{**TINY_WL, "seed": 3}))
    assert spec_hash(v2.to_dict()) != h


# ------------------------------------------------------------------- runner
def test_trace_memoized_one_generate_per_unique_workload(monkeypatch):
    calls = []
    real = exp_mod.generate_trace

    def counting(wl):
        calls.append(wl.seed)
        return real(wl)

    monkeypatch.setattr(exp_mod, "generate_trace", counting)
    spec = _exp(("reactive", "siloed", "lt-ua"))
    run_experiment(spec, jobs=1)
    assert len(calls) == 1          # three strategies, one generation
    calls.clear()
    spec = _exp(("reactive",), seeds=(0, 1))
    run_experiment(spec, jobs=1)
    assert len(calls) == 2          # two derived workloads


def _count_done(requests, report):
    """Probe: completion re-derived from the actual request outcomes."""
    return sum(1 for r in requests if not math.isnan(r.e2e))


def test_parallel_matches_serial_and_completion_from_report():
    spec = _exp(("reactive", "siloed"))
    probes = {"done": _count_done}
    serial = run_experiment(spec, jobs=1, probes=probes)
    parallel = run_experiment(spec, jobs=2, probes=probes)
    assert [r.variant for r in parallel] == [r.variant for r in serial]
    for a, b in zip(serial, parallel):
        da, db = a.to_dict(), b.to_dict()
        da.pop("wall_s"), db.pop("wall_s")     # timing genuinely differs
        assert da == db, a.variant
        # satellite: Report-derived completion == request-scan completion
        assert a.completed_total == a.extras["done"]
        assert 0.0 < a.completion <= 1.0


@pytest.mark.parametrize("backend,jobs,want", [
    ("cpu", None, "cpus"), ("cpu", 3, 3), ("tpu", None, 1), ("tpu", 1, 1),
    ("tpu", 2, ValueError),
])
def test_jobs_stay_in_process_on_an_accelerator(monkeypatch, backend, jobs,
                                                want):
    """An accelerator belongs to one process: no worker pool there."""
    import os

    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError, match="jobs=2"):
            exp_mod._resolve_jobs(jobs)
        return
    want = (os.cpu_count() or 1) if want == "cpus" else want
    assert exp_mod._resolve_jobs(jobs) == want


def test_consecutive_runs_share_trace_without_reset():
    """The footgun regression: two back-to-back runs over the *same*
    request list produce field-identical Reports — the run path owns
    the request lifecycle (no caller-side reset_trace anywhere)."""
    from benchmarks.common import BenchSpec, run_strategy
    from repro.sim.workload import generate
    trace = generate(WorkloadSpec(**TINY_WL))
    bench = BenchSpec(days=TINY_WL["days"], scale=TINY_WL["scale"],
                      seed=TINY_WL["seed"], initial_instances=3,
                      spot_spare=8)
    first = report_to_dict(run_strategy(trace, bench, "reactive"))
    assert any(not math.isnan(r.e2e) for r in trace)   # trace is dirty now
    second = report_to_dict(run_strategy(trace, bench, "reactive"))
    assert first == second


# ----------------------------------------------------------------- artifacts
def test_artifact_save_load_roundtrip(tmp_path):
    path = str(tmp_path / "sub" / "results.json")
    spec = _exp(("reactive", "siloed"))
    results = run_experiment(spec, jobs=1, out=path)
    loaded = ResultSet.load(path)
    assert loaded.schema == exp_mod.SCHEMA
    assert loaded.to_dict() == results.to_dict()
    assert loaded.experiment == spec.to_dict()
    # loaded results expose the same accessors as fresh ones
    r = loaded.get(strategy="reactive")
    assert r.total_instance_hours > 0
    assert r.spec_hash == results.get(strategy="reactive").spec_hash
    with pytest.raises(KeyError, match="matched 0 results"):
        loaded.get(strategy="nope")


def test_deltas_baseline_helpers(tmp_path):
    spec = _exp(("reactive", "siloed"))
    results = run_experiment(spec, jobs=1)
    deltas = results.deltas(baseline="siloed")
    assert set(deltas) == {"reactive/tiny"}
    d = deltas["reactive/tiny"]
    assert d["vs"] == "siloed/tiny"
    sil = results.get(strategy="siloed")
    uni = results.get(strategy="reactive")
    gd = d["gpu_dollars"]
    assert gd["base"] == pytest.approx(sil.total_gpu_dollars)
    assert gd["ours"] == pytest.approx(uni.total_gpu_dollars)
    assert gd["delta"] == pytest.approx(gd["base"] - gd["ours"])
    ih = d["instance_hours"]
    assert ih["pct"] == pytest.approx(
        100.0 * (1 - uni.total_instance_hours / sil.total_instance_hours))
    for tier, sla in d["sla_attainment"].items():
        assert sla["delta"] == pytest.approx(
            uni.sla_attainment(tier) - sil.sla_attainment(tier))
    with pytest.raises(KeyError, match="no results for baseline"):
        results.deltas(baseline="nope")
