"""Bring-up check: both JAX main paths, once each, on one TPU chip.

    python chip_smoke.py [--seed N]

Phases, in one process (a chip belongs to one process at a time):

1. device      — JAX must report a TPU; there is no CPU fallback.
2. deployment  — the paper fleet (3 regions x 4 models, one day at
                 ``scale=1.0``, ~4.9M requests, fleet floored at 150
                 instances per endpoint) under the ``reactive`` and
                 ``lt-ua+plan`` stacks on the vector engine, through
                 ``run_experiment``.  No variant may fall back to the
                 event loop; the engine refuses a plan with non-finite
                 targets or forecasts.
3. parity      — the same two stacks at ``scale=0.05`` on both engines,
                 held to the docs/PERF.md contract: completion within
                 ±0.02, instance-hours and gpu-dollars within ±10%.
4. serving     — ``python -m repro.launch.serve --full``: StarCoder2-7B
                 at published widths and depth, random weights from
                 ``--seed``; 8 IW-F/IW-N requests with 256- and
                 1024-token prompts, 32 new tokens each.  At every
                 step, the first request's greedy token must be the
                 argmax of a no-cache forward over prompt plus generated
                 tokens, up to bf16 rounding between the two programs
                 (its reference logit within 4 bf16 ulps of the
                 maximum); the exact-argmax agreement count is printed.

Every phase prints its wall time, compilation included.  A failed check
raises; the last line of standard output is the device JSON, printed
only when every phase passed.  The compilation cache is placed by
``repro.jaxconfig.configure_jax`` (``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

FLEET_FLOOR = 150               # instances per endpoint (benchmarks.perf_sim)
STACKS = ("reactive", "lt-ua+plan")
PARITY_SCALE = 0.05
COMPLETION_ABS_TOL = 0.02       # docs/PERF.md tolerance contract
HOURS_REL_TOL = 0.10


def _phase(name: str, t0: float, **fields) -> None:
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {kv} wall_s={time.perf_counter() - t0:.1f}", flush=True)


def fleet_stacks(floor: int = FLEET_FLOOR):
    """The benchmark stacks with the fleet floored at ``floor`` instances
    per (model, region): initial fleet, scaler and planner minimums."""
    from benchmarks.common import BenchSpec, stack_spec
    from repro.api import PolicySpec

    spec = BenchSpec(initial_instances=floor, spot_spare=4 * floor)
    out = {}
    for name in STACKS:
        st = stack_spec(spec, name)
        scaler = PolicySpec.coerce(st.scaler)
        kw = {"scaler": PolicySpec(scaler.name, {**scaler.kwargs,
                                                 "min_instances": floor})}
        if st.planner is not None:
            kw["planner"] = PolicySpec(st.planner.name, {
                **st.planner.kwargs, "min_instances": floor})
        out[name] = dataclasses.replace(st, **kw)
    return out


def run_fleet(seed: int, scale: float, engine: str):
    from repro.api.experiment import ExperimentSpec, run_experiment
    from repro.sim.workload import WorkloadSpec

    spec = ExperimentSpec(
        name=f"chip-smoke-{engine}-{scale}", strategies=fleet_stacks(),
        workloads={"day": WorkloadSpec(days=1.0, scale=scale, seed=seed)},
        engine=engine)
    return {r.strategy: r for r in run_experiment(spec, jobs=1)}


def deployment_phase(seed: int, scale: float = 1.0):
    t0 = time.perf_counter()
    res = run_fleet(seed, scale, "vector")
    for name, r in res.items():
        if r.engine != "vector":
            raise RuntimeError(f"{name} fell back to the {r.engine} engine")
        if not (0.9 <= r.completion <= 1.0):
            raise RuntimeError(f"{name} completed {r.completion}")
        for what in ("total_instance_hours", "total_gpu_dollars"):
            v = getattr(r, what)
            if not (math.isfinite(v) and v > 0):
                raise RuntimeError(f"{name} {what}={v}")
        print(f"[deployment] {name}: requests={r.n_requests} "
              f"completion={r.completion!r} "
              f"instance_hours={r.total_instance_hours!r} "
              f"gpu_dollars={r.total_gpu_dollars!r}", flush=True)
    ctl = res["lt-ua+plan"].extras["control"]
    if ctl["plans"] == 0:
        raise RuntimeError("lt-ua+plan produced no hourly plan")
    caches = {k: v for k, v in sorted(ctl.items())
              if k.startswith(("seg_cache_", "fit_cache_"))}
    _phase("deployment", t0, scale=scale, plans=ctl["plans"],
           boundaries=ctl["boundaries"],
           **{k: round(ctl[k], 3) for k in ("forecast_s", "ilp_s",
                                            "transfer_s", "apply_s")},
           **caches)
    return res


def parity_phase(seed: int, scale: float = PARITY_SCALE):
    t0 = time.perf_counter()
    vec = run_fleet(seed, scale, "vector")
    ev = run_fleet(seed, scale, "event")
    for name in STACKS:
        v, e = vec[name], ev[name]
        if v.engine != "vector":
            raise RuntimeError(f"{name} fell back to the {v.engine} engine")
        d_c = v.completion - e.completion
        r_h = v.total_instance_hours / e.total_instance_hours - 1.0
        r_d = v.total_gpu_dollars / e.total_gpu_dollars - 1.0
        print(f"[parity] {name}: completion vector={v.completion!r} "
              f"event={e.completion!r} delta={d_c!r}; instance_hours "
              f"rel={r_h!r}; gpu_dollars rel={r_d!r}", flush=True)
        if abs(d_c) > COMPLETION_ABS_TOL or abs(r_h) > HOURS_REL_TOL \
                or abs(r_d) > HOURS_REL_TOL:
            raise RuntimeError(f"{name} breaks the parity contract")
    _phase("parity", t0, scale=scale)


#: bf16 ulps at the top logit's magnitude that a cached token's reference
#: logit may trail the reference maximum by (see reference_check)
ULP_TOL = 4


def reference_check(cfg, params, prompt, tokens):
    """Score the cached greedy tokens against a plain no-cache forward over
    prompt plus generated tokens (teacher-forced).  Per step returns the
    reference argmax, the deficit of the cached token (reference max
    logit minus the reference logit of the token the cache chose) and
    one bf16 ulp at the reference max logit.

    The two paths run the same bf16 weights through differently shaped
    programs (one query against a 2048-slot cache vs the whole sequence),
    so their logits differ by rounding; where the reference's top two
    logits are closer than that, argmax can legitimately differ.  A
    cache or slot-write fault instead picks tokens whose deficit is of
    the order of the logits themselves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as model_mod

    S = len(prompt)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])

    @jax.jit
    def ref(p, toks, chosen):
        logits = model_mod.forward(cfg, p, {"tokens": toks})[0][0, S - 1:]
        top = logits.max(axis=-1)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        ulp = jnp.exp2(jnp.floor(jnp.log2(jnp.abs(top))) - 7)
        return jnp.argmax(logits, axis=-1), top - picked, ulp

    return jax.device_get(ref(params, jnp.asarray(seq)[None],
                              jnp.asarray(tokens, jnp.int32)))


def serving_phase(seed: int, argv=("--full",)):
    """``argv`` is the serving launcher's command line (``--full``:
    published widths and depth)."""
    import jax
    import numpy as np
    from jax import monitoring

    from repro.configs import get_arch
    from repro.launch import serve

    t0 = time.perf_counter()
    # executables built in this phase, and how many of those the
    # persistent cache supplied without compiling
    counts = {"built": 0, "cache_hits": 0}

    def on_build(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["built"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_build)
    monitoring.register_event_listener(on_event)
    args = serve.parse_args([*argv, "--seed", str(seed)])
    cfg, params, eng, reqs = serve.build(args)
    published = get_arch(args.arch).num_layers
    print(f"[serving] model={cfg.name} layers={cfg.num_layers}/{published} "
          f"d_model={cfg.d_model} max_batch={eng.max_batch} "
          f"max_seq={eng.max_seq} init_s={time.perf_counter() - t0:.1f}",
          flush=True)
    serve.serve(eng, reqs)
    for r in reqs:
        if len(r.tokens) != r.max_new_tokens:
            raise RuntimeError(f"request {r.rid} produced {len(r.tokens)} "
                               f"of {r.max_new_tokens} tokens")
    r0 = reqs[0]
    pred, deficit, ulp = reference_check(cfg, params, r0.prompt, r0.tokens)
    differs = np.flatnonzero(pred != np.asarray(r0.tokens))
    print(f"[serving] request 0: prompt={r0.prompt_tokens} "
          f"tokens={len(r0.tokens)} argmax_agrees="
          f"{len(r0.tokens) - len(differs)}/{len(r0.tokens)} "
          f"differs_at={differs.tolist()} "
          f"deficits_there={deficit[differs].tolist()} "
          f"max_deficit={float(deficit.max())!r} "
          f"bf16_ulp_at_top={float(ulp.max())!r}", flush=True)
    bad = np.flatnonzero(deficit > ULP_TOL * ulp)
    if len(bad):
        raise RuntimeError(f"cached greedy decode departs from the no-cache "
                           f"reference beyond {ULP_TOL} bf16 ulps at steps "
                           f"{bad.tolist()} (deficits "
                           f"{deficit[bad].tolist()})")
    stats = jax.devices()[0].memory_stats() or {}
    _phase("serving", t0, requests=len(reqs),
           prompt_lens=sorted({r.prompt_tokens for r in reqs}),
           prefills=eng.prefill_count, decode_steps=eng.decode_count,
           engine_steps=eng.step_count,
           compiles=counts["built"] - counts["cache_hits"],
           cache_loads=counts["cache_hits"],
           peak_bytes_in_use=stats.get("peak_bytes_in_use"),
           bytes_limit=stats.get("bytes_limit"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.jaxconfig import configure_jax
    cache = configure_jax()
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    _phase("device", t0, platform=dev["platform"], kind=repr(dev["kind"]),
           count=dev["count"], jax=jax.__version__, cache=cache)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev['platform']!r}); nothing was run", file=sys.stderr)
        return 2

    deployment_phase(args.seed)
    parity_phase(args.seed)
    serving_phase(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
