"""Trace-tier reprolint: contract checks over jaxprs and lowerings.

Unlike the AST tier (parsed, never imported), this tier imports the
real hot-path modules, builds tiny canonical instances of the sweep's
jitted computations — the vector-engine segment runner
(``repro.sim.vector.engine``) and the batched forecast fit
(``repro.control.forecast``) — and runs rules over what XLA actually
sees:

- **T1** no host callbacks (``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / ``debug_print`` / infeed / outfeed) inside
  ``lax.scan`` bodies — one callback per bucket would serialize the
  whole scan on host round-trips;
- **T2** dtype stability: tracing under ``enable_x64`` must produce no
  non-weak float64 values.  A weak-typed f64 is a bare Python literal
  (erased by promotion against the f32 state and lowered f32 with x64
  off); a *non-weak* f64 is a real ``np.float64`` constant or array
  that silently downcasts in production — exactly the leak this flags;
- **T3** recompile-key audit: lower the segment runner across
  perturbations of its static config and cross-check ``_Static.key()``
  — a variant whose key differs while the lowering is byte-identical
  fragments ``_SEG_CACHE`` (same kernel compiled twice); a variant
  whose lowering differs under an identical key would serve the wrong
  kernel;
- **T4** donation audit: a declared ``donate_argnums`` must produce
  actual input→output buffer aliasing in the compiled executable
  (upgrading the AST tier's R6 from "donation is declared" to
  "donation really happens").

Budget: canonical shapes are tiny (1 model × 2 regions, 2-bucket
segments, (2, 16) fit batches) and compilation reuses the persistent
XLA cache from ``repro.jaxconfig.configure_jax``, so
the whole tier stays well under the 60 s check.sh budget.

Run via ``python -m repro.analysis --trace`` or programmatically::

    from repro.analysis.trace import run_trace
    result = run_trace()
    assert not result.violations
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import Violation
from repro.jaxconfig import configure_jax

TRACE_RULES = ("T1", "T2", "T3", "T4")

TRACE_RULE_DOCS = {
    "T1": "no host callbacks inside lax.scan bodies",
    "T2": "dtype stability: no non-weak float64 in hot jaxprs",
    "T3": "recompile-key audit: _SEG_CACHE key vs actual lowerings",
    "T4": "donation audit: declared donations really alias buffers",
}

_HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed",
})

_CARRYING_PRIMS = frozenset({"scan", "while"})


# --------------------------------------------------------------- jaxpr walks
def _sub_jaxprs(eqn):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for p in eqn.params.values():
        for q in (p if isinstance(p, (tuple, list)) else (p,)):
            if isinstance(q, ClosedJaxpr):
                yield q.jaxpr
            elif isinstance(q, Jaxpr):
                yield q


def iter_eqns(jaxpr, scan_depth: int = 0):
    """Yield (eqn, scan_depth) over ``jaxpr`` and all sub-jaxprs, where
    ``scan_depth`` counts enclosing scan/while bodies."""
    for eqn in jaxpr.eqns:
        yield eqn, scan_depth
        inner = scan_depth + (1 if eqn.primitive.name in _CARRYING_PRIMS
                              else 0)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def host_callbacks_in_scan(closed) -> List[str]:
    """T1 core: host-callback primitives inside scan/while bodies."""
    out = []
    for eqn, depth in iter_eqns(closed.jaxpr):
        if depth > 0 and eqn.primitive.name in _HOST_CALLBACK_PRIMS:
            out.append(eqn.primitive.name)
    return out


def float64_leaks(closed) -> List[str]:
    """T2 core: non-weak float64 outvars anywhere in the jaxpr.  Trace
    the target under ``jax.enable_x64(True)`` first — with x64
    off, accidental f64 constants are silently downcast and invisible."""
    import jax
    import jax.numpy as jnp

    out = []
    for eqn, _ in iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            av = v.aval
            if isinstance(av, jax.core.ShapedArray) \
                    and av.dtype == jnp.float64 and not av.weak_type:
                out.append(f"{eqn.primitive.name} -> {av.str_short()}")
    return out


# ------------------------------------------------------------------ T3 / T4
@dataclasses.dataclass(frozen=True)
class KeyVariant:
    """One point of the static-config grid: the cache key the code
    would use and the lowering XLA would actually produce."""
    name: str
    key: Tuple
    lowering: str


def audit_static_key(baseline: KeyVariant,
                     variants: Sequence[KeyVariant]) -> List[str]:
    """T3 core: cross-check cache keys against real lowerings."""
    msgs = []
    for v in variants:
        same_key = v.key == baseline.key
        same_low = v.lowering == baseline.lowering
        if not same_key and same_low:
            msgs.append(
                f"{v.name}: static key differs but the lowering is "
                f"byte-identical — the key fragments the cache (same "
                f"kernel traced and compiled twice)")
        elif same_key and not same_low:
            msgs.append(
                f"{v.name}: lowering differs under an identical static "
                f"key — the cache would serve the wrong kernel")
    return msgs


def donation_aliases(compiled_text: str) -> int:
    """Number of input→output buffer aliases in a compiled HLO module
    (the ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` header
    entries)."""
    return (compiled_text.count("may-alias")
            + compiled_text.count("must-alias"))


def audit_donation(jitted, args) -> Optional[str]:
    """T4 core: compile ``jitted`` on ``args`` and verify at least one
    declared donation became a real buffer alias."""
    txt = jitted.lower(*args).compile().as_text()
    if donation_aliases(txt) == 0:
        return ("declared donate_argnums produced ZERO input->output "
                "aliases in the compiled executable — the donation is "
                "a lie (shape/dtype mismatch or unused donated input) "
                "and every segment copies its carry")
    return None


# ----------------------------------------------------- canonical instances
def _canonical_engine():
    """Tiny real instance of the vector engine's static config: first
    profiled model, two regions, the unified pool, default tick — built
    through the same ``extract`` path the production runner uses."""
    from repro.core.scaling import ReactivePolicy
    from repro.sim.perfmodel import PROFILES
    from repro.sim.simulator import SimConfig
    from repro.sim.vector import engine as eng
    from repro.sim.vector.params import extract

    import numpy as np

    model = sorted(PROFILES)[0]
    models, regions = [model], ["east", "west"]
    profiles = {model: PROFILES[model]}
    cfg = SimConfig(policy=ReactivePolicy())
    rp = extract(cfg, models, regions, profiles, "trace-tier")
    st = eng._Static(models, regions, rp.pools, profiles, cfg.tick)
    prm = eng._prm(st, rp)
    carry = eng._init_carry(st, rp)
    B = 2
    z = lambda *s: np.zeros(s, np.float32)
    xs = {k: z(B, st.C, st.J) for k in ("iw_n", "iw_p", "iw_o", "niw_n",
                                        "niw_p", "niw_o", "obs")}
    xs["fcum"] = z(B, st.C)
    xs["b"] = np.arange(B, dtype=np.int32)
    return eng, rp, st, prm, carry, xs


def _seg_runner(eng, st):
    import jax

    step = eng._build_step(st)

    def run_seg(prm, carry, xs):
        return jax.lax.scan(lambda c, x: step(prm, c, x), carry, xs)

    return run_seg


def _lower_text(eng, st, rp, xs) -> str:
    """StableHLO for this static config's segment runner (lower only —
    no compile — so the whole T3 grid costs seconds)."""
    import jax

    run_seg = _seg_runner(eng, st)
    return jax.jit(run_seg).lower(
        eng._prm(st, rp), eng._init_carry(st, rp), xs).as_text()


def engine_key_variants() -> Tuple[KeyVariant, List[KeyVariant]]:
    """The T3 grid: baseline plus name-only and numeric perturbations
    of everything ``_Static.key()`` claims to cover.  Name-only
    renames must not change the lowering (the step closes over counts
    and numeric arrays, never label strings); numeric perturbations
    must change both the key and the lowering."""
    import dataclasses as dc

    from repro.sim.perfmodel import PROFILES

    eng, rp, st, _, _, xs = _canonical_engine()
    model = st.models[0]
    prof = PROFILES[model]

    def variant(name, models=None, regions=None, pools=None, dt=None,
                profile=None):
        models = models or st.models
        regions = regions or st.regions
        pools = pools or st.pools
        profiles = {m: (profile or prof) for m in models}
        st2 = eng._Static(list(models), list(regions), tuple(pools),
                          profiles, dt or st.dt)
        return KeyVariant(name, st2.key(),
                          _lower_text(eng, st2, rp, xs))

    baseline = variant("baseline")
    variants = [
        variant("model renamed", models=[model + "-renamed"]),
        variant("regions renamed", regions=["north", "south"]),
        variant("pool renamed", pools=("primary",)),
        variant("tick doubled", dt=st.dt * 2),
        variant("profile prompt_tps doubled",
                profile=dc.replace(prof, prompt_tps=prof.prompt_tps * 2)),
    ]
    return baseline, variants


# ------------------------------------------------------------------ runner
@dataclasses.dataclass
class TraceCheck:
    rule: str
    target: str
    ok: bool
    detail: str = ""


@dataclasses.dataclass
class TraceResult:
    violations: List[Violation]
    checks: List[TraceCheck]
    elapsed_s: float

    def to_json(self) -> Dict:
        return {
            "elapsed_s": round(self.elapsed_s, 2),
            "checks": [dataclasses.asdict(c) for c in self.checks],
            "violations": [v.to_json() for v in self.violations],
        }


def _loc(obj) -> Tuple[str, int]:
    """(display path, line) of a live object, for violation reports."""
    try:
        path = inspect.getsourcefile(obj) or "<unknown>"
        line = inspect.getsourcelines(obj)[1]
    except (TypeError, OSError):
        return "<unknown>", 1
    try:
        path = str(Path(path).resolve().relative_to(Path.cwd()))
    except ValueError:
        pass
    return path, line


def run_trace() -> TraceResult:
    """Run T1–T4 over the canonical hot-path instances and return every
    violation (empty = the sweep's performance contracts hold)."""
    configure_jax()
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    checks: List[TraceCheck] = []
    violations: List[Violation] = []

    def record(rule, target, msgs, file, line):
        checks.append(TraceCheck(rule, target, not msgs,
                                 "; ".join(msgs)[:300]))
        for m in msgs:
            violations.append(Violation(rule, file, line, 0, m))

    # ---- vector engine: segment runner --------------------------------
    from repro.sim.vector import engine as eng

    _, rp, st, prm, carry, xs = _canonical_engine()
    run_seg = _seg_runner(eng, st)
    efile, eline = _loc(eng._build_step)
    with jax.enable_x64(True):
        seg_jaxpr = jax.make_jaxpr(run_seg)(prm, carry, xs)
    record("T1", "engine segment runner",
           [f"host callback '{p}' inside the segment scan body"
            for p in host_callbacks_in_scan(seg_jaxpr)], efile, eline)
    record("T2", "engine segment runner",
           [f"float64 leak in the segment scan: {m}"
            for m in float64_leaks(seg_jaxpr)], efile, eline)

    kfile, kline = _loc(eng._Static.key)
    baseline, variants = engine_key_variants()
    record("T3", "engine _SEG_CACHE static key",
           audit_static_key(baseline, variants), kfile, kline)

    sfile, sline = _loc(eng._compiled_segments)
    seg_single, _ = eng._compiled_segments(st)
    msg = audit_donation(seg_single, (prm, carry, xs))
    record("T4", "engine seg_single donate_argnums",
           [msg] if msg else [], sfile, sline)

    # ---- batched forecast fit -----------------------------------------
    from repro.control import forecast as fc

    ffile, fline = _loc(fc._fit_arma_batch)
    y = np.zeros((2, 16), np.float32)
    init = {"c": np.zeros((2,), np.float32),
            "phi": np.zeros((2, 2), np.float32),
            "theta": np.zeros((2, 1), np.float32)}
    with jax.enable_x64(True):
        fit_jaxpr = jax.make_jaxpr(
            lambda yy, ii: fc._fit_arma_batch(yy, ii, 2, 1, steps=8))(
                y, init)
    record("T1", "batched forecast fit",
           [f"host callback '{p}' inside the Adam scan body"
            for p in host_callbacks_in_scan(fit_jaxpr)], ffile, fline)
    record("T2", "batched forecast fit",
           [f"float64 leak in the fit path: {m}"
            for m in float64_leaks(fit_jaxpr)], ffile, fline)

    return TraceResult(violations, checks,
                       elapsed_s=time.perf_counter() - t0)
