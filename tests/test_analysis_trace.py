"""Tests for the reprolint trace tier (T1-T4), the R7 cache-key rule,
and the W0 stale-suppression warning.

Each T-rule is proven twice: it FIRES on a deliberately-bad jitted
fixture built inline here (host callback in a scan body, non-weak f64
leak, phantom static key, lying donate_argnums), and it PASSES on the
real hot paths via one shared ``run_trace()`` (which is also what
``scripts/check.sh`` gates on).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import run_lint
from repro.analysis import trace as tr
from tests.test_analysis import FIXTURES, _hits, _marker_line


@pytest.fixture(scope="module")
def trace_result():
    return tr.run_trace()


@pytest.fixture(scope="module")
def fixture_result():
    return run_lint([str(FIXTURES)])


# ------------------------------------------------------------- T1 fires
def test_t1_fires_on_host_callback_in_scan_body():
    def bad(xs):
        def body(c, x):
            jax.debug.print("x={x}", x=x)
            return c + x, x
        return jax.lax.scan(body, 0.0, xs)

    cj = jax.make_jaxpr(bad)(jnp.zeros(4))
    found = tr.host_callbacks_in_scan(cj)
    assert "debug_print" in found


def test_t1_ignores_callback_outside_scan():
    def ok(x):
        jax.debug.print("once: {x}", x=x)
        return x * 2.0

    cj = jax.make_jaxpr(ok)(jnp.zeros(4))
    assert tr.host_callbacks_in_scan(cj) == []


# ------------------------------------------------------------- T2 fires
def test_t2_fires_on_float64_constant():
    def bad(x):
        return x * np.float64(2.0)   # real f64 constant, not a literal

    with jax.enable_x64(True):
        cj = jax.make_jaxpr(bad)(np.zeros(3, np.float32))
    leaks = tr.float64_leaks(cj)
    assert leaks and any("float64" in m for m in leaks)


def test_t2_tolerates_weak_python_literals():
    # a bare Python float is weak-typed: erased by promotion against
    # the f32 state, lowered f32 with x64 off — not a leak
    def ok(x):
        return jnp.where(x > 0.5, 1.0, 0.0) * x

    with jax.enable_x64(True):
        cj = jax.make_jaxpr(ok)(np.zeros(3, np.float32))
    assert tr.float64_leaks(cj) == []


# ------------------------------------------------------------- T3 fires
def test_t3_flags_phantom_static_key():
    base = tr.KeyVariant("baseline", ("cfg", 1.0), "HLO-A")
    phantom = tr.KeyVariant("renamed label", ("cfg-renamed", 1.0), "HLO-A")
    msgs = tr.audit_static_key(base, [phantom])
    assert len(msgs) == 1 and "fragments the cache" in msgs[0]


def test_t3_flags_unsound_key():
    base = tr.KeyVariant("baseline", ("cfg", 1.0), "HLO-A")
    unsound = tr.KeyVariant("tick changed", ("cfg", 1.0), "HLO-B")
    msgs = tr.audit_static_key(base, [unsound])
    assert len(msgs) == 1 and "wrong kernel" in msgs[0]


def test_t3_passes_honest_variants():
    base = tr.KeyVariant("baseline", ("cfg", 1.0), "HLO-A")
    honest = [tr.KeyVariant("same", ("cfg", 1.0), "HLO-A"),
              tr.KeyVariant("changed", ("cfg", 2.0), "HLO-B")]
    assert tr.audit_static_key(base, honest) == []


def test_t3_catches_name_keyed_seg_cache_regression(trace_result):
    """The pre-fix ``_Static.key()`` keyed on model/region/pool name
    strings; rebuild that key shape from the real lowerings and assert
    the audit flags it — the committed counts-based key must not."""
    baseline, variants = tr.engine_key_variants()
    renamed = next(v for v in variants if v.name == "model renamed")
    # the rename really does not change what XLA compiles
    assert renamed.lowering == baseline.lowering
    assert renamed.key == baseline.key   # fixed key: names are not keyed
    # simulate the old name-keyed scheme: same lowering, distinct keys
    old_base = tr.KeyVariant("baseline", baseline.key + (("m",),),
                             baseline.lowering)
    old_renamed = tr.KeyVariant("model renamed",
                                renamed.key + (("m-renamed",),),
                                renamed.lowering)
    msgs = tr.audit_static_key(old_base, [old_renamed])
    assert msgs and "fragments the cache" in msgs[0]


# ------------------------------------------------------------- T4 fires
def test_t4_fires_on_lying_donation():
    # the donated operand's shape/dtype matches no output, so XLA
    # cannot alias anything: donation is declared but never happens
    lying = jax.jit(lambda a, b: a * 2.0, donate_argnums=(1,))
    msg = tr.audit_donation(
        lying, (np.zeros(4, np.float32), np.zeros(7, np.int32)))
    assert msg is not None and "ZERO" in msg


def test_t4_passes_on_honest_donation():
    honest = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
    assert tr.audit_donation(honest, (np.zeros(8, np.float32),)) is None
    txt = honest.lower(np.zeros(8, np.float32)).compile().as_text()
    assert tr.donation_aliases(txt) >= 1


# ------------------------------------------- real hot paths stay clean
def test_real_hot_paths_pass_all_trace_rules(trace_result):
    msgs = "\n".join(v.render() for v in trace_result.violations)
    assert not trace_result.violations, f"trace-tier violations:\n{msgs}"


def test_trace_covers_every_rule_on_both_paths(trace_result):
    rules = {c.rule for c in trace_result.checks}
    assert rules == set(tr.TRACE_RULES)
    targets = {c.target for c in trace_result.checks}
    assert any("engine" in t for t in targets)
    assert any("forecast" in t for t in targets)


def test_trace_within_check_budget(trace_result):
    assert trace_result.elapsed_s <= 60.0


# ------------------------------------------------------------- R7 rule
def test_r7_fires_on_missing_field(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-MISSING-FIELD")
    assert any(h.line == line and "freshly_added_knob" in h.message
               for h in hits)


def test_r7_fires_on_exemption_without_reason(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-NO-REASON") + 1
    assert any(h.line == line and "reason" in h.message for h in hits)


def test_r7_fires_on_unknown_field_exemption(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-UNKNOWN-FIELD")
    assert any(h.line == line and "not_a_field" in h.message for h in hits)


def test_r7_fires_on_stale_exemption(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-STALE-EXEMPT")
    assert any(h.line == line and "stale key-exempt" in h.message
               for h in hits)


def test_r7_fires_on_unknown_target(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-UNKNOWN-TARGET")
    assert any(h.line == line and "NoSuchConfig" in h.message for h in hits)


def test_r7_fires_on_init_attr_not_in_sig(fixture_result):
    hits = _hits(fixture_result, "R7", "bad_r7.py")
    line = _marker_line("bad_r7.py", "R7-VIOLATION-INIT-MISSING")
    missing = {h.message for h in hits if h.line == line}
    assert any("'q'" in m for m in missing)
    assert any("'counter'" in m for m in missing)


def test_r7_reasoned_exemption_passes(fixture_result):
    ok_line = _marker_line("bad_r7.py", "ok: exemption carries a reason")
    assert not any(h.line == ok_line
                   for h in _hits(fixture_result, "R7", "bad_r7.py"))


def test_r7_real_fingerprint_needs_zero_exemptions():
    """Acceptance: the real ``problem_fingerprint`` hashes every
    ProvisionProblem field with no exemption comments at all."""
    import inspect

    from repro.control import amortize

    src = inspect.getsource(amortize.problem_fingerprint)
    assert "key-exempt" not in src
    result = run_lint([inspect.getsourcefile(amortize)])
    assert not [v for v in result.violations if v.rule == "R7"]


# ------------------------------------------------------- W0 staleness
def test_w0_flags_stale_suppression(fixture_result):
    line = _marker_line("suppressed.py", "W0-STALE")
    w = [v for v in fixture_result.warnings
         if v.file.endswith("suppressed.py") and v.line == line]
    assert len(w) == 1
    assert w[0].rule == "W0" and w[0].severity == "warning"
    # warnings never count as violations
    assert not any(v.rule == "W0" for v in fixture_result.violations)


def test_w0_silent_on_live_suppression(fixture_result):
    live = _marker_line("suppressed.py", "measurement-only timing")
    assert not any(v.line == live and v.file.endswith("suppressed.py")
                   for v in fixture_result.warnings)


def test_w0_skips_rules_not_run(fixture_result):
    # with only R6 active, the R4 suppressions are unverifiable and
    # must not be reported stale
    result = run_lint([str(FIXTURES)], rules=["R6"])
    assert not any(v.file.endswith("suppressed.py")
                   for v in result.warnings)


def test_src_has_no_stale_suppressions():
    from tests.test_analysis import SRC

    result = run_lint([str(SRC)])
    msgs = "\n".join(v.render() for v in result.warnings)
    assert not result.warnings, f"stale suppressions:\n{msgs}"


# ------------------------------------------------- cache_stats plumbing
def test_cache_stats_accessors_are_uniform():
    from repro.control.amortize import SolveCache
    from repro.control.forecast import fit_cache_stats
    from repro.sim.vector.engine import seg_cache_stats

    keys = {"hits", "misses", "evictions", "entries"}
    assert set(SolveCache().cache_stats()) == keys
    assert set(fit_cache_stats()) == keys
    assert set(seg_cache_stats()) == keys


def test_solve_cache_counts_evictions():
    from repro.control.amortize import SolveCache
    from repro.control.provision import ProvisionSolution

    cache = SolveCache(max_entries=2)
    sol = ProvisionSolution(delta=np.zeros((1, 1)), objective=0.0,
                            status="optimal", nodes=0)
    for i in range(4):
        cache.put(bytes([i]), sol)
    st = cache.cache_stats()
    assert st["evictions"] == 2 and st["entries"] == 2


def test_fit_cache_counts_hits_misses_evictions():
    from repro.control import forecast as fc

    fc.clear_fit_cache()
    before = fc.fit_cache_stats()
    assert fc._fit_cache_get(b"sig-a") is None           # miss
    fc._fit_cache_put(b"sig-a", {"c": np.zeros(())})
    assert fc._fit_cache_get(b"sig-a") is not None       # hit
    after = fc.fit_cache_stats()
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 1
