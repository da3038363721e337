"""Whole runs of tiny serving cells on the CPU, with the look for a chip
skipped: a sound run is correct, a token altered where the engine produces
it is caught, and the float8 control fails the limit the program passes."""
from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
for p in (str(BENCH), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tinycell  # noqa: E402
from tinycell import restore_jax_cache_config  # noqa: E402,F401

SEED = 2**33 + 5          # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("bench"))


def run_cell(root, capsys, cell, trace=0, seed=SEED):
    import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1.5", "--trace", str(trace)], root=root,
                  require_chip=False)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    return result, out.err


@pytest.mark.parametrize("cell,trace", [("tiny.chat", 0), ("tiny.chat", 1),
                                        ("tiny.backlog", 0)])
def test_a_sound_run_is_correct_and_reports_its_metrics(root, capsys, cell,
                                                        trace):
    result, err = run_cell(root, capsys, cell, trace)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "check"
    assert err.strip().splitlines()[-1].startswith("check ")
    assert "programs_built_in_window=0" in err
    if trace:
        assert "breakdown" in result and "window_s" in result["device"]
    else:
        assert "setup_s" in result["metrics"]
        assert len(result["metrics"]) >= 2


def test_a_token_altered_where_it_is_produced_fails_the_check(
        root, capsys, monkeypatch):
    from repro.serving import engine as eng_mod

    step = eng_mod.ServingEngine.step
    vocab = tinycell.TINY_CONFIG["config"]["vocab_size"]

    def altered(self):
        step(self)
        for s in self.slots:                  # the second token of each
            if s.req is not None and len(s.req.tokens) == 2:
                s.req.tokens[-1] = (s.req.tokens[-1] + 1) % vocab

    monkeypatch.setattr(eng_mod.ServingEngine, "step", altered)
    result, err = run_cell(root, capsys, "tiny.chat")
    assert result["correct"] is False
    gap = result["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_float8_control_fails_the_check_that_the_program_passes(root):
    """The control at a test's size, put in the program's place in the
    comparison that decides ``correct``: over the same served tokens the
    program reads correct and the control does not."""
    import run as bench_run
    from benchkit import serving
    from benchkit.record import Run
    from benchkit.spec import load_cell, load_module
    from benchkit.tracing import Tracer

    cell = load_cell(root, "tiny.chat")
    bench_run.configure_cache(root)
    gen = load_module(BENCH / "traffic" / "generator.py")
    driver = cell.driver()
    for seed in (SEED, 7, 8):
        state = driver.prepare(cell, seed, 1.5, gen)
        run = Run(config=cell.config["config"], peaks={}, setup_s=0.0,
                  window_s=1.5)
        driver.window(state, cell, 1.5, Tracer(False, 0, 0, ""), run)
        state["engine"].free()
        g = serving.compare(state, cell, seed, fp8_control=True)
        program = serving.limits(g, state, cell)
        control = serving.limits(g, state, cell, "control")
        assert bench_run.correct(program), program
        assert not bench_run.correct(control), control
        assert control["widest_logit_gap"][0] > \
            control["widest_logit_gap"][1]
        del state
        gc.collect()
