"""``decode_fill``: live rows over the rows the decodes launched, read from
the engine's ``engine.decode`` spans. A tiny engine served under a profile
on the CPU gives a percentage; records without ``batch`` (those of a
program that launches every slot, as in the v5e recording of
``data/engine_spans.json``) give ``None``; and a traced run of the tiny
chat cell reports ``decode_fill.chat``."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
for p in (str(BENCH), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tinycell  # noqa: E402
from benchkit.spec import load_module  # noqa: E402
from tinycell import restore_jax_cache_config  # noqa: E402,F401

decode_fill = load_module(BENCH / "metrics" / "decode_fill.py")


def served_records(tmp_path):
    """The span records of a tiny engine (4 slots) serving requests that
    leave slots dead, under a profile."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.serving import telemetry
    from repro.serving.engine import ServeRequest, ServingEngine

    cfg = ModelConfig(**tinycell.TINY_CONFIG["model_config"])
    ref = load_module(BENCH / "references" / "transformer_lm.py")
    weights = ref.make_weights(tinycell.TINY_CONFIG["config"], 3)
    eng = ServingEngine(cfg, weights, max_batch=4, max_seq=48)
    rng = np.random.default_rng(3)
    for i, n in enumerate((9, 2, 5)):
        eng.submit(ServeRequest(rid=i, prompt=rng.integers(
            0, 512, 16).astype(np.int32), max_new_tokens=n))
    telemetry.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run()
        return eng, telemetry.spans()
    finally:
        telemetry.clear()


def test_the_program_s_spans_give_live_over_launched_rows(tmp_path):
    eng, recs = served_records(tmp_path)
    args = [r["args"] for r in recs if r["name"] == "engine.decode"]
    live = sum(a["rows"] for a in args)
    assert sum(a["batch"] for a in args) == eng.decode_rows_launched
    fill = decode_fill.read(None, recs)
    assert fill == pytest.approx(100 * live / eng.decode_rows_launched)
    assert 0 < fill < 100


def test_records_without_batch_read_nothing():
    recorded = json.loads((HERE / "data" / "engine_spans.json").read_text())
    assert any(r["name"] == "engine.decode" for r in recorded)
    assert decode_fill.read(None, recorded) is None
    assert decode_fill.read(None, []) is None


def test_a_traced_run_of_the_chat_cell_reports_the_fill(tmp_path, capsys):
    import run
    root = tinycell.make_root(tmp_path)
    assert run.main(["--workload", "tiny.chat", "--seed", str(2**33 + 7),
                     "--seconds", "1.5", "--trace", "1"], root=root,
                    require_chip=False) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    fill = result["metrics"]["decode_fill.chat"]
    assert fill["unit"] == "%" and 0 < fill["value"] <= 100
