"""The benchmark harness on the CPU: lookup by name, refusal without a chip,
the StarCoder2 cost functions and the client-side arithmetic."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
for p in (str(BENCH), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tinycell  # noqa: E402
from tinycell import restore_jax_cache_config  # noqa: E402,F401
from benchkit import costs, record  # noqa: E402
from benchkit.spec import load_cell  # noqa: E402


def sc2():
    return json.loads((BENCH / "configs" / "starcoder2-7b.json")
                      .read_text())["config"]


# ------------------------------------------------------------------ lookup
@pytest.mark.parametrize("cell", ["sc2-7b.chat", "sc2-7b.code-backlog"])
def test_every_piece_of_a_cell_is_found_by_name(cell):
    c = load_cell(REPO, cell)
    assert c.driver().window and c.driver().prepare and c.driver().check
    assert c.reference().token_stats_fn
    for m in c.end_to_end + c.per_layer:
        assert callable(c.metric(m["name"]).read)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


def test_a_cell_added_in_a_temp_dir_is_found(tmp_path):
    root = tinycell.make_root(tmp_path)
    c = load_cell(root, "tiny.chat")
    assert c.config["config"]["hidden_size"] == 128
    assert c.traffic["driver"] == "serve_open"
    assert "ttft_p90_ms" in {m["name"] for m in c.end_to_end}
    with pytest.raises(KeyError):
        load_cell(root, "tiny.absent")


def test_a_metric_split_by_cells_shares_one_reader(tmp_path):
    """``idle_share.chat`` is read by ``idle_share.py``; a split with a
    file of its own, added later, is read by that file."""
    root = tinycell.make_root(tmp_path)
    c = load_cell(root, "tiny.chat")
    assert c.metric("idle_share.chat").__file__.endswith("idle_share.py")
    (root / "bench" / "metrics" / "idle_share.tiny.py").write_text(
        "def read(run):\n    return 1.0\n")
    assert c.metric("idle_share.tiny").read(None) == 1.0
    with pytest.raises(FileNotFoundError):
        c.metric("absent_metric.chat")


def test_benchmark_json_names_only_files_that_exist():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert any((BENCH / "metrics" / f"{n}.py").is_file()
                   for n in (m["name"], m["name"].split(".")[0]))
    moves = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in moves for m in spec["per_layer"])


# ---------------------------------------------------------- no chip, no run
def _run(cwd: Path, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2-7b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_a_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_a_device_missing_from_the_peaks_table_is_an_error():
    import run
    with pytest.raises(KeyError):
        run.peaks_for("TPU v99", require_chip=True)
    assert run.peaks_for("TPU v5 lite", True)["hbm_bytes_per_s"] == 819e9


# ------------------------------------------------------------------- costs
def test_starcoder2_weight_and_kv_bytes():
    c = sc2()
    assert abs(costs.weight_bytes(c) / 14.800e9 - 1) < 1e-3
    assert costs.kv_bytes_per_token(c) == 65_536


def test_starcoder2_flops_per_token():
    c = sc2()
    # 7.17e9 matmul weights per token through layers and head
    assert costs.token_matmul_flops(c) == 2 * (32 * 217_055_232
                                               + 4608 * 49152)
    # decode: one token per row, attention over its live positions
    assert costs.decode_flops(c, [10, 20]) == \
        2 * costs.token_matmul_flops(c) + 4 * 32 * 36 * 128 * 30
    # a decode step reads every weight but the embedding table once
    b = costs.decode_bytes(c, [1000] * 8)
    assert 14.3e9 < b - 8 * 1000 * 65_536 < 14.4e9
    # causal prefill: each position attends to itself and what precedes
    assert costs.prefill_flops(c, 2) - costs.prefill_flops(c, 1) == \
        2 * 32 * 217_055_232 + costs.attn_flops(c, 2)


# --------------------------------------------------------- client arithmetic
def _sent(send, times):
    return SimpleNamespace(req=SimpleNamespace(send_s=send), times=times)


def test_ttft_tail_pools_all_requests_and_failures_count_as_misses():
    sent = [_sent(float(i), [i + 0.1 * (i + 1), i + 1.0]) for i in range(9)]
    sent.append(_sent(9.0, []))                       # never answered
    run = record.Run(config={}, peaks={}, setup_s=0,
                     window_s=12.0, sent=sent)
    x = run.ttfts()
    assert np.allclose(x[:9], 0.1 * np.arange(1, 10))
    assert x[9] == 12.0                               # the longest wait
    assert record.ttft_ms(run, 100) == pytest.approx(12_000)
    assert record.ttft_ms(run, 50) == pytest.approx(
        np.percentile(list(0.1 * np.arange(1, 10)) + [12.0], 50) * 1e3)


def test_token_gaps_are_pooled_over_requests():
    sent = [_sent(0.0, [0.1, 0.2, 0.4]), _sent(0.0, [1.0, 1.5]),
            _sent(0.0, [2.0])]
    run = record.Run(config={}, peaks={}, setup_s=0,
                     window_s=1, sent=sent)
    assert sorted(np.round(run.token_gaps(), 6)) == [0.1, 0.2, 0.5]
    assert record.itl_ms(run, 100) == pytest.approx(500)


def test_tokens_per_second_counts_prefill_and_output_tokens():
    from benchkit.serving import Step
    steps = [Step(0.0, 0.5, prefill_lens=[100, 50], decode_rows=[7, 8]),
             Step(0.5, 1.0, decode_rows=[9, 10, 11])]
    run = record.Run(config={}, peaks={}, setup_s=0,
                     window_s=2.0, steps=steps)
    # 150 prompt tokens, 2 first tokens from the prefills, 5 decoded
    assert run.tokens_per_s() == pytest.approx(157 / 2.0)


# ----------------------------------------------------------------- traffic
def _gen():
    from benchkit.spec import load_module
    return load_module(BENCH / "traffic" / "generator.py")


@pytest.mark.parametrize("mix", ["chat", "code-backlog"])
def test_every_seed_asks_for_the_same_work_in_another_order(mix):
    gen = _gen()
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    a = gen.requests(m, 64, seed=2**33 + 1, vocab=100)
    b = gen.requests(m, 64, seed=5, vocab=100)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(len(r.prompt) in m["prompt"]["buckets"] for r in a)
    assert all(m["output"]["min"] <= r.max_new <= m["output"]["max"]
               for r in a)
    assert all(len(r.prompt) + r.max_new <= m["engine"]["max_seq"]
               for r in a)


def test_open_loop_gaps_come_in_a_free_order_and_cluster():
    """No strata: each seed orders the same gaps another way, and short
    gaps run together as a Poisson process's do."""
    gen = _gen()
    m = json.loads((BENCH / "traffic" / "chat.json").read_text())
    gaps = []
    for seed in (2**33 + 3, 4, 5):
        sends = [r.send_s for r in gen.open_loop(m, 51.0, seed, 100)]
        gaps.append(np.diff(sends + [51.0]))      # the last closes the window
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert not np.allclose(gaps[0], gaps[1])
    short = [g < np.quantile(g, 0.25) for g in gaps]
    assert any(np.any(s[1:] & s[:-1]) for s in short)


def test_open_loop_sends_fill_the_window_at_the_mix_rate():
    gen = _gen()
    m = json.loads((BENCH / "traffic" / "chat.json").read_text())
    reqs = gen.open_loop(m, 51.0, seed=9, vocab=100)
    sends = [r.send_s for r in reqs]
    assert len(reqs) == round(m["arrivals"]["rate_per_s"] * 51.0)
    assert sends[0] == 0.0 and sends == sorted(sends) and sends[-1] < 51.0
