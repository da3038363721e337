"""A tiny Zamba2 cell on the CPU, added to a temp checkout the way a later
cell is (new files and entries only), with the look for a chip skipped: a
sound run through ``bench/run.py`` is correct, a token altered where the
engine produces it is caught, and the float8 control fails the limit the
program passes. Also: the engine's slot-write span counts what
``benchkit.hybrid_costs`` counts, and the costs' sizes are the served
tree's."""
from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

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
from benchkit import hybrid_costs  # noqa: E402

SEED = 2**33 + 7          # larger than 32 signed bits hold
CELL = "tiny.zamba2"

#: published keys of a tiny Zamba2: two groups of B/C, both shared blocks
#: (A, B, A over hybrid layers 1, 3 and 4), groups of one layer
PUBLISHED = {
    "model_type": "zamba2", "hidden_size": 128, "mamba_expand": 2,
    "n_mamba_heads": 8, "mamba_headdim": 32, "mamba_d_state": 16,
    "mamba_ngroups": 2, "mamba_d_conv": 4, "chunk_size": 16,
    "attention_hidden_size": 256, "attention_head_dim": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "ffn_hidden_size": 256, "adapter_rank": 8, "num_mem_blocks": 2,
    "num_hidden_layers": 5, "hybrid_layer_ids": [1, 3, 4],
    "vocab_size": 512, "hidden_act": "gelu", "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16"}

TINY_ZAMBA = {
    "name": "tiny-zamba2", "source": "tests", "reference": "zamba2_lm",
    "config": PUBLISHED,
    "model_config": {
        "name": "tiny-zamba2", "family": "hybrid", "num_layers": 5,
        "d_model": 128, "num_heads": 4, "num_kv_heads": 4, "head_dim": 64,
        "attn_scale": 32 ** -0.5, "d_ff": 256, "vocab_size": 512,
        "act": "gelu", "norm": "rmsnorm", "norm_eps": 1e-05,
        "tie_embeddings": True, "rope_theta": 10000.0, "ssm_state": 16,
        "ssm_headdim": 32, "ssm_expand": 2, "ssm_chunk": 16,
        "ssm_ngroups": 2, "hybrid_layer_ids": [1, 3, 4],
        "num_mem_blocks": 2, "adapter_rank": 8, "dtype": "bfloat16"},
}

#: the limit lies between the program's widest gap (at most 0.13 on the
#: control test's seeds) and the float8 control's (1.3 and more), CPU host:
#: a bfloat16 hybrid of 5 layers drifts further from its float32
#: reference than the tiny dense cell does (0.004)
TINY_TRAFFIC = dict(tinycell.TINY_TRAFFIC["tiny-chat"],
                    check={"sample_tokens": 40, "max_requests": 4,
                           "widest_logit_gap": 0.4})


def make_root(tmp: Path) -> Path:
    """``tinycell``'s checkout plus the cell ``tiny.zamba2``, which
    reports what ``zamba2-7b.chat`` reports."""
    root = tinycell.make_root(tmp)
    (root / "bench" / "configs" / "tiny-zamba2.json").write_text(
        json.dumps(TINY_ZAMBA))
    (root / "bench" / "traffic" / "tiny-zamba2-chat.json").write_text(
        json.dumps(TINY_TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-zamba2", "source": "tests",
                            "file": "bench/configs/tiny-zamba2.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-zamba2",
                              "traffic": "tiny-zamba2-chat", "chips": 1,
                              "why": "CPU tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "zamba2-7b.chat" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def run_cell(root, capsys, trace=0, seed=SEED):
    import run
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "1.5", "--trace", str(trace)], root=root,
                  require_chip=False)
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("trace", [0, 1])
def test_a_sound_run_is_correct_and_reports_its_metrics(root, capsys,
                                                        trace):
    result, err = run_cell(root, capsys, trace)
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["check"]["requests_unsampled"]["value"] == 0
    assert "programs_built_in_window=0" in err
    if trace:
        assert "breakdown" in result and "window_s" in result["device"]
    else:
        assert {"setup_s", "ttft_p90_ms", "itl_p95_ms"} <= \
            set(result["metrics"])


def test_a_token_altered_where_it_is_produced_fails_the_check(
        root, capsys, monkeypatch):
    from repro.serving import engine as eng_mod

    step = eng_mod.ServingEngine.step
    vocab = PUBLISHED["vocab_size"]

    def altered(self):
        step(self)
        for s in self.slots:                  # the second token of each
            if s.req is not None and len(s.req.tokens) == 2:
                s.req.tokens[-1] = (s.req.tokens[-1] + 1) % vocab

    monkeypatch.setattr(eng_mod.ServingEngine, "step", altered)
    result, _ = run_cell(root, capsys)
    assert result["correct"] is False
    gap = result["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_float8_control_fails_the_check_that_the_program_passes(root):
    """Over the same served tokens the program reads correct and the
    float8 control, put in its place, does not."""
    import run as bench_run
    from benchkit import serving
    from benchkit.record import Run
    from benchkit.spec import load_cell, load_module
    from benchkit.tracing import Tracer

    cell = load_cell(root, CELL)
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
        del state
        gc.collect()


def test_every_piece_of_the_real_cell_is_found_by_name():
    from benchkit.spec import load_cell
    c = load_cell(REPO, "zamba2-7b.chat")
    assert c.reference().token_stats_fn and c.driver().check
    names = {m["name"] for m in c.end_to_end + c.per_layer}
    assert {"setup_s", "ttft_p90_ms", "itl_p95_ms", "decode_roofline.zamba2",
            "slot_write_roofline.zamba2"} <= names
    for m in c.end_to_end + c.per_layer:
        assert callable(c.metric(m["name"]).read)
    published = c.config["config"]
    assert published["num_hidden_layers"] == 41
    assert published["layers_block_type"].count("hybrid") == \
        len(published["hybrid_layer_ids"]) == 6


# ------------------------------------------------------------------ costs
def _weights():
    from benchkit.spec import load_module
    ref = load_module(BENCH / "references" / "zamba2_lm.py")
    return ref.make_weights(PUBLISHED, 3)


def test_weight_bytes_are_the_served_trees():
    import jax
    w = _weights()
    assert hybrid_costs.weight_bytes(PUBLISHED) == sum(
        a.nbytes for a in jax.tree.leaves(w))


@pytest.mark.parametrize("S", [16, 32])
def test_the_slot_write_span_counts_what_hybrid_costs_counts(tmp_path, S):
    """The ``engine.slot_write`` span's ``state_bytes`` and ``kv_bytes``,
    counted by the engine from the prefill's cache tree, equal
    ``hybrid_costs.slot_write_bytes`` for the prompt's length."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.serving import telemetry
    from repro.serving.engine import ServeRequest, ServingEngine

    cfg = ModelConfig(**TINY_ZAMBA["model_config"])
    eng = ServingEngine(cfg, _weights(), max_batch=2, max_seq=48)
    prompt = np.random.default_rng(S).integers(0, 512, S).astype(np.int32)
    eng.submit(ServeRequest(rid=0, prompt=prompt, max_new_tokens=2))
    telemetry.clear()
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run()
        writes = [r["args"] for r in telemetry.spans()
                  if r["name"] == "engine.slot_write"]
    finally:
        telemetry.clear()
    assert writes == [hybrid_costs.slot_write_bytes(PUBLISHED, S)]


def test_decode_and_prefill_costs_grow_as_the_work_does():
    c = PUBLISHED
    one = hybrid_costs.decode_bytes(c, [10])
    assert hybrid_costs.decode_bytes(c, [10, 10]) - one == \
        2 * hybrid_costs.state_bytes_per_seq(c) \
        + 10 * hybrid_costs.kv_bytes_per_token(c)
    assert hybrid_costs.decode_flops(c, [10, 20]) > \
        2 * hybrid_costs.token_matmul_flops(c)
    f16, f32 = (hybrid_costs.prefill_flops(c, S) for S in (16, 32))
    assert f32 > 2 * f16 - hybrid_costs.head_flops(c)
