"""A checkout in a temp directory with tiny cells, for CPU tests.

It copies ``bench/`` and the real ``BENCHMARK.json`` metric entries, then
adds a tiny configuration and tiny traffic mixes of its own as new files
and entries only, the way a later cell is added.
"""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_CONFIG = {
    "name": "tiny-lm", "source": "tests", "reference": "transformer_lm",
    "config": {"model_type": "starcoder2", "hidden_size": 128,
               "intermediate_size": 256, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "vocab_size": 512,
               "hidden_act": "gelu_pytorch_tanh", "norm_type": "layer_norm",
               "norm_epsilon": 1e-05, "rope_theta": 1000000.0,
               "use_bias": True, "tie_word_embeddings": False,
               "torch_dtype": "bfloat16"},
    "model_config": {"name": "tiny-lm", "family": "dense", "num_layers": 2,
                     "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
                     "head_dim": 32, "d_ff": 256, "vocab_size": 512,
                     "act": "gelu", "norm": "layernorm", "norm_eps": 1e-05,
                     "use_qkv_bias": True, "rope_theta": 1000000.0,
                     "dtype": "bfloat16"},
}

#: the tiny cells' limit lies between the program's widest gap (at most
#: 0.004 on seeds 1-3) and the float8 control's (0.13-0.17), CPU host
TINY_TRAFFIC = {
    "tiny-chat": {
        "driver": "serve_open",
        "prompt": {"median": 24, "sigma": 0.5, "buckets": [16, 32]},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "arrivals": {"kind": "poisson", "rate_per_s": 12.0},
        "engine": {"max_batch": 4, "max_seq": 48, "scheduler": "fcfs"},
        "drain_s": 30, "trace": {"start_frac": 0.2, "seconds": 0.5},
        "check": {"sample_tokens": 40, "max_requests": 4,
                  "widest_logit_gap": 0.05}},
    "tiny-backlog": {
        "driver": "serve_backlog",
        "prompt": {"median": 24, "sigma": 0.5, "buckets": [16, 32]},
        "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
        "arrivals": {"kind": "backlog", "waiting": 8, "pool": 16},
        "engine": {"max_batch": 4, "max_seq": 48, "scheduler": "fcfs"},
        "trace": {"start_frac": 0.2, "seconds": 0.5},
        "check": {"sample_tokens": 24, "max_requests": 4,
                  "widest_logit_gap": 0.05}},
}


def make_root(tmp: Path) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json has the cells
    ``tiny.chat`` and ``tiny.backlog`` besides the real ones."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    (root / "bench" / "configs" / "tiny-lm.json").write_text(
        json.dumps(TINY_CONFIG))
    for name, mix in TINY_TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-lm", "source": "tests",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "CPU tests"})
    cells = {"tiny.chat": "tiny-chat", "tiny.backlog": "tiny-backlog"}
    for cell, traffic in cells.items():
        spec["workloads"].append({"name": cell, "config": "tiny-lm",
                                  "traffic": traffic, "chips": 1,
                                  "why": "CPU tests"})
    # the tiny cells report what the cell of their driver reports
    twin = {"sc2-7b.chat": "tiny.chat", "sc2-7b.code-backlog": "tiny.backlog"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twin[w] for w in m["workloads"] if w in twin]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def restore_jax_cache_config():
    """A run points JAX's compilation cache into its checkout; put the
    process's settings back, so later tests in this worker see none of it."""
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
