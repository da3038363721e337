"""Config registry + analytic parameter counts vs. published numbers."""
import pytest

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, reduce_for_smoke

# (arch, published total params, published active params, rel tolerance)
PUBLISHED = [
    ("starcoder2-7b", 7.4e9, 7.4e9, 0.08),
    ("mamba2-370m", 0.37e9, 0.37e9, 0.15),
    ("zamba2-7b", 7.357e9, 7.357e9, 0.001),
    ("llama4-scout-17b-a16e", 109e9, 17e9, 0.05),
    ("stablelm-12b", 12.1e9, 12.1e9, 0.05),
    ("qwen2-72b", 72.7e9, 72.7e9, 0.03),
    ("deepseek-v3-671b", 671e9, 37e9, 0.03),
    ("gemma-7b", 8.5e9, 8.5e9, 0.05),
    ("whisper-tiny", 0.039e9, 0.039e9, 0.6),  # tiny: vocab padding dominates
    ("pixtral-12b", 12.0e9, 12.0e9, 0.05),
]


def test_registry_complete():
    assert len(ARCHS) == 10
    assert len(SHAPES) == 4
    assert {s.mode for s in SHAPES.values()} == {"train", "prefill", "decode"}


@pytest.mark.parametrize("name,total,active,tol", PUBLISHED)
def test_param_counts_match_published(name, total, active, tol):
    cfg = get_arch(name)
    assert abs(cfg.param_count() - total) / total < tol
    assert abs(cfg.active_param_count() - active) / active < max(tol, 0.1)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_smoke_reduction_bounds(name):
    cfg = reduce_for_smoke(get_arch(name))
    assert cfg.num_layers == 2
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4
    assert cfg.family == get_arch(name).family


def test_unknown_raises():
    with pytest.raises(KeyError):
        get_arch("nope")
    with pytest.raises(KeyError):
        get_shape("nope")
