"""Model-shape presets: the reference benchmark's shape table as configs
(reference test_ag_gemm.py:149-156) + the interpreted layer-check mirror."""

import subprocess
import sys
import os

import pytest

from triton_dist_tpu.models import presets
from triton_dist_tpu.models.tp_transformer import MoETransformerConfig


@pytest.mark.parametrize("name", presets.PRESETS)
def test_preset_shapes_consistent(name):
    cfg = presets.preset(name)
    assert cfg.n_q_heads % cfg.n_kv_heads == 0
    assert cfg.head_dim % 128 == 0  # lane-aligned heads on TPU
    assert cfg.ffn > cfg.hidden
    # every preset must admit the TP degrees the reference benches (8 GPUs)
    presets.validate_tp(cfg, 8)


def test_preset_tp_validation_trips():
    cfg = presets.preset("llama-3.1-8b")
    with pytest.raises(ValueError):
        presets.validate_tp(cfg, 3)  # 3 divides neither kv heads nor ffn


def test_moe_preset_class():
    cfg = presets.preset("moe-gelu-8x")
    assert isinstance(cfg, MoETransformerConfig)
    assert (cfg.n_experts, cfg.topk) == (8, 2)


def test_unknown_preset_raises():
    with pytest.raises(KeyError):
        presets.preset("nope-13b")


def test_ep_preset_variants():
    """MoE presets carry their deployment: ep=True / ep_outer= build the
    expert-parallel configs; the :ep / :ep-hier name suffixes spell the
    same for CLI callers; dense presets reject EP."""
    from triton_dist_tpu.models import EPMoETransformerConfig

    flat = presets.preset("moe-gelu-8x:ep")
    assert isinstance(flat, EPMoETransformerConfig) and flat.ep_outer is None
    hier = presets.preset("moe-gelu-8x:ep-hier")
    assert isinstance(hier, EPMoETransformerConfig)
    assert hier.ep_outer == "dcn"
    kw = presets.preset("moe-gelu-8x", ep=True)
    assert isinstance(kw, EPMoETransformerConfig) and kw.ep_outer is None
    kw2 = presets.preset("moe-gelu-8x", ep_outer="dp")
    assert kw2.ep_outer == "dp"
    with pytest.raises(ValueError, match="dense"):
        presets.preset("llama-3.1-8b", ep=True)
    with pytest.raises(KeyError):
        presets.preset("nope-13b:ep")


@pytest.mark.slow
def test_layer_check_interpreted():
    """CI mirror of scripts/layer_check.py (tiny seq, interpreter)."""
    env = dict(os.environ, TDT_LAYER_CHECK_INTERPRET="1")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "layer_check.py")],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
