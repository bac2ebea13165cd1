"""FLOP and byte counts against hand counts at small configurations."""
import json
import pathlib

import pytest

from bench import flops

LLAMA = {"family": "llama", "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 32,
         "num_hidden_layers": 2}


def test_llama_counts_by_hand():
    # per layer: q 8x2x4=64, k 8x1x4=32, v 32, o 2x4x8=64, mlp 3x8x16=384
    # -> 576; two layers 1152; tied head 32x8=256
    assert flops.matmul_params(LLAMA) == 1408
    # 6N + 12 L S d: S = 4 -> 8448 + 12x2x4x8
    assert flops.flops_per_token(LLAMA, 4) == 8448 + 768


def test_blend_bytes_by_hand():
    b = flops.blend_bytes(n=1000, p=4, workers=2, tile=50)
    ext = 250 * 1 + 4 * 250 / 50
    assert b["reduce"] == pytest.approx(2 * (8 * 250 + ext))
    assert b["apply"] == pytest.approx(2 * (12 * 1000 + ext))


@pytest.mark.parametrize("name", ["smollm-135m"])
def test_configured_parameter_count(name):
    """The configuration files state the parameter count the program packs;
    the matmul weights are all of it but the norms."""
    root = pathlib.Path(__file__).resolve().parents[2]
    cfg = json.load(open(root / "bench" / "configs" / f"{name}.json"))
    n = flops.matmul_params(cfg)
    assert 0.98 * cfg["parameters"] < n <= cfg["parameters"]
