"""The operations-and-bytes functions against hand counts."""

import json
import os

import pytest

from harness import manifest, opsbytes


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_medium_forward_by_hand():
    # Per layer and token: 4 projections 2*4*1024^2 = 8,388,608; MLP
    # 2*2*1024*4096 = 16,777,216; causal attention 2*2*1024*(1025/2) =
    # 2,099,200. 24 layers: 654,360,576. Tied head 2*1024*50257 =
    # 102,926,336.
    cfg = _config("gpt2-medium")
    assert opsbytes.lm_forward_flops_per_token(cfg, 1024) == 757_286_912
    assert opsbytes.lm_train_flops_per_item(cfg, {"seq_len": 1024}) == (
        3 * 757_286_912
    )


def test_resnet50_forward_by_hand():
    cfg = _config("resnet50")
    total = opsbytes.resnet50_forward_flops_per_image(cfg)
    # The published count for ResNet-50 at 224^2 is 4.09 G multiply-adds.
    assert total == pytest.approx(2 * 4.09e9, rel=0.01)
    # Stem alone: 112*112 outputs, 7*7*3 inputs each, 64 filters.
    tiny = dict(cfg, num_filters=64)
    stem = 2 * 112 * 112 * 49 * 3 * 64
    assert stem == 236_027_904 and total > stem
    assert opsbytes.resnet50_train_flops_per_item(tiny, {}) == 3 * total


@pytest.mark.parametrize("kind,products,tensors",
                         [("fwd", 2, 4), ("dq", 3, 6), ("dkv", 4, 7)])
def test_flash_kernel_cost_counts_the_causal_half(kind, products, tensors):
    cost = opsbytes.flash_kernel_cost(kind, rows=8, heads=16, seq_len=1024,
                                      head_dim=64)
    pairs = 1024 * 1025 // 2
    assert cost["flops"] == 8 * 16 * products * 2 * pairs * 64
    assert cost["bytes"] == 8 * 16 * tensors * 1024 * 64 * 2
    square = 8 * 16 * products * 2 * 1024 * 1024 * 64
    assert cost["flops"] < 0.51 * square


def test_roofline_names_the_bound():
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert opsbytes.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peaks) \
        == (1.0, "compute")
    assert opsbytes.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peaks) \
        == (1.0, "memory")
