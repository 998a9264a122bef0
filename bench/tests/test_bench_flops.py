"""Operations and bytes from shapes, and the table of peaks."""

import json
from pathlib import Path

import pytest

from harness import flops as F
from harness.device import PEAKS, peak_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def m3vit():
    return json.loads((CONFIGS / "m3vit.json").read_text())


def test_m3vit_depth_prediction_by_hand():
    # 128 tokens, d 192, 3 heads x 64, f 768, 16 experts top-4, 12 blocks
    t, d, f = 128, 192, 768
    embed = 2 * t * 768 * d                          # 16x16x3 patches
    attn = 2 * t * d * d * 4 + 2 * 2 * 3 * t * t * 64
    mlp = 2 * 2 * t * d * f
    moe = 2 * t * d * 16 + 4 * 2 * 2 * t * d * f
    head = 2 * t * d * 256
    by_hand = embed + 12 * attn + 6 * mlp + 6 * moe + head
    assert F.prediction_flops(m3vit(), 1) == by_hand
    assert F.prediction_flops(m3vit(), 1) == pytest.approx(2.9e9, rel=0.01)
    # semseg's head is 19 classes per pixel instead of one depth
    assert F.prediction_flops(m3vit(), 0) - by_hand == 2 * t * d * 256 * 18


def test_moe_counts_top_k_not_capacity():
    cfg = m3vit()
    many = dict(cfg, moe=dict(cfg["moe"], num_experts=256))
    # 16x the experts: only the gate grows, the routed work does not
    assert F.moe_flops(many) - F.moe_flops(cfg) == 2 * 128 * 192 * 240


def test_dense_block_cost_and_roofline():
    ops, nbytes = F.dense_block_cost(m3vit(), batch=4)
    assert ops == 4 * (F.attention_flops(m3vit()) + F.mlp_flops(m3vit()))
    weights = (4 * 192 * 192 + 2 * 192 * 768) * 2 + (5 * 192 + 768) * 4
    assert nbytes == weights + 2 * 4 * 128 * 192 * 2
    t, bound = F.roofline_s(ops, nbytes, peak_for("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(ops / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peak"):
        peak_for("TPU v99 imaginary")
    assert PEAKS["TPU v5 lite"].flops_per_s == 197e12
    assert PEAKS["TPU v5 lite"].hbm_bytes_per_s == 819e9
