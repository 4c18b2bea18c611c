"""``flops.py`` against values worked out by hand (two operations per
multiply-accumulate)."""

import json
import os

import pytest

from benchmarks import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def test_one_bottleneck_block():
    # First block of ResNet-50's first stage: 56x56, 64 -> 64 -> 64 -> 256,
    # stride 1, projection shortcut 64 -> 256.
    want = (2 * 56 * 56 * 64 * 64            # 1x1 reduce      25,690,112
            + 2 * 56 * 56 * 9 * 64 * 64      # 3x3            231,211,008
            + 2 * 56 * 56 * 64 * 256         # 1x1 expand     102,760,448
            + 2 * 56 * 56 * 64 * 256)        # projection     102,760,448
    assert want == 462_422_016
    assert flops.resnet_bottleneck_flops(56, 64, 64, 1) == want
    # An identity block of the same stage has no projection.
    assert flops.resnet_bottleneck_flops(56, 256, 64, 1) == (
        2 * 56 * 56 * 256 * 64 + 231_211_008 + 102_760_448)


def test_resnet50_is_4_1_giga_multiply_accumulates():
    fwd = flops.resnet_forward_flops(224, (3, 4, 6, 3), 64, 1000)
    # He et al. Table 1 gives 3.8e9 "FLOPs" for the 50-layer net, counting
    # multiply-accumulates; v1.5's stride placement adds about 7%.
    assert 4.0e9 < fwd / 2 < 4.2e9
    stem = 2 * 112 * 112 * 49 * 3 * 64
    assert flops.resnet_train_flops(224, (3, 4, 6, 3), 64, 1000) \
        == 3 * fwd - stem


def test_one_starcoder2_layer_per_token():
    want = (2 * 3072 * (24 + 2 * 2) * 128    # q, k, v        22,020,096
            + 2 * 24 * 128 * 3072            # o              18,874,368
            + 4 * 3072 * 12288               # up and down   150,994,944
            + 2 * (4096 + 1) * 24 * 128)     # scores, values 25,171,968
    assert want == 217_061_376
    assert flops.gpt_layer_forward_flops(
        4096, embed=3072, heads=24, kv_heads=2, head_dim=128,
        mlp=12288) == want
    per_token = flops.gpt_train_flops(
        4096, layers=6, embed=3072, heads=24, kv_heads=2, head_dim=128,
        mlp=12288, vocab=49152)
    assert per_token == 3 * (6 * want + 2 * 3072 * 49152)


def test_one_flash_call():
    pairs = 2 * 24 * (4096 * 4097 // 2)
    assert pairs == 402_751_488
    fwd = flops.flash_forward_cost(2, 4096, heads=24, kv_heads=2,
                                   head_dim=128)
    assert fwd["ops"] == 4 * 128 * pairs == 206_208_761_856
    assert fwd["bytes"] == 8192 * 128 * 2 * 2 * 26 + 8192 * 24 * 4 \
        == 109_838_336
    bwd = flops.flash_backward_cost(2, 4096, heads=24, kv_heads=2,
                                    head_dim=128)
    assert bwd["ops"] == 10 * 128 * pairs
    assert bwd["bytes"] == 8192 * 128 * 2 * 4 * 26 + 8192 * 24 * 4


def test_roofline_names_its_bound():
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    seconds, bound = flops.roofline_seconds(
        {"ops": 197e12, "bytes": 819e9 / 2}, peak)
    assert (seconds, bound) == (pytest.approx(1.0), "compute")
    seconds, bound = flops.roofline_seconds(
        {"ops": 197e12 / 4, "bytes": 819e9}, peak)
    assert (seconds, bound) == (pytest.approx(1.0), "memory")
