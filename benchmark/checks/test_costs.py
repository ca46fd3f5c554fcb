"""The operation counts against counts made by hand."""
import json
import os

import pytest

from lib import spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def costs(config, batch):
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    return spec.reference(cfg["reference"]).costs(cfg, batch)


def test_one_resnet_bottleneck_block_by_hand():
    """stage1_unit2 on one image: a 56x56 map, 256 -> 64 -> 64 -> 256.
    1x1: 56*56*256*64 multiply-adds, 3x3: 56*56*9*64*64, 1x1:
    56*56*64*256; two operations each, forward plus twice that back."""
    by = costs("resnet50", 1)["by_layer"]
    macs = {"conv1": 3136 * 256 * 64, "conv2": 3136 * 9 * 64 * 64,
            "conv3": 3136 * 64 * 256}
    assert macs == {"conv1": 51380224, "conv2": 115605504,
                    "conv3": 51380224}
    for name, m in macs.items():
        assert by["stage1_unit2_" + name] == 6 * m
    # a unit that strides: stage2_unit1's 3x3 reads 56x56, writes 28x28
    assert by["stage2_unit1_conv2"] == 6 * 784 * 9 * 128 * 128
    assert by["stage2_unit1_sc"] == 6 * 784 * 256 * 512
    assert by["conv0"] == 6 * 112 * 112 * 49 * 3 * 64


def test_resnet50_is_the_known_four_gigamacs():
    c = costs("resnet50", 256)
    per_image = c["model_flops"] / 256
    # 4.09 G multiply-adds forward, the figure usually quoted for it
    assert per_image / 6 == pytest.approx(4.09e9, rel=0.01)
    assert c["matmul"]["flops"] == c["model_flops"]


def test_one_gpt2_medium_layer_by_hand():
    """A token in one block: qkv 1024x3072, proj 1024x1024, two of
    1024x4096: 12,582,912 multiply-adds; attention at T=1024 over the
    causal half: (2 forward + 4 backward products) x 16 heads x 64 x
    T*T/2 a sequence."""
    by = costs("gpt2-medium", 1)["by_layer"]
    dense = sum(by["l7_" + n] for n in
                ("attn_qkv", "attn_proj", "mlp1", "mlp2"))
    assert dense == 6 * 1024 * 12582912
    assert by["l7_attn"] == 6 * 2 * 16 * 64 * (1024 * 1024 // 2)
    assert by["head"] == 6 * 1024 * 1024 * 50257


def test_gpt2_medium_per_token():
    c = costs("gpt2-medium", 8)
    per_token = c["model_flops"] / 8192
    assert per_token == pytest.approx(2.27e9, rel=0.01)
    assert c["attention"]["flops"] / c["model_flops"] \
        == pytest.approx(0.066, abs=0.005)
