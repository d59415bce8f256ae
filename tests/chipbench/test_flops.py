"""Operation and byte counts against hand-worked small shapes."""

import pytest

from chipbench import flops, harness

TINY = {"d_model": 4, "n_heads": 2, "n_layers": 3, "d_ff": 16, "vocab": 10,
        "max_len": 8, "eps": 1e-5}


def test_lm_matmul_params_by_hand():
    # a layer: qkv 4x12, out 4x4, mlp 4x16 + 16x4 = 48+16+64+64 = 192
    assert flops.lm_matmul_params(TINY) == 3 * 192


def test_lm_token_flops_by_hand():
    # 2 x 576 for the maps, 4 L D ctx = 4*3*4*5 = 240, head 2*10*4 = 80
    assert flops.lm_token_flops(TINY, 5) == 2 * 576 + 240 + 80
    assert flops.lm_token_flops(TINY, 5, with_head=False) == 2 * 576 + 240


def test_lm_prefill_flops_by_hand():
    # 3 new tokens after 2 cached: keys 3+4+5 = 12; head once
    assert flops.lm_prefill_flops(TINY, 3, 2) == \
        2 * 576 * 3 + 4 * 3 * 4 * 12 + 80


def test_lm_bytes_by_hand():
    assert flops.lm_kv_bytes_per_token(TINY) == 2 * 3 * 4 * 2
    assert flops.lm_weight_bytes(TINY) == 2 * (576 + 40) + 4 * 3 * 4 * 4


def test_cerebras_1p3b_sizes():
    cfg = harness.load_json(
        harness.HERE + "/configs/cerebras-gpt-1.3b-standin.json")
    d = flops.lm_dims(cfg)
    assert d["d_model"] // d["n_heads"] == 128
    assert flops.lm_matmul_params(d) == 24 * 12 * 2048 * 2048
    # one 128-token block of K and V over 24 layers, bf16: 25.2 MB
    assert flops.lm_kv_bytes_per_token(d) * 128 == pytest.approx(25.2e6,
                                                                 rel=0.01)


def test_resnet50_forward_macs_match_the_paper():
    convs = flops.resnet_convs(224)
    assert len(convs) == 53                       # 49 + 4 projections
    macs = sum(k * k * cin * cout * ho * ho for k, cin, cout, ho in convs)
    # He et al. table 1: 3.8e9 "FLOPs" (multiply-adds) for the 50-layer
    # net; stride on the first 1x1 as in v1
    assert 3.7e9 < macs < 3.95e9
    step = flops.resnet_train_flops(2)
    assert step == pytest.approx(2 * (3 * 2 * (macs + 2048 * 1000)
                                      - 2 * 49 * 3 * 64 * 112 * 112))
