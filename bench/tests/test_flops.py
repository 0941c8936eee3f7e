"""FLOP counts against hand counts."""

import pytest

from bench import flops

BERT_BASE = {"num_layers": 12, "d_model": 768, "num_heads": 12,
             "d_ff": 3072, "vocab_size": 30522}


def test_bert_base_request_matches_hand_count():
    # per token and layer: QKV 2*768*2304, output 2*768*768, MLP
    # 2*2*768*3072, attention 2*2*128*768 (QK^T and AV over 128 keys)
    per_layer = (2 * 768 * 2304 + 2 * 768 * 768 + 4 * 768 * 3072
                 + 4 * 128 * 768)
    head = 2 * 768 * 30522
    per_token = 12 * per_layer + head
    assert per_token == 221_469_696
    assert flops.expert_request_flops(BERT_BASE, 128) == 128 * per_token


def test_bert_base_bucket_of_eight():
    assert 8 * flops.expert_request_flops(BERT_BASE, 128) \
        == 226_784_968_704


def test_router_request():
    r = {"num_layers": 4, "d_model": 128, "num_heads": 4, "d_ff": 512}
    per_layer = 2 * 128 * 384 + 2 * 128 * 128 + 4 * 128 * 512 \
        + 4 * 128 * 128
    assert flops.router_request_flops(r, 128) == 128 * 4 * per_layer


def test_decision_head():
    f = flops.decision_head_flops(256, 128, 128, 11, 2, cascade=False)
    assert f == 2 * 256 * (128 * 128 + 128 * 11) + 2 * 256 * 2 * 11
    fc = flops.decision_head_flops(256, 128, 128, 11, 2, cascade=True)
    assert fc == f + 2 * 256 * (128 * 128 + 128 * 11)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9 imaginary")
