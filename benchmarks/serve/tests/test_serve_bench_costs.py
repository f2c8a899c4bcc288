"""Operations, bytes and model FLOPs against hand counts, and the peak
table."""

import json
import os

import pytest

from serve_bench_util import BENCH

import costs


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen2_1_5b_hand_counts():
    cfg = config("qwen2-1.5b")
    # One layer: q 1536x1536, k and v 256x1536, o 1536x1536, gate/up/down
    # 8960x1536 each; biases on q, k, v; two norms.
    attn = 1536 * 1536 + 2 * 256 * 1536 + 1536 * 1536
    mlp = 3 * 8960 * 1536
    layer = attn + mlp + (1536 + 2 * 256) + 2 * 1536
    assert costs.layer_params(cfg) == layer
    total = 28 * layer + 151936 * 1536 + 1536  # tied head: embedding once
    assert costs.param_count(cfg) == total
    assert round(total / 1e9, 2) == 1.54
    assert round(total * 2 / 1e9, 2) == 3.09
    # 2 (K, V) x 2 KV heads x 128 x 28 layers x 2 bytes = 28 KiB.
    assert costs.kv_bytes_per_token(cfg) == 28 * 1024


# qwen2.5-14b's published widths (hf Qwen/Qwen2.5-14B config.json) at 8 of
# its 48 layers.
QWEN2_5_14B_D8 = {
    "hidden_size": 5120, "intermediate_size": 13824, "num_attention_heads": 40,
    "num_key_value_heads": 8, "num_hidden_layers": 8, "vocab_size": 152064,
    "tie_word_embeddings": False,
}


def test_qwen2_5_14b_d8_hand_counts():
    cfg = QWEN2_5_14B_D8
    attn = 5120 * 5120 + 2 * 1024 * 5120 + 5120 * 5120
    mlp = 3 * 13824 * 5120
    layer = attn + mlp + (5120 + 2 * 1024) + 2 * 5120
    assert costs.layer_params(cfg) == layer
    assert round(layer / 1e6, 1) == 275.3
    total = costs.param_count(cfg)
    assert total == 8 * layer + 2 * 152064 * 5120 + 5120  # untied head
    assert round(total / 1e9, 2) == 3.76
    assert round(total * 2 / 1e9, 2) == 7.52
    # 2 x 8 KV heads x 128 x 8 layers x 2 bytes = 32 KiB.
    assert costs.kv_bytes_per_token(cfg) == 32 * 1024


def test_matmul_cost_and_least_time():
    ops, byts = costs.matmul_cost(64, 256, 1536)
    assert ops == 2 * 64 * 256 * 1536
    assert byts == 256 * 1536 * 2 + 64 * 1536 * 2 + 64 * 256 * 4
    pk = costs.peaks("TPU v5 lite")
    t, bound = costs.least_time(ops, byts, pk)
    assert bound == "memory" and t == pytest.approx(byts / 819e9)
    t, bound = costs.least_time(*costs.matmul_cost(8192, 8192, 8192), pk)
    assert bound == "compute" and t == pytest.approx(2 * 8192**3 / 197e12)


def test_projection_cost_is_every_layer_at_rows():
    cfg = config("qwen2-1.5b")
    ops, byts = costs.projection_cost(cfg, 1)
    mats = 28 * (1536 * 1536 * 2 + 2 * 256 * 1536 + 3 * 8960 * 1536)
    assert ops == 2 * mats
    assert byts > 2 * mats  # every weight read once, in bf16


def test_model_flops():
    cfg = config("qwen2-1.5b")
    mats = 28 * (1536 * 1536 * 2 + 2 * 256 * 1536 + 3 * 8960 * 1536)
    one = costs.model_flops_per_token(cfg, 100)
    assert one == 2 * mats + 2 * 151936 * 1536 + 4 * 100 * 128 * 12 * 28
    # Each position more of history adds QK^T and PV for every query head.
    assert costs.model_flops_per_token(cfg, 101) - one == 4 * 128 * 12 * 28


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        costs.peaks("TPU v9 imaginary")
    pk = costs.peaks("TPU v5e")
    assert pk.bf16_flops == 197e12 and pk.hbm_bytes_per_s == 819e9
    assert "Google Cloud" in pk.source
