"""Operation and byte counts against hand counts for one layer."""

import json
import os

import pytest

from chipbench import flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_smollm_layer_and_causal_attention():
    hf = cfg("smollm-360m")
    # q, o: 960 x 960 each; k, v: 960 x 320 each; gate, up, down 960 x 2560
    assert flops.layer_matmul_params(hf) == (
        2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560)
    # causal over 4 positions: 1 + 2 + 3 + 4 pairs
    assert flops.attn_pairs(4) == 10
    # QK^T and PV: 2 products x 2 FLOPs x 15 heads x 64 x pairs
    assert flops.attn_flops(hf, 2048) == 4 * 15 * 64 * 2048 * 2049 // 2
    # one 1024-token prompt: every layer over every position, the LM head
    # for the last position only
    assert flops.prefill_flops(hf, 1024) == (
        2 * 32 * flops.layer_matmul_params(hf) * 1024
        + 32 * flops.attn_flops(hf, 1024) + 2 * 960 * 49152)


#: h2o-danube-1.8b's published widths (arXiv 2401.16818), window 4096
DANUBE = {"hidden_size": 2560, "intermediate_size": 6912,
          "num_hidden_layers": 24, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 80, "vocab_size": 32000,
          "sliding_window": 4096}


def test_windowed_attention():
    hf = DANUBE
    assert flops.layer_matmul_params(hf) == (
        2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912)
    w = 4096
    # below the window: causal; past it each query sees exactly w keys
    assert flops.attn_pairs(w, w) == w * (w + 1) // 2
    assert flops.attn_pairs(w + 10, w) == w * (w + 1) // 2 + 10 * w
    assert flops.attn_flops(hf, 8192) == 4 * 32 * 80 * (
        w * (w + 1) // 2 + 4096 * w)


def test_flash_call_bytes_and_least_time():
    hf = cfg("smollm-360m")
    c = flops.flash_call(hf, 1, 1024)
    # Q and O at 15 heads, K and V at 5, 64 wide, bf16
    assert c["bytes"] == (2 * 15 + 2 * 5) * 1024 * 64 * 2
    peak = flops.peaks("TPU v5 lite")
    assert flops.least_time(c["flops"], c["bytes"], peak) == pytest.approx(
        max(c["flops"] / 197e12, c["bytes"] / 819e9))


def test_decode_bytes_count_weights_and_live_kv():
    hf = DANUBE
    row = 24 * 2 * 8 * 80 * 2
    base = flops.weight_bytes(hf)
    # positions past the window read only the window
    assert flops.decode_bytes(hf, [10, 9000]) == base + row * (11 + 4097)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
