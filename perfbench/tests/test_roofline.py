"""Operations and bytes from shapes, and the peaks table."""
import pytest

import roofline


def test_peaks_of_v5e_and_unknown_device():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_int8_matmul_bound():
    pk = roofline.peaks("TPU v5 lite")
    ops, nbytes = roofline.int8_matmul(4, 2048, 12288)
    assert ops == 2 * 4 * 2048 * 12288
    assert nbytes == 2048 * 12288 + 4 * 2048 * 4 + 4 * 12288 * 4 \
        + 16 * 12288
    # at M = 4 the bound is the weight stream, about 31 us
    t = roofline.least_time(ops, nbytes, pk)
    assert t == nbytes / 819e9
    assert 30e-6 < t < 32e-6


def test_decode_step_ops_count_every_matmul():
    cfg = {"model_type": "qwen3", "hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 4, "hidden_act": "silu", "num_hidden_layers": 3,
           "vocab_size": 10}
    proj = 8 * (2 + 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16
    assert roofline.projection_macs(cfg) == proj
    pos = [0, 5]
    attn = 2 * 2 * 4 * (1 + 6)
    assert roofline.decode_step_ops(cfg, pos) == \
        2 * (3 * (2 * proj + attn) + 2 * 8 * 10)


def test_decode_attention_bytes():
    # two slots at positions 0 and 9: 1 + 10 valid rows, K and V, bf16
    b = roofline.decode_attention_bytes([0, 9], 4, 2, 8)
    assert b == 11 * 2 * 8 * 2 * 2 + 2 * 2 * 8 * 2 * 2 + 2 * 2 * 4 * 8 * 4
