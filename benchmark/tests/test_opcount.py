"""The byte and flop functions against sums done by hand, for both
configurations of the first benchmark."""

import json
import os

import pytest

from harness import opcount, peaks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def dims(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return opcount.dims(json.load(f))


def test_gpt2_345m_by_hand():
    d = dims("gpt2-345m")
    # per block: qkv 1024x3072, out 1024x1024, two 1024x4096 feed-forward
    layer = 1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096
    assert layer == 12_582_912 == opcount.layer_matmul_params(d)
    assert opcount.matmul_params(d) == 24 * layer + 50257 * 1024
    # tied head: the table counts once; plus 1024 learned positions
    assert opcount.param_count(d) == 24 * layer + 50257 * 1024 + 1024 * 1024
    # 24 layers x (k and v) x 1024 lanes x 2 bytes = 96 KiB a token
    assert opcount.kv_bytes_per_token(d) == 98_304
    # 6 x 353.4M weights + 6 x 24 x 1024 x 1024 attention = 2.27 GFLOP
    assert opcount.train_flops_per_token(d, 1024) == (
        6 * (24 * layer + 50257 * 1024) + 6 * 24 * 1024 * 1024)
    assert opcount.train_flops_per_token(d, 1024) == pytest.approx(2.27e9,
                                                                  rel=0.01)


def test_internlm2_1_8b_by_hand():
    d = dims("internlm2-1.8b")
    # q 2048x2048, k and v 2048x1024 each (8 KV heads of 128), o 2048x2048,
    # three 2048x8192 SwiGLU matrices
    layer = 2048 * (2048 + 2 * 1024) + 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 62_914_560 == opcount.layer_matmul_params(d)
    # untied: embedding and head are two 92544x2048 tables: 1.89 B in all
    assert opcount.param_count(d) == 24 * layer + 2 * 92544 * 2048
    assert opcount.param_count(d) == pytest.approx(1.889e9, rel=0.001)
    # 24 x 2 x (8 x 128) x 2 bytes: also 96 KiB a token
    assert opcount.kv_bytes_per_token(d) == 98_304


def test_paged_decode_step_by_hand():
    d = dims("internlm2-1.8b")
    got = opcount.paged_decode_step(d, rows=16, attended_tokens=16 * 600)
    lw = 24 * 62_914_560
    assert got["bytes"] == 2 * lw + (16 * 600 + 16) * 98_304
    assert got["flops"] == 2 * lw * 16 + 4 * 2048 * 24 * 16 * 600
    # 3.02 GB of weights + 0.95 GB of cache: HBM-bound, 4.8 ms at 819 GB/s
    r = opcount.roofline(got["flops"], got["bytes"], 0.0097,
                         peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "hbm"
    assert r["share"] == pytest.approx(0.5, abs=0.01)


def test_flash_attention_by_hand():
    got = opcount.flash_attention(batch=8, heads=16, seq=1024, head_dim=64)
    one = 2 * 8 * 16 * 1024 * 1024 * 64 // 2     # one causal s x s matmul
    assert got["fwd_flops"] == 2 * one and got["bwd_flops"] == 5 * one
    assert got["fwd_bytes"] == 4 * 8 * 16 * 1024 * 64 * 2
    full = opcount.flash_attention(8, 16, 1024, 64, causal=False)
    assert full["fwd_flops"] == 2 * got["fwd_flops"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no operation counts"):
        opcount.dims({"arch": "mamba"})
