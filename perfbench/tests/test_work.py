"""The work counts against hand arithmetic."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import json  # noqa: E402

import pytest  # noqa: E402

from perfbench import work  # noqa: E402
from perfbench.refs import inception_v3  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mistral():
    with open(os.path.join(HERE, "configs", "mistral_7b_l8.json")) as f:
        return json.load(f)


def test_inception_layer_shapes():
    layers, final = inception_v3.conv_layers()
    assert len(layers) == 94
    first = layers[0]
    assert (first["hout"], first["wout"], first["cin"], first["cout"]) == (149, 149, 3, 32)
    assert final == (8, 8, 2048)
    # the grid sizes of the published layout: 35x35 (A), 17x17 (C), 8x8 (E)
    assert {l["hout"] for l in layers if l["path"][:2] == ("blocks", 0)} == {35}
    assert {l["hout"] for l in layers if l["path"][:2] == ("blocks", 4)} == {17}
    assert {l["hout"] for l in layers if l["path"][:2] == ("blocks", 10)} == {8}


def test_inception_flops_per_row():
    # first convolution: 149 * 149 * 32 outputs, each 3 * 3 * 3 multiply-adds
    assert 2 * 149 * 149 * 32 * 27 == 38_363_328
    flops = work.inception_flops_per_row()
    # 5.7 G multiply-adds is the figure usually quoted for Inception-v3
    assert 11.0e9 < flops < 11.8e9
    params = sum(l["kh"] * l["kw"] * l["cin"] * l["cout"] for l in inception_v3.conv_layers()[0])
    assert 21.5e6 < params + 2048 * 1000 < 24.5e6


def test_inception_least_time_is_at_least_the_compute_time():
    peak = work.peaks("TPU v5 lite")
    rows = 2048
    compute = rows * work.inception_flops_per_row() / peak["flops_per_s"]
    assert compute <= work.inception_least_time(rows, peak) < 1.5 * compute


def test_mistral_layer_parameters():
    cfg = mistral()
    # q and o 4096x4096 each, k and v 4096x1024 each, three 4096x14336, two norms
    assert work.transformer_layer_params(cfg) == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert round(work.transformer_layer_params(cfg) / 1e6, 1) == 218.1
    assert work.transformer_kv_bytes_per_token(cfg) == 8 * 2 * 8 * 128 * 2  # 32,768 B
    assert round(work.transformer_matmul_params(cfg) / 1e9, 3) == 1.879


def test_decode_least_time_is_bound_by_traffic():
    cfg, peak = mistral(), work.peaks("TPU v5 lite")
    # one step, 12 streams of 500 positions: weights 3.758 GB + K/V 0.197 GB over 819 GB/s
    t = work.decode_least_time(cfg, 1, 12, 12 * 500, peak)
    nbytes = work.transformer_matmul_params(cfg) * 2 + 32768 * 6000
    assert abs(t - nbytes / 819e9) < 1e-12
    assert 4.7e-3 < t < 4.9e-3


def test_prefill_least_time_is_bound_by_compute():
    cfg, peak = mistral(), work.peaks("TPU v5 lite")
    t = work.prefill_least_time(cfg, 1, [1024], peak)
    layers = (work.transformer_matmul_params(cfg) - 4096 * 32768) * 2 * 1024
    attention = 4 * 8 * 4096 * (1024 * 1025 // 2)
    assert abs(t - (layers + 2 * 4096 * 32768 + attention) / 197e12) < 1e-12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9")
