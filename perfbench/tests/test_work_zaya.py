"""`work_zaya`'s counts against hand arithmetic at ZAYA1-8B's published widths."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import work, work_zaya  # noqa: E402
from perfbench.refs import zaya_decoder  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def zaya():
    with open(os.path.join(HERE, "configs", "zaya1_8b_l20.json")) as f:
        return json.load(f)


def test_layer_parameters():
    cfg = zaya()
    assert work_zaya.expert_params(cfg) == 3 * 2048 * 2048 == 12_582_912
    attention = 2048 * 1024 + 2 * 2048 * 256 + 1024 * 2048          # Wq, Wk, Wv1 + Wv2, Wo
    convolutions = 3 * 1280 + 2 * 10 * 128 * 128 + 10 * 128 + 2      # a0 a1 b1; A0 A1; b2; tau
    router = 2048 * 256 + 1 + 256 + 2 * 256 * 256 + 256 * 16 + 16    # Wr, gamma, norm, W1 W2, W3, b
    assert (attention, convolutions, router) == (5_242_880, 332_802, 659_729)
    assert work_zaya.layer_other_params(cfg) == attention + convolutions + router + 2 * 2048 == 6_239_507
    assert work_zaya.head_params(cfg) == 262_272 * 2048 == 537_133_056
    layer = 16 * work_zaya.expert_params(cfg) + work_zaya.layer_other_params(cfg)
    assert round(layer / 1e6, 1) == 207.6  # 415 MB in bfloat16
    whole = 20 * layer + work_zaya.head_params(cfg)
    assert round(2 * whole / 1e9, 2) == 9.38  # what the chip holds; 40 layers would be 17.68 GB
    assert round(2 * (40 * layer + work_zaya.head_params(cfg)) / 1e9, 2) == 17.68


def test_weight_shapes_sum_to_the_count():
    """The reference's weight tree holds exactly the counted parameters."""
    cfg = zaya()
    shapes = zaya_decoder.block_shapes(zaya_decoder.dims(cfg))
    total = sum(int(__import__("math").prod(shape)) for _, shape, _ in shapes.values())
    assert total == 16 * work_zaya.expert_params(cfg) + work_zaya.layer_other_params(cfg)


def test_state_and_cache_bytes():
    cfg = zaya()
    assert work_zaya.kv_bytes_per_token(cfg) == 20 * 2 * 2 * 128 * 2 == 20_480   # 1 KB a layer
    assert work_zaya.state_bytes_per_slot(cfg) == 20 * (2 * 1280 + 128) * 2 == 107_520


def test_step_least_time_follows_the_experts_touched():
    cfg, peak = zaya(), work.peaks("TPU v5 lite")
    # one step of 48 tokens at 500 positions each, 15 of 16 experts touched in each of 20 layers
    nbytes = 2 * (20 * 6_239_507 + 537_133_056 + 300 * 12_582_912) + 20_480 * 48 * 500
    assert nbytes == 9_365_113_592
    t = work_zaya.decode_least_time(cfg, 1, 48, 48 * 500, 300, peak)
    assert abs(t - nbytes / 819e9) < 1e-12 and round(t * 1e3, 2) == 11.43  # bound by bytes, not FLOPs
    flops = 48 * (2 * 20 * (6_239_507 + 12_582_912) + 2 * 537_133_056) + 4 * 20 * 1024 * 48 * 500
    assert flops / 197e12 < t / 20
    # an expert nobody chose is not read: one expert a layer fewer is 20 x 25.2 MB less
    less = work_zaya.decode_least_time(cfg, 1, 48, 48 * 500, 280, peak)
    assert abs((t - less) - 20 * 2 * 12_582_912 / 819e9) < 1e-12


def test_prefill_touches_at_most_its_tokens():
    cfg, peak = zaya(), work.peaks("TPU v5 lite")
    assert work_zaya.prefill_experts_touched_at_most(cfg, [5, 100]) == 20 * 5 + 20 * 16
    t = work_zaya.prefill_least_time(cfg, [100], 320, peak)
    nbytes = 2 * (20 * 6_239_507 + 537_133_056 + 320 * 12_582_912) + 20_480 * 100
    assert abs(t - nbytes / 819e9) < 1e-12  # a 100-token prompt is bound by the weights it reads
    # a dropless 256-token prefill needs 0.13 TFLOP in its experts over 20 layers; 2.1 if every expert saw every token
    assert round(256 * 2 * 20 * 12_582_912 / 1e12, 2) == 0.13
    assert round(16 * 256 * 2 * 20 * 12_582_912 / 1e12, 1) == 2.1
