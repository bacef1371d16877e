"""`work_axk1`'s counts against hand arithmetic at A.X-K1's published widths,
for the share `axk1_l7_ep16` holds (ISSUE 34's count, parameter for parameter)."""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import work, work_axk1  # noqa: E402
from perfbench.refs import axk1_decoder  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def axk1():
    with open(os.path.join(HERE, "configs", "axk1_l7_ep16.json")) as f:
        return json.load(f)


def test_layer_parameters():
    cfg = axk1()
    q_a, q_b = 7168 * 1536, 1536 * 64 * 192
    kv_a, kv_b, o = 7168 * 576, 512 * 64 * 256, 8192 * 7168
    assert (q_a, q_b, kv_a, kv_b, o) == (11_010_048, 18_874_368, 4_128_768, 8_388_608, 58_720_256)
    assert work_axk1.attention_params(cfg) == q_a + q_b + kv_a + kv_b + o == 101_122_048
    assert work_axk1.norm_params(cfg) == 2 * 7168 + 1536 + 512
    assert work_axk1.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert work_axk1.dense_layer_params(cfg) == 101_122_048 + 16_384 + 3 * 7168 * 18432 == 497_500_160
    own = 101_122_048 + 16_384 + 7168 * 192 + 44_040_192   # attention, gains, router over 192, shared
    assert work_axk1.expert_layer_own_params(cfg) == own == 146_554_880
    assert own + 12 * 44_040_192 == 675_037_184             # a chip's share of an expert layer: 1.350 GB
    assert own + 192 * 44_040_192 == 8_602_271_744          # a whole one: 17.2 GB, which no chip holds
    assert work_axk1.head_params(cfg) == 20_480 * 7168 == 146_800_640
    whole = 497_500_160 + 6 * 675_037_184 + 2 * 146_800_640 + 7168
    assert work_axk1.held_params(cfg) == whole == 4_841_331_712
    assert round(2 * whole / 1e9, 2) == 9.68                # what the chip holds in bfloat16
    assert round(2 * (whole + 675_037_184) / 1e9, 2) == 11.03  # a seventh expert layer would not leave room


def test_weight_shapes_sum_to_the_count():
    """The reference's weight tree holds exactly the counted parameters."""
    cfg = axk1()
    s = axk1_decoder.dims(cfg)
    count = lambda shapes: sum(math.prod(shape) for _, shape, _ in shapes.values())
    assert count(axk1_decoder.dense_shapes(s)) == work_axk1.dense_layer_params(cfg)
    assert count(axk1_decoder.expert_shapes(s)) == work_axk1.expert_layer_own_params(cfg) \
        + 12 * work_axk1.expert_params(cfg)
    assert (s["e"], s["e_all"], s["first"], s["k"]) == (12, 192, 0, 8)


def test_latent_cache_bytes():
    cfg = axk1()
    assert work_axk1.latent_bytes_per_token(cfg) == 7 * 576 * 2 == 8_064   # 1,152 B a layer
    assert 64 * 192 * 2 * 2 * 7 == 344_064                                   # K and V by head: 43 times that
    assert round(64 * 3072 * 8_064 / 1e9, 2) == 1.59                         # 64 slots x 3,072 reserved
    assert work_axk1.attention_flops_per_token_held(cfg) == 7 * 2 * 64 * (576 + 512)


def test_step_least_time_follows_the_experts_touched_and_the_tokens_held():
    cfg, peak = axk1(), work.peaks("TPU v5 lite")
    own = 497_500_160 + 6 * 146_554_880 + 146_800_640
    assert work_axk1.step_own_params(cfg) == own == 1_523_630_080
    # one step of 64 rows that hold 60,000 tokens, 11 of 12 held experts touched in each of 6 layers
    nbytes = 2 * (own + 66 * 44_040_192) + 8_064 * 60_000
    assert nbytes == 9_344_405_504
    t = work_axk1.decode_least_time(cfg, 1, 64, 60_000, 66, peak)
    assert abs(t - nbytes / 819e9) < 1e-12 and round(t * 1e3, 2) == 11.41   # bound by bytes
    flops = 2 * (own + 6 * 8 * 12 / 192 * 44_040_192) * 64 + 7 * 2 * 64 * 1088 * 60_000
    assert flops / 197e12 < t / 5
    # an expert nobody chose is not read; a token more held is 8,064 B more
    less = work_axk1.decode_least_time(cfg, 1, 64, 60_000, 60, peak)
    assert abs((t - less) - 6 * 2 * 44_040_192 / 819e9) < 1e-12
    more = work_axk1.decode_least_time(cfg, 1, 64, 61_000, 66, peak)
    assert abs((more - t) - 1_000 * 8_064 / 819e9) < 1e-12


def test_prefill_touches_at_most_the_held_experts():
    cfg, peak = axk1(), work.peaks("TPU v5 lite")
    assert work_axk1.prefill_experts_touched_at_most(cfg, [1, 1747]) == 6 * 8 + 6 * 12
    t = work_axk1.prefill_least_time(cfg, [1747], 72, peak)
    own = 1_523_630_080
    flops = 2 * (own - 146_800_640 + 6 * 0.5 * 44_040_192) * 1747 + 2 * 146_800_640 \
        + 7 * 2 * 64 * (192 + 128) * (1747 * 1748 // 2)
    assert abs(t - flops / 197e12) < 1e-9   # a long prompt is bound by its products: 29 ms
    assert round(t * 1e3) == 29
    short = work_axk1.prefill_least_time(cfg, [150], 72, peak)
    nbytes = 2 * (own + 72 * 44_040_192) + 8_064 * 150
    assert abs(short - nbytes / 819e9) < 1e-12  # a short one by the weights it reads
