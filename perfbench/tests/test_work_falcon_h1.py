"""`work_falcon_h1`'s counts against hand arithmetic at Falcon-H1-34B's published
widths (ISSUE 41 pins them: 430,120,032 parameters a layer, 4,394,354,048 in
all at depth 4, 4,194,304 B of SSM state a slot a layer)."""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import work, work_falcon_h1  # noqa: E402
from perfbench.refs import falcon_h1_decoder  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = work.peaks("TPU v5 lite")


def falcon():
    with open(os.path.join(HERE, "configs", "falcon_h1_34b_l4.json")) as f:
        return json.load(f)


def test_configuration_is_the_published_one_cut_in_depth():
    cfg = falcon()
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["num_hidden_layers"] == 4
    widths = {"hidden_size": 5120, "intermediate_size": 21504, "num_attention_heads": 20,
              "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 261120, "mamba_d_ssm": 4096,
              "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_n_groups": 2, "mamba_d_state": 256,
              "mamba_d_conv": 4, "mamba_chunk_size": 128}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["ssm_multipliers"] == [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                      0.3535533905932738]
    assert cfg["mlp_multipliers"] == [0.1767766952966369, 0.011160714285714284]


def test_layer_parameters():
    cfg = falcon()
    attention = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    mixer = 5120 * 9248 + 4 * 5120 + 5120 + 3 * 32 + 4096 + 4096 * 5120
    mlp = 3 * 5120 * 21504
    assert (attention, mixer, mlp) == (31_457_280, 68_351_072, 330_301_440)
    assert work_falcon_h1.attention_params(cfg) == attention
    assert work_falcon_h1.mixer_params(cfg) == mixer
    assert work_falcon_h1.layer_params(cfg) == attention + mixer + mlp + 2 * 5120 == 430_120_032
    assert round(2 * work_falcon_h1.layer_params(cfg) / 1e9, 3) == 0.860  # GB in bfloat16
    assert work_falcon_h1.head_params(cfg) == 261_120 * 5120 == 1_336_934_400
    assert work_falcon_h1.params(cfg) == 4 * 430_120_032 + 2 * 1_336_934_400 + 5120 == 4_394_354_048
    assert round(2 * work_falcon_h1.params(cfg) / 1e9, 3) == 8.789  # what the chip holds of weights


def test_weight_shapes_sum_to_the_count():
    """The reference's weight tree holds exactly the counted parameters."""
    cfg = falcon()
    shapes = falcon_h1_decoder.block_shapes(falcon_h1_decoder.dims(cfg))
    assert sum(math.prod(shape) for _, shape, _ in shapes.values()) == work_falcon_h1.layer_params(cfg)


def test_state_is_4_mb_a_slot_a_layer_and_the_cell_fills_the_chip():
    cfg = falcon()
    assert work_falcon_h1.ssm_state_bytes_per_slot_layer(cfg) == 32 * 128 * 256 * 4 == 4_194_304
    assert work_falcon_h1.tail_bytes_per_slot_layer(cfg) == 3 * 5120 * 2 == 30_720
    assert work_falcon_h1.kv_bytes_per_token(cfg) == 4 * 2 * 512 * 2 == 8192  # 2,048 B a layer
    state = 128 * 4 * (4_194_304 + 30_720)
    pages = 128 * 2048 * work_falcon_h1.kv_bytes_per_token(cfg)  # and one trash page
    assert (round(state / 1e9, 3), round(pages / 1e9, 3)) == (2.163, 2.147)
    held = 2 * work_falcon_h1.params(cfg) + state + pages
    assert round(held / 1e9, 2) == 13.10 and held / 16.91e9 > 0.77


def test_decode_step_bytes_and_the_states_share():
    """128 live slots at ~400 tokens held each (ISSUE 41's table)."""
    cfg = falcon()
    weights = 2 * (4 * 430_120_032 + 1_336_934_400)
    assert (round(2 * 4 * 430_120_032 / 1e9, 3), round(2 * 1_336_934_400 / 1e9, 3)) == (3.441, 2.674)
    ssm = 2 * 128 * 4 * 4_194_304
    assert round(ssm / 1e9, 3) == 4.295
    state = work_falcon_h1.state_bytes(cfg, 128)
    assert state == ssm + 2 * 128 * 4 * 30_720
    step = work_falcon_h1.step_bytes(cfg, 1, 128, 128 * 400)
    assert step == weights + state + 128 * 400 * 8_192
    assert 10.8e9 < step < 10.9e9 and 39 < 100 * state / step < 40
    least = work_falcon_h1.decode_least_time(cfg, 1, 128, 128 * 400, V5E)
    assert least == step / 819e9 and 13.2e-3 < least < 13.4e-3
    assert round(1e3 * work_falcon_h1.kernel_least_time(cfg, 128, V5E), 2) == 5.24  # 1.31 ms a layer


def test_prefill_reads_the_weights_once_a_dispatch():
    cfg = falcon()
    token = work_falcon_h1.token_flops(cfg)
    assert token == 4 * (2 * 430_080_000 + 4 * 32 * 128 * 256)
    weights = 2 * (4 * 430_120_032 + 1_336_934_400)
    short = work_falcon_h1.prefill_least_time(cfg, 1, [75], V5E)
    assert short == (weights + 75 * 8_192 + 4 * (4_194_304 + 30_720)) / 819e9  # bound by bytes
    one = work_falcon_h1.prefill_least_time(cfg, 1, [1024], V5E)
    assert 0.0175 < one < 0.019  # bound by FLOPs: 1,024 tokens of 3.44 GFLOP and the pairs


def test_kernel_roofline_reads_the_trace_by_the_kernels_name():
    obs = {"trace.device_ops": [["tfs_ssm_step.1", 0.5], ["fusion.3", 0.2]], "least.kernel_s": 0.4}
    assert work_falcon_h1.kernel_roofline(obs, "tfs_ssm_step", "least.kernel_s") == 80.0
    assert work_falcon_h1.kernel_roofline({}, "tfs_ssm_step", "least.kernel_s") is None
