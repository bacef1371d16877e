"""`work_brumby`'s counts against hand arithmetic at Brumby-14B's published widths
(ISSUE 37 pins them: 330.3 M a layer, 34.08 MB a slot a layer, least step 21.7 ms)."""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import work, work_brumby  # noqa: E402
from perfbench.refs import brumby_decoder  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = work.peaks("TPU v5 lite")


def brumby():
    with open(os.path.join(HERE, "configs", "brumby_14b_l8.json")) as f:
        return json.load(f)


def test_layer_parameters():
    cfg = brumby()
    q = o = 5120 * 5120
    k = v = 5120 * 1024
    gate, swiglu = 5120 * 8, 3 * 5120 * 17408
    assert (q, k, gate, swiglu) == (26_214_400, 5_242_880, 40_960, 267_386_880)
    assert work_brumby.layer_matmul_params(cfg) == q + k + v + o + gate + swiglu == 330_342_400
    assert work_brumby.layer_params(cfg) == 330_342_400 + 8 + 2 * 5120 + 2 * 128 == 330_352_904
    assert round(work_brumby.layer_matmul_params(cfg) / 1e6, 1) == 330.3  # the issue's count: the matrices
    assert round(2 * work_brumby.layer_params(cfg) / 1e6, 1) == 660.7  # MB in bfloat16
    assert work_brumby.head_params(cfg) == 151_936 * 5120 == 777_912_320
    assert round(work_brumby.params(cfg) / 1e9, 2) == 4.20
    assert round(2 * work_brumby.params(cfg) / 1e9, 2) == 8.40  # what the chip holds of weights
    forty = 40 * work_brumby.layer_params(cfg) + 2 * work_brumby.head_params(cfg) + 5120
    assert round(forty / 1e9, 2) == 14.77  # the published depth: "14B"


def test_weight_shapes_sum_to_the_count():
    """The reference's weight tree holds exactly the counted parameters."""
    cfg = brumby()
    shapes = brumby_decoder.block_shapes(brumby_decoder.dims(cfg))
    assert sum(math.prod(shape) for _, shape, _ in shapes.values()) == work_brumby.layer_params(cfg)


def test_state_is_34_mb_a_slot_a_layer_at_any_length():
    cfg = brumby()
    assert work_brumby.phi_dim(cfg) == 128 * 129 // 2 == 8256
    per_head = 8256 * 128 + 8256  # S and the normaliser
    assert work_brumby.state_bytes_per_slot_layer(cfg) == 4 * 8 * per_head == 34_080_768
    assert round(20 * 8 * 34_080_768 / 1e9, 2) == 5.45  # the cell's 20 slots of 8 layers
    # in bytes, the K and V of 8,320 tokens of a bf16 GQA cache of these heads
    assert 34_080_768 // (2 * 8 * 128 * 2) == 8320
    # memory the cell holds: weights and state, of the chip's 16.9 GB
    held = 2 * work_brumby.params(cfg) + 20 * 8 * 34_080_768
    assert round(held / 1e9, 2) == 13.85


def test_decode_step_is_the_states_traffic():
    cfg = brumby()
    weights = 2 * (8 * 330_342_400 + 777_912_320)
    assert (round(2 * 8 * 330_342_400 / 1e9, 2), round(2 * 777_912_320 / 1e9, 2)) == (5.29, 1.56)
    state = 2 * 20 * 8 * 34_080_768
    assert work_brumby.state_bytes(cfg, 20) == state
    assert work_brumby.step_bytes(cfg, 1, 20) == weights + state == 17_747_148_800
    assert round(100 * state / (weights + state)) == 61  # the mechanism's share of a step's bytes
    least = work_brumby.decode_least_time(cfg, 1, 20, V5E)
    assert least == (weights + state) / 819e9 and round(1e3 * least, 1) == 21.7
    # bound by bytes: the step's FLOPs at 20 rows are far under the matrix unit's
    flops = (work_brumby.token_flops(cfg) + 2 * work_brumby.head_params(cfg)) * 20
    assert flops / 197e12 < 0.05 * least
    # the kernel alone: the state, read once and written once
    assert round(1e3 * work_brumby.kernel_least_time(cfg, 20, V5E), 2) == 13.32
    # three live slots of twenty: a seventh of the state's traffic, the weights as before
    assert work_brumby.step_bytes(cfg, 1, 3) == weights + 2 * 3 * 8 * 34_080_768


def test_prefill_counts_retention_among_the_flops():
    cfg = brumby()
    retention = 2 * (8 + 40) * 8256 * 128
    assert work_brumby.retention_flops_per_token_layer(cfg) == retention == 101_449_728
    token = work_brumby.token_flops(cfg)
    assert token == 8 * (2 * 330_342_400 + retention) and round(token / 1e9, 1) == 6.1
    assert round(100 * 8 * retention / token) == 13  # retention's share of a prefilled token
    one = work_brumby.prefill_least_time(cfg, 1, [2048], V5E)
    assert one == (token * 2048 + 2 * 777_912_320) / 197e12  # bound by FLOPs: 63 ms
    assert 0.06 < one < 0.066
    # a 2,089-token prompt in two dispatches reads the weights twice and is still bound by FLOPs
    two = work_brumby.prefill_least_time(cfg, 2, [2089], V5E)
    assert two == (token * 2089 + 2 * 777_912_320) / 197e12


def test_kernel_roofline_reads_the_trace_by_the_kernels_name():
    obs = {"trace.device_ops": [["%tfs_retention_step.1 = custom-call", 0.5], ["fusion.3", 0.2],
                                ["tfs_retention_step.2", 0.3]], "least.kernel_s": 0.6}
    assert work_brumby.kernel_roofline(obs, "tfs_retention_step", "least.kernel_s") == 75.0
    # a program without the kernel, or a run without a trace: nothing, and no error
    assert work_brumby.kernel_roofline({"trace.device_ops": [["fusion.3", 0.2]], "least.kernel_s": 0.6},
                                       "tfs_retention_step", "least.kernel_s") is None
    assert work_brumby.kernel_roofline({}, "tfs_retention_step", "least.kernel_s") is None
    assert work_brumby.kernel_roofline(obs, "tfs_retention_step", "least.absent") is None
