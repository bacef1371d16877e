"""`work_trinity`'s counts against hand arithmetic at Trinity-Large-Preview's
published widths, for the share `trinity_large_l5_ep8` holds (ISSUE 43's
count, parameter for parameter), and its least times and prefill attention
against the same arithmetic written out."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import work, work_trinity  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = work.PEAKS["TPU v5 lite"]


def trinity():
    with open(os.path.join(HERE, "configs", "trinity_large_l5_ep8.json")) as f:
        return json.load(f)


def test_layer_parameters():
    cfg = trinity()
    q, kv, o, gate = 3072 * 6144, 3072 * 1024, 6144 * 3072, 3072 * 6144
    assert (q, kv, o, gate) == (18_874_368, 3_145_728, 18_874_368, 18_874_368)
    assert work_trinity.attention_params(cfg) == q + 2 * kv + o + gate == 62_914_560
    assert work_trinity.norm_params(cfg) == 4 * 3072 + 2 * 128
    assert work_trinity.expert_params(cfg) == 3 * 3072 * 3072 == 28_311_552
    assert work_trinity.router_params(cfg) == 3072 * 256 + 256 == 786_688
    assert work_trinity.dense_layer_params(cfg) == 176_173_312
    own = 62_914_560 + 12_544 + 786_688 + 28_311_552  # attention, gains, router, shared
    assert work_trinity.expert_layer_own_params(cfg) == own
    assert work_trinity.expert_layer_params(cfg) == own + 32 * 28_311_552 == 997_995_008
    assert own + 256 * 28_311_552 == 7_339_782_656  # a whole one: 14.7 GB, which no chip holds
    assert 2 * work_trinity.head_params(cfg) == 2 * 25_024 * 3072 == 153_747_456


def test_what_the_chip_holds():
    cfg = trinity()
    whole = 176_173_312 + 4 * 997_995_008 + 153_747_456 + 3072
    assert work_trinity.held_params(cfg) == whole == 4_321_903_872
    assert round(2 * whole / 1e9, 3) == 8.644  # in bfloat16
    assert work_trinity.layers_of(cfg) == (1, 4)
    assert work_trinity.kv_bytes_per_key(cfg) == 8 * 128 * 2 * 2 == 4096


def test_window_keys_and_prefill_attention_by_kind():
    cfg = trinity()
    W = 4096
    assert work_trinity.window_keys(1, W) == 1
    assert work_trinity.window_keys(W, W) == W * (W + 1) // 2
    assert work_trinity.window_keys(14_336, W) == sum(min(t + 1, W) for t in range(14_336))
    windowed, causal = work_trinity.prefill_attention_flops(cfg, [883, 14_336])
    per_pair = 4 * 48 * 128
    assert causal == 1 * per_pair * (883 * 884 // 2 + 14_336 * 14_337 // 2)
    assert windowed == 4 * per_pair * (883 * 884 // 2 + work_trinity.window_keys(14_336, W))
    # past the window the window layers' work grows with the window, not the prompt
    assert windowed < 4 * causal


def test_kernel_least_time_is_the_keys_read_once():
    cfg = trinity()
    held, held_w = 1_000_000, 400_000
    nbytes = 4096 * (1 * held + 4 * held_w)
    assert work_trinity.kernel_bytes(cfg, held, held_w) == nbytes
    assert work_trinity.kernel_least_time(cfg, held, held_w, PEAK) == nbytes / 819e9
    # a step's least time holds the kernel's bytes and the weights it reads
    step = work_trinity.decode_least_time(cfg, 10, 480, held, held_w, 10 * 4 * 20, PEAK)
    weights = (10 * work_trinity.step_own_params(cfg) + 800 * work_trinity.expert_params(cfg)) * 2
    assert step == max(step, (weights + nbytes) / 819e9)


def test_readers_return_nothing_without_the_kernel():
    obs = {"trace.device_ops": [["fusion.1", 0.5]], "least.kernel_s": 0.1,
           "least.prefill_attention_flops": 1e12, "peak.flops_per_s": 197e12}
    assert work_trinity.kernel_roofline(obs, "tfs_paged_attention", "least.kernel_s") is None
    assert work_trinity.flops_roofline(obs, "tfs_flash_prefill", "least.prefill_attention_flops") is None
    obs["trace.device_ops"] += [["tfs_paged_attention.3", 0.2], ["tfs_flash_prefill.1", 0.05]]
    assert work_trinity.kernel_roofline(obs, "tfs_paged_attention", "least.kernel_s") == 50.0
    share = work_trinity.flops_roofline(obs, "tfs_flash_prefill", "least.prefill_attention_flops")
    assert abs(share - 100 * 1e12 / (0.05 * 197e12)) < 1e-9
