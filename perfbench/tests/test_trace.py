"""The trace reduction on a small synthetic trace, worked by hand."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import readers, trace  # noqa: E402

US = 1_000


def synthetic():
    """Two devices, a 1,000 us window.  Device 0 runs `jit_step` twice
    (100-300 and 500-700 us), each a `while` holding two fusions; device 1
    runs it once (100-500 us).  The host is under `bench:map_blocks` from 0 to
    450 us and under `bench:fetch` from 450 to 1,000 us."""
    dev0_ops = [("while.1", 100 * US, 200 * US), ("fusion.1", 100 * US, 80 * US),
                ("fusion.2", 190 * US, 100 * US),
                ("while.1", 500 * US, 200 * US), ("fusion.1", 500 * US, 80 * US),
                ("fusion.2", 590 * US, 100 * US)]
    dev1_ops = [("fusion.1", 100 * US, 400 * US)]
    host = [("bench:window", 0, 1000 * US), ("bench:map_blocks", 0, 450 * US),
            ("bench:fetch", 450 * US, 550 * US), ("PjitFunction(step)", 10 * US, 5 * US)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": dev0_ops},
            {"name": "XLA Modules", "events": [("jit_step(123)", 100 * US, 200 * US),
                                                ("jit_step(123)", 500 * US, 200 * US),
                                                ("jit_late(9)", 900 * US, 200 * US)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": dev1_ops},
            {"name": "XLA Modules", "events": [("jit_step(123)", 100 * US, 400 * US)]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]


def test_busy_union_counts_nested_operations_once():
    r = trace.reduce(synthetic())
    assert abs(r["window_s"] - 1000e-6) < 1e-12
    assert [round(b * 1e6) for b in r["busy_s_per_device"]] == [400, 400]
    assert abs(r["busy_s"] - 400e-6) < 1e-12


def test_modules_count_whole_runs_inside_the_window():
    r = trace.reduce(synthetic())
    assert r["module_runs"] == {"jit_step": 3}  # jit_late runs past the window's end
    assert abs(r["module_s"]["jit_step"] - 800e-6) < 1e-12


def test_operations_report_self_time():
    ops = dict(trace.reduce(synthetic())["device_ops"])
    # per device mean: fusion.1 (80 + 80 + 400) / 2, fusion.2 200 / 2, while (200 - 180) * 2 / 2
    assert abs(ops["fusion.1"] - 280e-6) < 1e-12
    assert abs(ops["fusion.2"] - 100e-6) < 1e-12
    assert abs(ops["while.1"] - 20e-6) < 1e-12


def test_idle_gaps_go_to_the_span_the_host_was_under():
    gaps = dict(trace.reduce(synthetic())["idle_gaps"])
    # device 0: 0-100 and 300-500 (midpoint 400) under map_blocks, 700-1000 under fetch;
    # device 1: 0-100 under map_blocks, 500-1000 under fetch; halved for the mean
    assert abs(gaps["bench:map_blocks"] - 200e-6) < 1e-12
    assert abs(gaps["bench:fetch"] - 400e-6) < 1e-12


def test_no_window_or_no_device_operation_reduces_to_nothing():
    planes = synthetic()
    assert trace.reduce(planes[2:]) is None
    planes[2]["lines"][0]["events"] = planes[2]["lines"][0]["events"][1:]
    assert trace.reduce(planes) is None


def test_readers_on_the_reduced_trace():
    r = trace.reduce(synthetic())
    obs = {"trace." + k: v for k, v in r.items()}
    obs["least.block_s"] = 100e-6
    assert abs(readers.module_ms(obs, "^jit_step$") - 800e-3 / 3) < 1e-9
    assert abs(readers.roofline(obs, "^jit_step$", "least.block_s") - 100 * 100 / (800 / 3)) < 1e-9
    assert readers.roofline(obs, "^jit_absent$", "least.block_s") is None
    assert abs(readers.ratio(obs, "trace.busy_s", "trace.window_s", 100.0, True) - 60.0) < 1e-9
    assert readers.skew(obs, "trace.busy_s_per_device") == 0.0
    assert readers.skew({"trace.busy_s_per_device": [1.0]}, "trace.busy_s_per_device") is None
    assert readers.ratio({}, "a", "b") is None
