"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: loads the cell named in BENCHMARK.json (configuration file,
traffic file, the driver the traffic file names), sets up and warms the cell's
own shapes, measures one window, frees the program, compares what the window
produced with the plain reference, and prints one JSON line last.  It needs
the TPU and fails without one.  `--rehearse` runs the same control flow on the
CPU at the `tiny` sizes of the files; its line names the CPU and carries no
rate.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def overlay(base, over):
    """`base` with `over` laid on top, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, rehearse):
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(conf["file"])
    traffic = load_json("perfbench", "traffic", cell["traffic"] + ".json")
    if rehearse:
        config, traffic = (overlay(d, d.get("tiny", {})) for d in (config, traffic))
    return bench, cell, config, traffic


def context(cell, config, traffic, seed, peak=None, mark=lambda name: None):
    """What a driver is given.  `mark(name)` stamps the end of a span of set-up."""
    return {"config": config, "traffic": traffic, "seed": seed, "chips": cell["chips"],
            "root": ROOT, "peak": peak, "mark": mark}


def metric_cells(metric, bench):
    """The cells that report a metric."""
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" not in metric:
        return [w["name"] for w in bench["workloads"]]
    return metric_cells(next(m for m in bench["end_to_end"] if m["name"] == metric["moves"]), bench)


def read_metric(name, obs):
    spec = dict(load_json("perfbench", "metrics", name + ".json"))
    module, _, fn = spec.pop("reader").rpartition(":")
    reader = getattr(importlib.import_module(module or "perfbench.readers"), fn)
    return reader(obs, **spec)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload, args.rehearse)
    marks = [("start", T_START)]

    def mark(name):
        marks.append((name, time.monotonic()))

    import jax
    from tensorframes_tpu import compile_cache, observability

    # the compile cache sits at a fixed path inside the checkout, unless
    # JAX_COMPILATION_CACHE_DIR places it (configure() yields to that)
    compile_cache.configure(os.path.join(ROOT, ".cache", "jax"))
    devices = jax.devices()
    mark("import_and_devices")
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); jax found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 3
    from perfbench import work

    peak = None if args.rehearse else work.peaks(devices[0].device_kind)
    ctx = context(cell, config, traffic, args.seed, peak, mark)
    driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(ctx)
    trace_dir = os.path.join(ROOT, ".cache", "trace")
    try:
        c0 = observability.counters()
        driver.setup()
        setup = observability.counters_delta(c0)
        setup_s = time.monotonic() - T_START
        print(json.dumps({"setup_spans_s": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}}))
        seconds = min(args.seconds, traffic.get("trace_seconds", args.seconds)) if args.trace else args.seconds
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level, opts.host_tracer_level = 0, 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        c1 = observability.counters()
        obs = driver.window(seconds)  # the driver marks the measured part `bench:window`
        window = observability.counters_delta(c1)
        if args.trace:
            jax.profiler.stop_trace()
        stats = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
        obs["hbm_peak_bytes"] = max(s.get("peak_bytes_in_use", 0) for s in stats) or None
    finally:
        driver.release()
    obs.update({"setup_s": setup_s, "chips": cell["chips"]})
    obs.update({"counters." + k: v for k, v in window.items()})
    obs.update({"setup." + k: v for k, v in setup.items()})
    obs.update({"peak." + k: v for k, v in (peak or {}).items()})
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": obs["hbm_peak_bytes"] or 0}
    result = {}
    if args.trace:
        from perfbench import trace

        files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir) for f in fs
                 if f.endswith(".xplane.pb")]
        planes = trace.load(files[0]) if files else []
        reduced = trace.reduce(planes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced:
            print(json.dumps({"trace_modules": {k: [reduced["module_runs"][k], v]
                                                for k, v in reduced["module_s"].items()}}))
            obs.update({"trace." + k: v for k, v in reduced.items()})
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        elif not args.rehearse:
            print("the trace holds no device operation in the window; planes and lines:",
                  [(p["name"], [l["name"] for l in p["lines"]][:8]) for p in planes], file=sys.stderr)
            return 4

    # correct: every number compared, each beside the limit the configuration's file gives it
    numbers = driver.check()
    limits = config["limits"]
    compared = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = obs["failed"] == 0 and all(
        c["value"] == c["value"] and c["value"] <= c["limit"] for c in compared.values())

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if cell["name"] not in metric_cells(m, bench):
            continue
        v = obs.get(m["name"]) if kind == "end_to_end" else read_metric(m["name"], obs)
        if v is not None and not (args.rehearse and m["source"] in ("device_trace", "host_clock")):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    print(json.dumps({"observed": {k: v for k, v in obs.items()
                                   if isinstance(v, (int, float)) and not k.startswith("peak.")
                                   and (v or not k.startswith(("counters.", "setup.")))}}))
    for k, c in compared.items():
        print(f"compared {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": metrics, "device": device, **result, "compared": compared}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
