"""Readings that the limits of `correct` are set from, many seeds in one process.

    python3 perfbench/tools/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 [--control 3]

For each seed: the cell's set-up, a short window at the cell's own load, the
numbers `correct` compares; for the first `--control` seeds also the control's
(the reference in the nearest lower precision, put in the program's place).
One JSON line per seed; not part of a benchmark run.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import importlib

    import jax
    from perfbench import run, work
    from tensorframes_tpu import compile_cache

    compile_cache.configure(os.path.join(ROOT, ".cache", "jax"))
    _, cell, config, traffic = run.load_cell(args.workload, args.rehearse)
    peak = None if args.rehearse else work.peaks(jax.devices()[0].device_kind)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = run.context(cell, config, traffic, seed, peak)
        driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(ctx)
        try:
            driver.setup()
            obs = driver.window(args.seconds)
        finally:
            driver.release()
        line = {"seed": seed, "program": driver.check(), "failed": obs["failed"],
                "attempted": obs["attempted"]}
        if n < args.control:
            line["control"] = driver.control()
        print(json.dumps(line), flush=True)
        del driver
        gc.collect()  # the last seed's weights and cache go before the next set-up


if __name__ == "__main__":
    main()
