"""Driver for frame scoring: `tfs.map_blocks(program, frame)` over a frame
cached in device memory, then one batched `device_get` of the outputs, epoch
after epoch.  The configuration's file names the program's factory, its
reference and the shape of a row; the traffic file the rows and blocks per
chip.  Nothing here is particular to one model.
"""

import concurrent.futures
import importlib
import json
import os
import time

import numpy as np

from perfbench import work


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx["config"], ctx["traffic"]
        self.ref = importlib.import_module("perfbench.refs." + self.config["reference"])
        self.rows = int(self.traffic["rows_per_chip"]) * ctx["chips"]
        self.blocks = int(self.traffic["blocks_per_chip"]) * ctx["chips"]
        self.first = self.last = None
        self.epoch_mismatches = 0

    # ------------------------------------------------------------------ set-up
    def _images(self):
        """Distinct random rows, one generator per 256 rows, filled by a pool of threads.  Each
        row has its own brightness and contrast (uniform noise of a power-of-two
        range above an offset), so that rows score differently."""
        width = int(np.prod(self.config["input"]["row_shape"]))
        chunk = 256
        starts = range(0, self.rows, chunk)
        seeds = np.random.SeedSequence([int(self.ctx["seed"]), 0x1A6E]).spawn(len(starts))
        out = np.empty((self.rows, width), np.dtype(self.config["input"]["dtype"]))

        def fill(task):
            start, seed = task
            rng = np.random.default_rng(seed)
            for i in range(start, min(start + chunk, self.rows)):
                span = 1 << int(rng.integers(3, 8))
                out[i] = rng.integers(0, span, size=width, dtype=out.dtype)
                out[i] += out.dtype.type(rng.integers(0, 257 - span))

        with concurrent.futures.ThreadPoolExecutor(min(os.cpu_count() or 4, 24)) as pool:
            list(pool.map(fill, zip(starts, seeds)))
        return out

    def setup(self):
        import jax.numpy as jnp
        import tensorframes_tpu as tfs

        spec = self.config["program"]
        factory = getattr(importlib.import_module(spec["module"]), spec["factory"])
        dtype = jnp.dtype(self.config["dtype"])
        column = self.config["input"]["column"]
        self.weights = self.ref.make_weights(self.ctx["seed"], dtype)
        self.ctx["mark"]("weights")
        self.images = self._images()
        self.ctx["mark"]("images")

        def fn(weights, **cols):
            return factory(weights, dtype=dtype, **spec.get("kwargs", {}))(cols[column])

        # weights ride as traced arguments (Program's `params`), so that the
        # executable does not depend on the seed and the compile cache hits
        fn.__signature__ = _signature([column, "weights"])
        self.tfs = tfs
        self.program = tfs.Program.wrap(fn, fetches=list(self.config["outputs"]),
                                        params={"weights": self.weights})
        self.frame = tfs.TensorFrame.from_arrays(
            {column: self.images}, num_blocks=self.blocks).cache()
        self.ctx["mark"]("frame_cache")
        self.first = self._epoch()  # compiles, and is what later epochs must repeat
        self.ctx["mark"]("first_epoch")
        self._epoch()
        self.ctx["mark"]("second_epoch")

    def _epoch(self):
        import jax

        with jax.profiler.TraceAnnotation("bench:map_blocks"):
            out = self.tfs.map_blocks(self.program, self.frame)
        with jax.profiler.TraceAnnotation("bench:fetch"):
            got = jax.device_get(tuple(out.column(n).data for n in self.config["outputs"]))
        return [np.asarray(g) for g in got]

    # ------------------------------------------------------------------ window
    def window(self, seconds):
        import jax

        epochs, t0 = 0, time.monotonic()
        with jax.profiler.TraceAnnotation("bench:window"):
            while True:
                self.last = self._epoch()
                epochs += 1
                self.epoch_mismatches += int(sum(
                    np.count_nonzero(a != b) for a, b in zip(self.first, self.last)))
                elapsed = time.monotonic() - t0
                if elapsed >= seconds:  # the window closes on an epoch's boundary
                    break
        rows = epochs * self.rows
        per_block = self.rows // self.blocks
        obs = {
            "rows_per_s": rows / elapsed, "window_s": elapsed, "rows": rows,
            "blocks": epochs * self.blocks, "attempted": rows, "failed": 0,
            "input_bytes": rows * self.images.shape[1] * self.images.dtype.itemsize,
        }
        if self.ctx["peak"]:  # work from shapes, by the functions the configuration names
            obs["flops"] = rows * getattr(work, self.config["work"]["flops_per_row"])()
            obs["least.block_s"] = getattr(work, self.config["work"]["least_time"])(
                per_block, self.ctx["peak"])
        return obs

    def release(self):
        self.frame = self.program = None

    # ------------------------------------------------------------------ correct
    def sample(self):
        n = min(int(self.traffic["check_rows"]), self.rows)
        return np.sort(np.random.default_rng([int(self.ctx["seed"]), 0xC4EC]).choice(
            self.rows, size=n, replace=False))

    def reference(self, idx, precision="float32", block=32):
        """(prediction, score, log-probs) of the rows `idx`, in blocks."""
        import jax

        fwd = jax.jit(lambda w, x: self.ref.forward(w, x, precision))
        parts = [jax.device_get(fwd(self.weights, self.images[idx[i:i + block]]))
                 for i in range(0, len(idx), block)]
        return [np.concatenate(p) for p in zip(*parts)]

    @staticmethod
    def gaps(pred, score, ref):
        """How far the answers lie from the reference's.  A row's distance is
        that of its score from the reference's, plus the gap by which its
        predicted class lies below the reference's best in the reference's
        log-probabilities; the number compared is the root mean square over
        the sampled rows (the widest row, which swings by its nature, goes on
        an earlier line)."""
        _, ref_score, logp = ref
        below = ref_score - logp[np.arange(len(pred)), np.asarray(pred, np.int64)]
        dist = np.abs(np.asarray(score, np.float64) - ref_score) + below
        return {"answer_rms_gap": float(np.sqrt(np.mean(dist * dist))),
                "widest_row_gap": float(np.max(dist))}

    def check(self):
        idx = self.sample()
        pred, score = (np.asarray(a)[idx] for a in self.last[:2])
        numbers = self.gaps(pred, score, self.reference(idx))
        print(json.dumps({"widest_row_gap": numbers.pop("widest_row_gap")}))
        numbers["epoch_mismatches"] = float(self.epoch_mismatches)
        return numbers

    def control(self):
        """The reference in the lower precision the configuration's file
        names (`control_precision`), in the program's place."""
        idx = self.sample()
        low = self.reference(idx, self.config["control_precision"])
        return self.gaps(low[0], low[1], self.reference(idx))


def _signature(names):
    import inspect

    return inspect.Signature([inspect.Parameter(n, inspect.Parameter.POSITIONAL_OR_KEYWORD)
                              for n in names])
