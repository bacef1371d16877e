"""Driver for served decoding: `bridge.serve(decode_model=...)` in this
process, which holds the chip, and a closed loop of `BridgeClient.decode`
callers in a child process (`decode_clients.py`).  The configuration's file
gives the model's sizes and its reference; the traffic file the clients, the
length distributions and the server's slots.
"""

import importlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx["config"], ctx["traffic"]
        self.ref = importlib.import_module("perfbench.refs." + self.config["reference"])
        self.serve = dict(self.traffic["serve"])
        self.child = self.server = self.sched = None

    # ------------------------------------------------------------------ set-up
    def setup(self):
        import jax
        import jax.numpy as jnp
        from tensorframes_tpu import bridge
        from tensorframes_tpu.models import transformer
        from tensorframes_tpu.ops import bucketing

        m, dtype = self.config, jnp.dtype(self.config["dtype"])
        cfg = transformer.TransformerConfig(
            vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
            d_ff=m["intermediate_size"], max_seq=self.serve["max_seq"],
            rope_theta=float(m["rope_theta"]), dtype=dtype, param_dtype=dtype)
        self.requests = traffic_gen.decode_requests(self.traffic, self.ctx["seed"], m["vocab_size"])
        # the clients start (and import) while the weights and programs are made
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=self.ctx["root"])
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(self.ctx["root"], "perfbench/drivers/decode_clients.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=self.ctx["root"])
        self.weights = self.ref.make_weights(self.ctx["seed"], m, dtype)
        jax.block_until_ready(self.weights)
        self.ctx["mark"]("weights")
        self.server = bridge.serve(
            max_inflight=self.serve["max_inflight"],
            decode_model={"params": self.weights, "cfg": cfg,
                          **{k: self.serve[k] for k in ("max_slots", "max_seq", "tokens_per_page")}})
        self.sched = self.server.decode_scheduler
        self.ctx["mark"]("serve")
        # warm the decode step and every prefill bucket this traffic's prompts reach
        buckets = sorted({bucketing.bucket_for(len(r["prompt"])) for r in self.requests})
        for b in buckets:
            self.sched.submit(np.arange(b, dtype=np.int32) % m["vocab_size"], 2)
        self.ctx["mark"]("warm_buckets")
        host, port = self.server.address[:2]
        self.child.stdin.write(json.dumps({"host": host, "port": port, "requests": self.requests,
                                           "clients": self.traffic["clients"]}) + "\n")
        self.child.stdin.flush()
        if self.child.stdout.readline().strip() != "ready":
            raise RuntimeError("the client process did not come up")
        self.ctx["mark"]("clients_ready")

    # ------------------------------------------------------------------ window
    def window(self, seconds):
        import jax

        before = self.sched.snapshot()
        self.child.stdin.write("go\n")
        self.child.stdin.flush()
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:window"), jax.profiler.TraceAnnotation("bench:serve"):
            time.sleep(seconds)
        t1 = time.monotonic()
        after = self.sched.snapshot()
        self.child.stdin.write("stop\n")
        self.child.stdin.flush()
        # answers still in flight are waited for: late is late, not wrong
        self.results = json.loads(self.child.stdout.readline())
        self.child.wait(timeout=60)
        inside = [r for r in self.results if r["done"] <= t1]
        ok = [r for r in inside if "tokens" in r]
        failed = [r for r in self.results if "error" in r]
        for r in failed[:3]:
            print("failed request:", r["error"], file=sys.stderr)
        lat = np.array([r["done"] - r["sent"] for r in ok]) * 1000.0
        steps = after["steps"] - before["steps"]
        tokens = after["total_tokens"] - before["total_tokens"]
        prefills = after["prefill_batches"] - before["prefill_batches"]
        started = [self.requests[r["i"]] for r in self.results if r["sent"] <= t1]
        obs = {
            "tokens_per_s": sum(len(r["tokens"]) for r in ok) / (t1 - t0),
            "request_p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "request_p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "window_s": t1 - t0, "attempted": len(self.results), "failed": len(failed),
            "completed_in_window": len(ok), "in_flight_at_close": len(self.results) - len(inside),
            "steps": steps, "decode_tokens": tokens - len(started), "sched_tokens": tokens,
            "prefill_batches": prefills, "slots": self.serve["max_slots"],
            "refused": sum(after[k] - before[k] for k in ("refused_pages", "refused_slots")),
        }
        if self.ctx["peak"] and steps:
            # positions held, summed over streams and steps, from the lengths of
            # the requests that ran: a stream at step j holds prompt + j tokens
            done = [(len(self.requests[r["i"]]["prompt"]), len(r["tokens"])) for r in ok]
            mean_ctx = np.mean([p + (n + 1) / 2 for p, n in done]) if done else 0.0
            dec = work.decode_least_time(self.config, steps, obs["decode_tokens"],
                                           mean_ctx * obs["decode_tokens"], self.ctx["peak"])
            pre = work.prefill_least_time(self.config, prefills,
                                            [len(q["prompt"]) for q in started], self.ctx["peak"])
            obs.update({"least.step_s": dec / steps, "least.window_s": dec + pre})
        return obs

    def release(self):
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait()
        if self.server is not None:
            self.server.close()
            self.sched.close()
            # the page pools go, so that the reference has the memory
            self.sched.pool.k_pages = self.sched.pool.v_pages = None
            self.sched._kp = self.sched._vp = None
        self.server = self.sched = None

    # ------------------------------------------------------------------ correct
    def sample(self):
        """The finished requests to hold against the reference: the longest,
        and others drawn from the seed."""
        ok = [r for r in self.results if "tokens" in r]
        if not ok:
            return []
        size = lambda r: len(self.requests[r["i"]]["prompt"]) + len(r["tokens"])
        longest = max(ok, key=size)
        rest = [r for r in ok if r is not longest]
        rng = np.random.default_rng([int(self.ctx["seed"]), 0xC4EC])
        n = min(int(self.traffic["check_requests"]) - 1, len(rest))
        return [longest] + [rest[i] for i in rng.choice(len(rest), size=n, replace=False)]

    def gaps(self, sample, control=False):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled requests' served tokens.  With
        `control`, the token is the one the lower precision puts first."""
        pad = self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"]
        widest, count = 0.0, 0
        for r in sample:
            prompt, served = self.requests[r["i"]]["prompt"], r["tokens"]
            seq = np.zeros((pad,), np.int32)
            seq[: len(prompt) + len(served)] = prompt + served
            at = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
            ref = np.asarray(self.ref.logits(self.weights, self.config, seq)[at], np.float64)
            tokens = np.asarray(served)
            if control:
                low = self.ref.logits(self.weights, self.config, seq,
                                      self.config["control_precision"])[at]
                tokens = np.asarray(low).argmax(axis=-1)
            gap = ref.max(axis=-1) - ref[np.arange(len(at)), tokens]
            widest, count = max(widest, float(gap.max())), count + len(at)
        return {"token_logit_gap": widest, "tokens_compared": count}

    def check(self):
        short = sum(1 for r in self.results
                    if "tokens" in r and len(r["tokens"]) != self.requests[r["i"]]["max_new"])
        numbers = self.gaps(self.sample())
        numbers["no_reply_compared"] = 0.0 if numbers.pop("tokens_compared") else 1.0
        numbers["wrong_length_replies"] = float(short)
        return numbers

    def control(self):
        numbers = self.gaps(self.sample(), control=True)
        numbers.pop("tokens_compared")
        return numbers
