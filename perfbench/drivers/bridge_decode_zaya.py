"""Driver for served decoding of a ZAYA1 configuration (`zaya1_8b_l20`):
`bridge_decode.Driver`'s server, clients, window and sample, with what that
driver fixes for a dense model replaced: the `TransformerConfig` is built
from this configuration's keys with its block spec (compressed convolutional
attention, top-1 experts), the least times follow the experts that got tokens
(`work_zaya`), and the comparison with the reference follows the served path's
routing.

Why the routing.  Top-1 routing is discontinuous: where a token's two best
expert scores nearly tie, the program's bfloat16 residual and the float32
reference choose differently, that token's layer output is another expert's,
and every logit after it moves by far more than rounding moves it.  With 20
layers and some hundred positions a request such ties are in every request, in
any sound implementation, so a gap between served tokens and a free-running
reference says how often routing flipped and cannot tell bfloat16 from int8
(my chip runs, PR 28).  So the scheduler is asked to keep what the timed path
chose (`routing_trace`: the expert of every fed position in every layer, read
back with the step's tokens), the reference is run along those choices, and two
numbers decide `correct`:

  token_logit_gap   the widest gap by which a served token's logit lies below
                    the reference's best, the reference following the served
                    routing (as `bridge_decode`'s, on a path that is comparable);
  router_gap        the widest gap by which an expert the program chose lies
                    below the reference router's best `p + b` at that token and
                    layer: every routing decision is held against the reference.

The control is the reference in int8 in the program's place: its own tokens and
its own routing, held against the float32 reference in the same way.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work_zaya
from perfbench.drivers import bridge_decode


def transformer_config(m, max_seq, dtype):
    """The program's configuration for this file's keys."""
    from tensorframes_tpu.models import transformer

    rope = m["rope_parameters"]["hybrid"]
    return transformer.TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["moe_intermediate_size"], moe_experts=m["num_experts"],
        moe_top_k=m["num_experts_per_tok"], moe_d_ff=m["moe_intermediate_size"],
        max_seq=max_seq, rope_theta=float(rope["rope_theta"]), dtype=dtype, param_dtype=dtype,
        block=transformer.BlockSpec(
            attention="cca", ffn="experts_top1", norm_eps=float(m["rms_norm_eps"]),
            rotary_share=float(rope["partial_rotary_factor"]), head_dim=m["head_dim"],
            router_hidden=m["router_hidden_size"]))


class Driver(bridge_decode.Driver):
    def setup(self):
        import jax
        import jax.numpy as jnp
        from tensorframes_tpu import bridge
        from tensorframes_tpu.ops import bucketing

        m, dtype = self.config, jnp.dtype(self.config["dtype"])
        # first, so that a program without this block fails before anything starts
        cfg = transformer_config(m, self.serve["max_seq"], dtype)
        self.requests = traffic_gen.decode_requests(self.traffic, self.ctx["seed"], m["vocab_size"])
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=self.ctx["root"])
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(self.ctx["root"], "perfbench/drivers/decode_clients.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=self.ctx["root"])
        self.weights = self.ref.make_weights(self.ctx["seed"], m, dtype)
        jax.block_until_ready(self.weights)
        self.ctx["mark"]("weights")
        self.server = bridge.serve(
            max_inflight=self.serve["max_inflight"],
            decode_model={"params": self.weights, "cfg": cfg, "routing_trace": len(self.requests),
                          **{k: self.serve[k] for k in ("max_slots", "max_seq", "tokens_per_page")}})
        self.sched = self.server.decode_scheduler
        self.ctx["mark"]("serve")
        # warm the decode step and every prefill bucket this traffic's prompts reach
        for b in sorted({bucketing.bucket_for(len(r["prompt"])) for r in self.requests}):
            self.sched.submit(np.arange(b, dtype=np.int32) % m["vocab_size"], 2)
        self.ctx["mark"]("warm_buckets")
        host, port = self.server.address[:2]
        self.child.stdin.write(json.dumps({"host": host, "port": port, "requests": self.requests,
                                           "clients": self.traffic["clients"]}) + "\n")
        self.child.stdin.flush()
        if self.child.stdout.readline().strip() != "ready":
            raise RuntimeError("the client process did not come up")
        self.ctx["mark"]("clients_ready")

    def window(self, seconds):
        from tensorframes_tpu import observability

        peak, self.ctx["peak"] = self.ctx["peak"], None  # the dense counts do not apply
        c0, t0, routed = observability.counters(), time.monotonic(), {}
        # the routing counters as the window closes: the parent's window() goes on
        # to wait out the replies in flight, whose steps are not the window's
        at_close = threading.Timer(seconds, lambda: routed.update(observability.counters_delta(c0)))
        at_close.start()
        try:
            obs = super().window(seconds)
        finally:
            self.ctx["peak"] = peak
            at_close.join()
        touched = routed.get("moe_experts_touched", 0)
        obs["experts"] = self.config["num_experts"]
        if peak and obs["steps"]:
            t1 = t0 + obs["window_s"]
            ok = [r for r in self.results if "tokens" in r and r["done"] <= t1]
            started = [len(self.requests[r["i"]]["prompt"]) for r in self.results if r["sent"] <= t1]
            done = [(len(self.requests[r["i"]]["prompt"]), len(r["tokens"])) for r in ok]
            mean_ctx = np.mean([p + (n + 1) / 2 for p, n in done]) if done else 0.0
            # the counter covers steps and prefills: a prefill is given the most it
            # can have touched, so the step's share, and its least time, come out low
            pre_touched = min(touched, work_zaya.prefill_experts_touched_at_most(self.config, started))
            dec = work_zaya.decode_least_time(
                self.config, obs["steps"], obs["decode_tokens"], mean_ctx * obs["decode_tokens"],
                touched - pre_touched, peak)
            pre = work_zaya.prefill_least_time(self.config, started, pre_touched, peak)
            obs.update({"least.step_s": dec / obs["steps"], "least.window_s": dec + pre})
        return obs

    def release(self):
        sched = self.sched
        if sched is not None:  # what the timed path chose, before the scheduler goes
            self.routing = {r["i"]: sched.routing_of(self.requests[r["i"]]["prompt"])
                            for r in getattr(self, "results", []) if "tokens" in r}
        super().release()
        if sched is not None:  # the convolution state goes with the pages
            sched.pool.conv_state = sched._state = None

    def gaps(self, sample, control=False):
        """Over the sampled requests: statistics of the gap by which a served
        token's logit lies below the reference's best, and of the gap by which
        a chosen expert lies below the reference router's best, the reference
        following the served path's routing.  With `control`, tokens and
        routing are those the lower precision puts first."""
        pad = self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"]
        layers = self.config["num_hidden_layers"]
        token_gaps, router_gaps, untraced = [], [], 0
        for r in sample:
            prompt, served = self.requests[r["i"]]["prompt"], r["tokens"]
            fed = len(prompt) + len(served) - 1
            seq = np.zeros((pad,), np.int32)
            seq[: fed + 1] = prompt + served
            at = np.arange(len(prompt) - 1, fed)
            forced = np.full((layers, pad), -1, np.int32)
            tokens = np.asarray(served)
            if control:
                low, chosen, _ = self.ref.logits(self.weights, self.config, seq,
                                                 self.config["control_precision"], with_routing=True)
                tokens, forced[:, :fed] = np.asarray(low[at]).argmax(axis=-1), np.asarray(chosen)[:, :fed]
            elif self.routing.get(r["i"]) is not None and self.routing[r["i"]].shape == (layers, fed):
                forced[:, :fed] = self.routing[r["i"]]
            else:
                untraced += 1
            ref, _, rgap = self.ref.logits(self.weights, self.config, seq, routing=forced, with_routing=True)
            ref = np.asarray(ref[at], np.float64)
            token_gaps.append(ref.max(axis=-1) - ref[np.arange(len(at)), tokens])
            router_gaps.append(np.asarray(rgap, np.float64)[:, :fed].ravel())
        stats = {"tokens_compared": int(sum(g.size for g in token_gaps)), "requests_untraced": untraced}
        for name, gaps in (("token_logit_gap", token_gaps), ("router_gap", router_gaps)):
            if stats["tokens_compared"]:
                g = np.concatenate(gaps)
                stats.update({name: float(g.max()), name + "_mean": float(g.mean()),
                              name + "_p99": float(np.percentile(g, 99)),
                              name + "_over_0": int((g > 0).sum()), name + "_n": int(g.size)})
        print(json.dumps({"control_gaps" if control else "served_gaps": stats}))
        return stats

    def judged(self, stats):
        """Of the statistics, those the configuration's limits name."""
        return {k: stats.get(k, float("nan")) for k in ("token_logit_gap", "router_gap")
                if k in self.config["limits"]}

    def check(self):
        short = sum(1 for r in self.results
                    if "tokens" in r and len(r["tokens"]) != self.requests[r["i"]]["max_new"])
        stats = self.gaps(self.sample())
        return {**self.judged(stats), "no_reply_compared": 0.0 if stats["tokens_compared"] else 1.0,
                "requests_untraced": float(stats["requests_untraced"]),
                "wrong_length_replies": float(short)}

    def control(self):
        return self.judged(self.gaps(self.sample(), control=True))
