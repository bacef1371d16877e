"""Driver for served decoding of an A.X-K1 configuration (`axk1_l7_ep16`):
`bridge_decode.Driver`'s server, clients, window and sample, with what that
driver fixes for a dense model replaced, as `bridge_decode_zaya` does for its
own (whose `release`, `check` and `control` this one inherits: keep the served
routing, judge the two gaps the limits name): the `TransformerConfig` is built
from this configuration's keys with its block spec (latent attention under
YaRN, a leading dense layer, a shared expert beside this chip's share of the
routed ones), the least times follow the held experts that got rows and the
tokens the steps held (`work_axk1`), and the comparison with the reference
follows the served path's picks.

Why the picks.  A token takes the 8 best of 192 sigmoid scores, and the 8th
and 9th lie a few thousandths apart: in every layer some token's program
(bfloat16 residual) and float32 reference disagree on the last pick, that
token's layer output then differs by an expert's whole contribution, and every
logit after it moves by far more than rounding moves it (as `bridge_decode_zaya`
found for top-1).  So the scheduler is asked to keep what the timed path chose
(`routing_trace`: the 8 global expert ids of every fed position in every expert
layer, read back with the step's tokens), the reference is run along those
picks, weighted by its own scores at them, and two numbers decide `correct`:

  token_logit_gap   the widest gap by which a served token's logit lies below
                    the reference's best, the reference following the served
                    picks: prefill, then decode in the latent space through the
                    pages, against the reference's expanded full forward;
  router_gap        the widest gap by which the lowest reference score among a
                    token's served picks lies below the reference router's 8th
                    best at that token and layer: 0 where the sets agree, the
                    price of every decision where they do not.

The control is the reference in int8 in the program's place: its own tokens and
its own picks, held against the float32 reference in the same way.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work_axk1
from perfbench.drivers import bridge_decode, bridge_decode_zaya


def transformer_config(m, max_seq, dtype):
    """The program's configuration for this file's keys."""
    from tensorframes_tpu.models import transformer

    sc, share = m["rope_scaling"], m["expert_share"]
    return transformer.TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], moe_experts=m["n_routed_experts"] * share["of"],
        moe_top_k=m["num_experts_per_tok"], moe_d_ff=m["moe_intermediate_size"],
        max_seq=max_seq, rope_theta=float(m["rope_theta"]), dtype=dtype, param_dtype=dtype,
        block=transformer.BlockSpec(
            attention="mla", ffn="experts_topk", norm_eps=float(m["rms_norm_eps"]),
            dense_layers=m["first_k_dense_replace"], shared_experts=m["n_shared_experts"],
            routed_scale=float(m["routed_scaling_factor"]),
            experts_share=(share["index"], share["of"]),
            latent=transformer.LatentSpec(
                q_rank=m["q_lora_rank"], kv_rank=m["kv_lora_rank"], nope_dim=m["qk_nope_head_dim"],
                rope_dim=m["qk_rope_head_dim"], v_dim=m["v_head_dim"]),
            yarn=transformer.Yarn(
                factor=float(sc["factor"]), original_max=sc["original_max_position_embeddings"],
                beta_fast=float(sc["beta_fast"]), beta_slow=float(sc["beta_slow"]),
                mscale=float(sc["mscale"]), mscale_all_dim=float(sc["mscale_all_dim"]))))


class Driver(bridge_decode_zaya.Driver):
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
        c0, t0, closed = observability.counters(), time.monotonic(), {}
        # the device's counters as the window closes: the parent's window() goes on
        # to wait out the replies in flight, whose steps are not the window's
        at_close = threading.Timer(seconds, lambda: closed.update(observability.counters_delta(c0)))
        at_close.start()
        try:
            obs = bridge_decode.Driver.window(self, seconds)
        finally:
            self.ctx["peak"] = peak
            at_close.join()
        obs["experts"] = self.config["n_routed_experts"]  # the experts held: what the moe_* count
        if peak and obs["steps"]:
            t1 = t0 + obs["window_s"]
            started = [len(self.requests[r["i"]]["prompt"]) for r in self.results if r["sent"] <= t1]
            touched = closed.get("moe_experts_touched", 0)
            # the counter covers steps and prefills: a prefill is given the most it
            # can have touched, so the step's share, and its least time, come out low
            pre_touched = min(touched, work_axk1.prefill_experts_touched_at_most(self.config, started))
            dec = work_axk1.decode_least_time(
                self.config, obs["steps"], obs["decode_tokens"], closed.get("decode_tokens_held", 0),
                touched - pre_touched, peak)
            pre = work_axk1.prefill_least_time(self.config, started, pre_touched, peak)
            obs.update({"least.step_s": dec / obs["steps"], "least.window_s": dec + pre})
        return obs

    def gaps(self, sample, control=False):
        """Over the sampled requests: statistics of the gap by which a served
        token's logit lies below the reference's best, and of the gap by which
        a token's served picks lie below the reference router's 8th best, the
        reference following the served path's picks.  With `control`, tokens
        and picks are those the lower precision puts first."""
        m = self.config
        pad = self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"]
        layers, k = m["num_hidden_layers"] - m["first_k_dense_replace"], m["num_experts_per_tok"]
        token_gaps, router_gaps, untraced = [], [], 0
        for r in sample:
            prompt, served = self.requests[r["i"]]["prompt"], r["tokens"]
            fed = len(prompt) + len(served) - 1
            seq = np.zeros((pad,), np.int32)
            seq[: fed + 1] = prompt + served
            at = np.arange(len(prompt) - 1, fed)
            forced = np.full((layers, pad, k), -1, np.int32)
            tokens = np.asarray(served)
            if control:
                low, picks, _ = self.ref.logits(self.weights, m, seq, m["control_precision"],
                                                with_routing=True)
                tokens, forced[:, :fed] = np.asarray(low[at]).argmax(axis=-1), np.asarray(picks)[:, :fed]
            elif self.routing.get(r["i"]) is not None and self.routing[r["i"]].shape == (layers, fed, k):
                forced[:, :fed] = self.routing[r["i"]]
            else:
                untraced += 1
            ref, _, rgap = self.ref.logits(self.weights, m, seq, routing=forced, with_routing=True)
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
