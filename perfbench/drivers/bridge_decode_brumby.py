"""Driver for served decoding of a Brumby configuration (`brumby_14b_l8`):
`bridge_decode.Driver`'s server, clients, window, sample and gap, with what
that driver fixes for a dense model over pages replaced: the
`TransformerConfig` is built from this configuration's keys with its block
spec (power retention: a state a slot and no pages, so the server is given no
`tokens_per_page`), the warm-up covers the buckets of every prefill DISPATCH
(a prompt longer than `retention.PREFILL_TOKENS` goes in two, the second
resuming from the first's state), and the least times count the state a
step's live slots hold (`work_brumby`).

`correct` is decided as `decode_chat` decides it — the longest finished
request and others drawn from the seed, teacher-forced through the float32
reference, `token_logit_gap` the widest gap by which a served token's logit
lies below the reference's best — with three things of its own: the reference
(`refs/brumby_decoder`) computes retention in attention form over the whole
sequence, so prefill in chunks, resumption and the recurrent decode step are
held against another algorithm; one of the compared requests must have a
prompt that went in two dispatches, where the traffic has such prompts
(`no_resumed_compared`); and the STATE itself is compared
(`state_readout_gap`), because a token's logit hardly moves with the state's
precision: after the window that request is served once more on the idle
server, its slot's state is kept, and what the reference's queries at the
last fed position read out of it is held against what the reference's
attention form sums over the whole sequence there, every layer and head.  The
model routes nothing, so a free-running reference can decide.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work_brumby
from perfbench.drivers import bridge_decode


def transformer_config(m, max_seq, dtype):
    """The program's configuration for this file's keys."""
    from tensorframes_tpu.models import transformer

    return transformer.TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], max_seq=max_seq, rope_theta=float(m["rope_theta"]),
        dtype=dtype, param_dtype=dtype,
        block=transformer.BlockSpec(attention="retention", ffn="swiglu",
                                    norm_eps=float(m["rms_norm_eps"]), head_dim=m["head_dim"]))


def dispatch_lengths(prompt_len, most):
    """The real tokens of each prefill dispatch of a prompt."""
    return [min(most, prompt_len - at) for at in range(0, prompt_len, most)]


class Driver(bridge_decode.Driver):
    replayed = None  # what `replay` kept, once the window has run

    def setup(self):
        import jax
        import jax.numpy as jnp
        from tensorframes_tpu import bridge
        from tensorframes_tpu.models import retention
        from tensorframes_tpu.ops import bucketing

        m, dtype = self.config, jnp.dtype(self.config["dtype"])
        # first, so that a program without this block fails before anything starts
        cfg = transformer_config(m, self.serve["max_seq"], dtype)
        self.most = retention.PREFILL_TOKENS
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
            decode_model={"params": self.weights, "cfg": cfg,
                          **{k: self.serve[k] for k in ("max_slots", "max_seq")}})
        self.sched = self.server.decode_scheduler
        self.ctx["mark"]("serve")
        # warm the decode step and the bucket of every prefill dispatch this
        # traffic's prompts make (a resumed dispatch runs its bucket's executable)
        buckets = sorted({bucketing.bucket_for(n) for r in self.requests
                          for n in dispatch_lengths(len(r["prompt"]), self.most)})
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

    def window(self, seconds):
        peak, self.ctx["peak"] = self.ctx["peak"], None  # the dense counts do not apply
        t0 = time.monotonic()
        try:
            obs = super().window(seconds)
        finally:
            self.ctx["peak"] = peak
        if peak and obs["steps"]:
            t1 = t0 + obs["window_s"]
            started = [len(self.requests[r["i"]]["prompt"]) for r in self.results if r["sent"] <= t1]
            # a live slot emits a token a step: the slots the steps held, summed
            held = obs["decode_tokens"]
            dec = work_brumby.decode_least_time(self.config, obs["steps"], held, peak)
            pre = work_brumby.prefill_least_time(self.config, obs["prefill_batches"], started, peak)
            obs.update({"least.step_s": dec / obs["steps"], "least.window_s": dec + pre,
                        "least.step_bytes": work_brumby.step_bytes(self.config, obs["steps"], held),
                        "least.state_bytes": work_brumby.state_bytes(self.config, held),
                        "least.kernel_s": work_brumby.kernel_least_time(self.config, held, peak)})
        return obs

    def replay(self):
        """The compared request whose prompt resumed (else the longest) served
        once more, alone, on the idle server: the tokens fed and the state they
        left in its slot, all layers (`S [layers, kvh, ..]`, `z`; on the
        device).  A reply of n tokens has fed the prompt and n - 1 of them."""
        sample = self.sample()
        if not sample:
            return None
        r = next((r for r in sample if self.resumed(r)), sample[0])
        prompt = self.requests[r["i"]]["prompt"]
        served = self.sched.submit(np.asarray(prompt, np.int32), len(r["tokens"]))
        slot = self.sched._free[-1]  # the slot retired last
        S, z = self.sched._ret
        return {"fed": list(prompt) + [int(t) for t in served[:-1]], "state": (S[:, slot], z[:, slot])}

    def release(self):
        sched = self.sched
        try:
            # after a window that ran to its end, outside its counters and its trace
            if sched is not None and getattr(self, "results", None):
                self.replayed = self.replay()
        finally:
            super().release()
            if sched is not None:  # the state is this block's pool: it goes like the pages
                sched.pool.retention = sched._ret = None

    def resumed(self, r):
        return len(self.requests[r["i"]]["prompt"]) > self.most

    def sample(self):
        """`bridge_decode`'s sample, one of which is a request whose prompt
        went in two dispatches, where any such request finished."""
        sample = super().sample()
        if sample and not any(self.resumed(r) for r in sample):
            long = [r for r in self.results if "tokens" in r and self.resumed(r)]
            if long:
                sample[-1] = long[0]
        return sample

    def gaps(self, sample, control=False):
        """As `bridge_decode`'s, the reference asked for the head at the
        positions compared and no other (151,936 logits a position)."""
        pad = self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"]
        widest, count = 0.0, 0
        for r in sample:
            prompt, served = self.requests[r["i"]]["prompt"], r["tokens"]
            seq = np.zeros((pad,), np.int32)
            seq[: len(prompt) + len(served)] = prompt + served
            at = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
            ref = np.asarray(self.ref.logits(self.weights, self.config, seq, at=at), np.float64)
            tokens = np.asarray(served)
            if control:
                low = self.ref.logits(self.weights, self.config, seq,
                                      self.config["control_precision"], at=at)
                tokens = np.asarray(low).argmax(axis=-1)
            gap = ref.max(axis=-1) - ref[np.arange(len(at)), tokens]
            widest, count = max(widest, float(gap.max())), count + len(at)
        return {"token_logit_gap": widest, "tokens_compared": count}

    def state_gaps(self):
        """By layer and KV head, the root mean square by which what the
        reference's queries read out of the replayed request's served state
        (`retention.read_out`, the program's own) differs from what the
        reference puts out at that position, over the reference's."""
        from tensorframes_tpu.models import retention

        fed, (S, z) = self.replayed["fed"], self.replayed["state"]
        seq = np.zeros((self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"],), np.int32)
        seq[: len(fed)] = fed
        q, want = self.ref.read_outs(self.weights, self.config, seq, len(fed) - 1)
        got = retention.read_out(q, S, z)
        by_head = lambda y: np.asarray(y, np.float64).reshape(S.shape[0], S.shape[1], -1)
        diff, want = by_head(got - want), by_head(want)
        return np.sqrt((diff ** 2).sum(-1) / (want ** 2).sum(-1))

    def check(self):
        sample = self.sample()
        due = any(len(q["prompt"]) > self.most for q in self.requests)
        short = sum(1 for r in self.results
                    if "tokens" in r and len(r["tokens"]) != self.requests[r["i"]]["max_new"])
        numbers = self.gaps(sample)
        numbers["no_reply_compared"] = 0.0 if numbers.pop("tokens_compared") else 1.0
        numbers["no_resumed_compared"] = float(due and not any(self.resumed(r) for r in sample))
        numbers["state_readout_gap"] = float(self.state_gaps().max()) if self.replayed else 0.0
        numbers["wrong_length_replies"] = float(short)
        return numbers

    def control(self):
        numbers = self.gaps(self.sample(), control=True)
        numbers.pop("tokens_compared")
        return numbers
