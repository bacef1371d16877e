"""Driver for served decoding of a Falcon-H1 configuration (`falcon_h1_34b_l4`):
`bridge_decode.Driver`'s server, clients, window, sample and gap, with what
that driver fixes for a dense model replaced: the `TransformerConfig` is
built from this configuration's keys with its block spec (grouped-query
attention over pages beside a Mamba-2 mixer whose state the pool holds a
slot, and the model's named multipliers) before anything starts, so that a
program without that block fails at once; the least times count the state
the step's live slots hold (`work_falcon_h1`); and the pools released after
the window include the state.

`correct` is decided as `decode_documents` decides it: the longest finished
request and others drawn from the seed, teacher-forced through the float32
reference, `token_logit_gap` the widest gap by which a served token's logit
(after `lm_head_multiplier`) lies below the reference's best.  The
reference (`refs/falcon_h1_decoder`) computes Mamba-2 in SSD's quadratic
form over the whole sequence, so the chunked prefill, the recurrent decode
step and the pool are held against another algorithm.  And the STATE itself
is compared (`ssm_state_gap`), because a token's logit hardly moves with the
state's precision: after the window the longest compared request whose
prompt was padded to its bucket is served once more on the idle server, its
slot's state is kept, and it is held
against the reference's closed form at the last fed position: the relative
root mean square by layer and head, the worst of them.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work_falcon_h1
from perfbench.drivers import bridge_decode


def transformer_config(m, max_seq, dtype):
    """The program's configuration for this file's keys."""
    from tensorframes_tpu.models import transformer

    if m["mamba_d_ssm"] != m["mamba_n_heads"] * m["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is the heads times the head's size")
    if m["mamba_norm_before_gate"] or not m["mamba_rms_norm"] or not m["mamba_conv_bias"]:
        raise ValueError("the program's mixer normalises after the gate and has a conv bias")
    gate, down = m["mlp_multipliers"]
    return transformer.TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], max_seq=max_seq, rope_theta=float(m["rope_theta"]),
        dtype=dtype, param_dtype=dtype,
        block=transformer.BlockSpec(
            attention="gqa", ffn="swiglu", norm_eps=float(m["rms_norm_eps"]),
            head_dim=m["head_dim"],
            mixer=transformer.SSMSpec(
                heads=m["mamba_n_heads"], head_dim=m["mamba_d_head"],
                groups=m["mamba_n_groups"], d_state=m["mamba_d_state"],
                d_conv=m["mamba_d_conv"], chunk=m["mamba_chunk_size"]),
            multipliers=transformer.Multipliers(
                embedding=float(m["embedding_multiplier"]),
                attention_in=float(m["attention_in_multiplier"]),
                key=float(m["key_multiplier"]),
                attention_out=float(m["attention_out_multiplier"]),
                ssm_in=float(m["ssm_in_multiplier"]),
                ssm_segments=tuple(float(x) for x in m["ssm_multipliers"]),
                ssm_out=float(m["ssm_out_multiplier"]),
                mlp_gate=float(gate), mlp_down=float(down),
                head=float(m["lm_head_multiplier"]))))


class Driver(bridge_decode.Driver):
    replayed = None  # what `replay` kept, once the window has run

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
            decode_model={"params": self.weights, "cfg": cfg,
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
        peak, self.ctx["peak"] = self.ctx["peak"], None  # the dense counts do not apply
        t0 = time.monotonic()
        try:
            obs = super().window(seconds)
        finally:
            self.ctx["peak"] = peak
        if peak and obs["steps"]:
            t1 = t0 + obs["window_s"]
            started = [len(self.requests[r["i"]]["prompt"]) for r in self.results if r["sent"] <= t1]
            # a live slot emits a token a step: the slots the steps held,
            # summed; a stream at step j holds prompt + j tokens
            slots = obs["decode_tokens"]
            done = [(len(self.requests[r["i"]]["prompt"]), len(r["tokens"]))
                    for r in self.results if "tokens" in r and r["done"] <= t1]
            tokens = (np.mean([p + (n + 1) / 2 for p, n in done]) if done else 0.0) * slots
            dec = work_falcon_h1.decode_least_time(self.config, obs["steps"], slots, tokens, peak)
            pre = work_falcon_h1.prefill_least_time(self.config, obs["prefill_batches"], started, peak)
            obs.update({
                "least.step_s": dec / obs["steps"], "least.window_s": dec + pre,
                "least.step_bytes": work_falcon_h1.step_bytes(self.config, obs["steps"], slots, tokens),
                "least.state_bytes": work_falcon_h1.state_bytes(self.config, slots),
                "least.kernel_s": work_falcon_h1.kernel_least_time(self.config, slots, peak)})
        return obs

    def replay(self):
        """The longest compared request whose prefill was padded (else the
        longest) served once more, alone, on the idle server: the tokens fed
        and the SSM state they left in its slot, all layers (`[layers, heads,
        P, N]`, on the device).  A reply of n tokens has fed the prompt and
        n - 1 of them."""
        from tensorframes_tpu.ops import bucketing

        sample = self.sample()
        if not sample:
            return None
        padded = lambda r: bucketing.bucket_for(len(self.requests[r["i"]]["prompt"])) > len(  # noqa: E731
            self.requests[r["i"]]["prompt"])
        r = next((r for r in sample if padded(r)), sample[0])
        prompt = self.requests[r["i"]]["prompt"]
        served = self.sched.submit(np.asarray(prompt, np.int32), len(r["tokens"]))
        slot = self.sched._free[-1]  # the slot retired last
        return {"fed": list(prompt) + [int(t) for t in served[:-1]],
                "state": self.sched._ret[0][:, slot]}

    def release(self):
        sched = self.sched
        try:
            # after a window that ran to its end, outside its counters and its trace
            if sched is not None and getattr(self, "results", None):
                self.replayed = self.replay()
        finally:
            super().release()
            if sched is not None:  # the state goes with the pages
                sched._ret = None

    def gaps(self, sample, control=False):
        """As `bridge_decode`'s, the reference asked for the head at the
        positions compared and no other (261,120 logits a position)."""
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
        """By layer and head, the root mean square by which the replayed
        request's served state differs from the reference's closed form at
        the last fed position, over the reference's: [layers, heads]."""
        fed, got = self.replayed["fed"], self.replayed["state"]
        seq = np.zeros((self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"],), np.int32)
        seq[: len(fed)] = fed
        want = np.asarray(self.ref.ssm_states(self.weights, self.config, seq, len(fed) - 1), np.float64)
        diff = np.asarray(got, np.float64) - want
        return np.sqrt((diff ** 2).sum((-1, -2)) / (want ** 2).sum((-1, -2)))

    def check(self):
        numbers = super().check()
        numbers["ssm_state_gap"] = float(self.state_gaps().max()) if self.replayed else 0.0
        return numbers
