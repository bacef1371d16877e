"""Driver for served decoding of a Trinity configuration
(`trinity_large_l5_ep8`): `bridge_decode.Driver`'s server, clients and
window, with what that driver fixes for a dense model replaced: the
`TransformerConfig` is built from this configuration's keys with its block
spec (window and full attention layers interleaved, each kind in its own pool
of pages, QK-norm, an output gate, sandwich norms, a leading dense layer and a
bias-steered sigmoid top-4 beside a shared expert) before anything starts, so
that a program without that block fails at once; the full layers' pool is
sized by the traffic file's `pool_pages`; the least times count the keys each
kind of layer read and the held experts that got rows (`work_trinity`).

Which requests.  `correct` holds against the reference what the timed window
itself served, at the occupancy the window ran at: of the requests that
retired inside it, the last with the traffic's longest prompt (14,336 tokens,
3.5 windows deep, where a window layer's ring has wrapped and its first page
is one it has written over) and the last `check_requests - 1` others.  Their
replies, the prefill's token and every decode step's, are held against the
reference's full forward (`refs/trinity_decoder.py`).

Why the picks.  A token takes the 4 best of 256 biased sigmoid scores, and
the 4th and 5th lie a few thousandths apart: over 15,000 positions and four
expert layers some token's program (bfloat16 residual) and float32 reference
disagree on a pick, that token's layer output then differs by an expert's
whole contribution, and every logit after it moves by far more than rounding
moves it (`bridge_decode_axk1` says the same of its top-8).  So the scheduler
keeps what the served path chose (`routing_trace`, for every request that
retires), the reference is run along those picks, weighted by its own scores
at them, and three numbers decide:

  token_logit_gap   the widest gap by which a served token's logit lies below
                    the reference's best, the reference following the served
                    picks;
  logit_rms_gap     the logits themselves: each compared request fed again,
                    its served tokens teacher-forced, through the program's
                    own prefill and decode step at one row on pools of its
                    own; at each decode position the root mean square over
                    the vocabulary of the program's logits less the
                    reference's, and of those the median (a position where
                    the one-row step picks otherwise than the served step did
                    is an outlier the median passes over);
  router_gap        the widest gap by which the lowest biased reference score
                    among a token's served picks lies below the reference
                    router's 4th best at that token and layer: 0 where the
                    sets agree.

The control is the reference in int8 in the program's place, its own tokens,
logits and picks; `control(variant)` puts the reference with one of its
mutations there instead (`trinity_decoder.VARIANTS`).
"""

import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np

from perfbench import traffic as traffic_gen
from perfbench import work_trinity
from perfbench.drivers import bridge_decode


def transformer_config(m, max_seq, dtype):
    """The program's configuration for this file's keys."""
    from tensorframes_tpu.models import transformer

    if m["num_hidden_layers"] != len(m["layers"]):
        raise ValueError("num_hidden_layers counts the layers held")
    if m["score_func"] != "sigmoid" or not m["route_norm"]:
        raise ValueError("the program's router is a normalised sigmoid top-k")
    share = m["expert_share"]
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    return transformer.TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], moe_experts=m["num_experts"] * share["of"],
        moe_top_k=m["num_experts_per_tok"], moe_d_ff=m["moe_intermediate_size"],
        max_seq=max_seq, rope_theta=float(m["rope_theta"]), dtype=dtype, param_dtype=dtype,
        block=transformer.BlockSpec(
            attention="gqa", ffn="experts_topk", norm_eps=float(m["rms_norm_eps"]),
            head_dim=m["head_dim"],
            dense_layers=sum(1 for i in m["layers"] if i < m["num_dense_layers"]),
            shared_experts=m["num_shared_experts"], routed_scale=float(m["route_scale"]),
            experts_share=(share["index"], share["of"]),
            layer_types=tuple(kinds[m["layer_types"][i]] for i in m["layers"]),
            window=m["sliding_window"], qk_norm=True, attn_gate=True,
            sandwich=True, selection_bias=True,
            multipliers=transformer.Multipliers(
                embedding=math.sqrt(m["hidden_size"]) if m["mup_enabled"] else 1.0)))


class Driver(bridge_decode.Driver):
    checked = ()  # [(request index, served tokens)] `correct` compares
    closed_at = None  # the window's close on the clock the clients stamp
    program = None  # {request index: the program's teacher-forced logits}
    refs = None  # {request index: (logits, router gaps) along the served picks}

    def setup(self):
        import jax
        import jax.numpy as jnp
        from tensorframes_tpu import bridge
        from tensorframes_tpu.ops import bucketing

        m, dtype = self.config, jnp.dtype(self.config["dtype"])
        # first, so that a program without this block fails before anything starts
        cfg = self.cfg = transformer_config(m, self.serve["max_seq"], dtype)
        self.requests = traffic_gen.decode_requests(self.traffic, self.ctx["seed"], m["vocab_size"])
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=self.ctx["root"])
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(self.ctx["root"], "perfbench/drivers/decode_clients.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=self.ctx["root"])
        self.weights = self.ref.make_weights(self.ctx["seed"], m, dtype)
        jax.block_until_ready(self.weights)
        self.ctx["mark"]("weights")
        # the picks of every request that retires are kept: those `correct`
        # compares are known only once the window has closed
        self.server = bridge.serve(
            max_inflight=self.serve["max_inflight"],
            decode_model={"params": self.weights, "cfg": cfg,
                          "routing_trace": len(self.requests),
                          **{k: self.serve[k] for k in
                             ("max_slots", "max_seq", "tokens_per_page", "pool_pages")}})
        self.sched = self.server.decode_scheduler
        self.ctx["mark"]("serve")
        # warm the decode step and every prefill bucket this traffic's prompts
        # reach, the top one (the capacity itself) with a prompt just short of it
        for b in sorted({bucketing.bucket_for(len(r["prompt"])) for r in self.requests}):
            n = min(b, self.sched.cap - 2)
            self.sched.submit(np.arange(n, dtype=np.int32) % m["vocab_size"], 2)
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
        from tensorframes_tpu.models import kv_pager
        from tensorframes_tpu.ops import bucketing

        peak, self.ctx["peak"] = self.ctx["peak"], None  # the dense counts do not apply
        c0, t0, closed = observability.counters(), time.monotonic(), {}
        # the device's counters as the window closes: the parent's window() goes on
        # to wait out the replies in flight, whose steps are not the window's
        at_close = threading.Timer(seconds, lambda: closed.update(observability.counters_delta(c0)))
        at_close.start()
        try:
            obs = super().window(seconds)
        finally:
            self.ctx["peak"] = peak
            at_close.join()
        obs["experts"] = self.config["num_experts"]  # the experts held: what the moe_* count
        t1 = self.closed_at = t0 + obs["window_s"]
        if peak and obs["steps"]:
            started = [len(self.requests[r["i"]]["prompt"]) for r in self.results if r["sent"] <= t1]
            touched = closed.get("moe_experts_touched", 0)
            held = closed.get("decode_tokens_held", 0)
            held_w = closed.get("decode_window_tokens_held", 0)
            # the counter covers steps and prefills: a prefill is given the most it
            # can have touched, so the step's share, and its least time, come out low
            pre_touched = min(touched, work_trinity.prefill_experts_touched_at_most(self.config, started))
            dec = work_trinity.decode_least_time(
                self.config, obs["steps"], obs["decode_tokens"], held, held_w,
                touched - pre_touched, peak)
            pre = work_trinity.prefill_least_time(self.config, started, pre_touched, peak)
            # the prefills the flash kernel ran: prompts whose bucket's scores pass
            # what the program holds whole, sent a second or more before the close
            # (a later one may prefill after it): a count that errs low
            heads = self.config["num_attention_heads"]
            flashed = [p for p in (len(self.requests[r["i"]]["prompt"]) for r in self.results
                                   if r["sent"] <= t1 - 1.0)
                       if heads * bucketing.bucket_for(p) ** 2 * 4 > kv_pager.PREFILL_SCORES_BYTES]
            obs.update({
                "least.step_s": dec / obs["steps"], "least.window_s": dec + pre,
                "least.kernel_s": work_trinity.kernel_least_time(self.config, held, held_w, peak),
                "least.prefill_attention_flops": sum(
                    work_trinity.prefill_attention_flops(self.config, flashed))})
        return obs

    def finished_in_window(self):
        """The requests `correct` holds against the reference, among those
        the window finished: the last to retire with the traffic's longest
        prompt and the last `check_requests - 1` others, in the order they
        retired.  [(request index, tokens)]."""
        inside = sorted((r for r in self.results if "tokens" in r and r["done"] <= self.closed_at),
                        key=lambda r: r["done"])
        longest = max(len(r["prompt"]) for r in self.requests)
        deep = [r for r in inside if len(self.requests[r["i"]]["prompt"]) == longest][-1:]
        rest = [r for r in inside if r not in deep]
        rest = rest[len(rest) - (int(self.traffic["check_requests"]) - len(deep)):]
        return [(r["i"], r["tokens"]) for r in sorted(deep + rest, key=lambda r: r["done"])]

    def release(self):
        sched = self.sched
        try:
            if sched is not None and getattr(self, "results", None) is not None:
                self.checked, self.refs = self.finished_in_window(), {}
                self.routing = {i: sched.routing_of(self.requests[i]["prompt"])
                                for i, _ in self.checked}
        finally:
            super().release()

    def teacher_forced(self):
        """{request index: the program's logits [decode positions, vocab]}
        for the compared requests: each prompt prefilled through
        `paged_prefill` (padded to the bucket of the longest compared
        prompt, one executable), then its served tokens but the last fed one
        at a time through the decode step at one row (`apply_paged`, which
        returns the logits the scheduler's step takes its argmax of), on
        pools of its own, with the params turned as the scheduler turns
        them.  Run on an idle chip, after the scheduler's pools are gone."""
        import jax
        import jax.numpy as jnp
        from tensorframes_tpu.models import kv_pager
        from tensorframes_tpu.ops import bucketing

        cfg, P = self.cfg, self.serve["tokens_per_page"]
        max_pages = kv_pager.pages_for(self.serve["max_seq"], P)
        params = kv_pager.serving_params(self.weights, cfg)

        def step(params, tokens, tables, indices, k_pages, v_pages, cfg):
            # a function of this call's own, traced anew: what the model's
            # modules say at this call is what runs
            return kv_pager.apply_paged(params, tokens, tables, indices, k_pages, v_pages, cfg)

        step = jax.jit(step, static_argnames=("cfg",), donate_argnames=("k_pages", "v_pages"))
        bucket = bucketing.bucket_for(max(len(self.requests[i]["prompt"]) for i, _ in self.checked))
        out = {}
        for i, served in self.checked:
            prompt = self.requests[i]["prompt"]
            pool = kv_pager.PagePool(cfg, max_pages + 1, tokens_per_page=P, slots=1)
            kp, vp, state = pool.take()
            held = kv_pager.pages_for(len(prompt) + len(served), P)
            table = np.zeros((1, max_pages + pool.ring), np.int32)
            table[0, :held] = np.arange(1, held + 1)
            table[0, max_pages:] = pool.ring_of(0)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, : len(prompt)] = prompt
            _, kp, vp, _, _ = kv_pager.paged_prefill(
                params, toks, table, np.array([len(prompt) - 1], np.int32), kp, vp, cfg,
                state, np.zeros((1,), np.int32))
            rows = []
            for j, t in enumerate(served[:-1]):
                logits, kp, vp = step(params, np.array([[t]], np.int32), table,
                                      np.array([len(prompt) + j], np.int32), kp, vp, cfg=cfg)
                rows.append(logits[0, 0])
            out[i] = np.asarray(jnp.stack(rows)) if rows else np.zeros((0, cfg.vocab_size), np.float32)
            del kp, vp
        return out

    def gaps(self, control=False, variant=None):
        """Over the compared requests: statistics of the gap by which a
        served token's logit lies below the reference's best, of the root
        mean square by which the program's teacher-forced logits at a decode
        position differ from the reference's, and of the gap by which a
        token's served picks lie below the reference router's 4th best, the
        reference following the served path's picks.  With `control` (or a
        `variant`), tokens, logits and picks are those the lower precision
        (the mutated reference) gives."""
        m = self.config
        pad = self.traffic["prompt_tokens"]["max"] + self.traffic["max_new"]["max"]
        layers = m["num_hidden_layers"] - sum(1 for i in m["layers"] if i < m["num_dense_layers"])
        k = m["num_experts_per_tok"]
        sound = not (control or variant)
        if sound and self.program is None and self.checked:
            self.program = self.teacher_forced()
        token_gaps, rms_gaps, router_gaps, untraced = [], [], [], 0
        for i, served in self.checked:
            prompt = self.requests[i]["prompt"]
            fed = len(prompt) + len(served) - 1
            seq = np.zeros((pad,), np.int32)
            seq[: fed + 1] = prompt + list(served)
            at = np.arange(len(prompt) - 1, fed)
            forced = np.full((layers, pad, k), -1, np.int32)
            tokens = np.asarray(served)
            if not sound:
                low, picks, _ = self.ref.logits(
                    self.weights, m, seq, m["control_precision"] if control else "float32",
                    with_routing=True, at=at, variant=variant)
                low = np.asarray(low, np.float64)
                tokens, forced[:, :fed] = low.argmax(axis=-1), np.asarray(picks)[:, :fed]
                program = low[1:]
            else:
                program = np.asarray(self.program[i], np.float64)
                if self.routing.get(i) is not None and self.routing[i].shape == (layers, fed, k):
                    forced[:, :fed] = self.routing[i]
                else:
                    untraced += 1
            if not sound or i not in self.refs:
                ref, _, rgap = self.ref.logits(self.weights, m, seq, routing=forced, with_routing=True, at=at)
                ref = (np.asarray(ref, np.float64), np.asarray(rgap, np.float64))
                if sound:  # the sound reference is the same for every reading
                    self.refs[i] = ref
            ref, rgap = self.refs[i] if sound else ref
            token_gaps.append(ref.max(axis=-1) - ref[np.arange(len(at)), tokens])
            rms_gaps.append(np.sqrt(np.mean((program - ref[1:]) ** 2, axis=-1)))
            router_gaps.append(rgap[:, :fed].ravel())
        stats = {"tokens_compared": int(sum(g.size for g in token_gaps)), "requests_untraced": untraced}
        for name, gaps in (("token_logit_gap", token_gaps), ("router_gap", router_gaps)):
            if stats["tokens_compared"]:
                g = np.concatenate(gaps)
                stats.update({name: float(g.max()), name + "_mean": float(g.mean()),
                              name + "_p99": float(np.percentile(g, 99)),
                              name + "_over_0": int((g > 0).sum()), name + "_n": int(g.size)})
        g = np.concatenate(rms_gaps) if rms_gaps else np.zeros((0,))
        if g.size:
            stats.update({"logit_rms_gap": float(np.median(g)), "logit_rms_gap_p10": float(np.percentile(g, 10)),
                          "logit_rms_gap_p90": float(np.percentile(g, 90)),
                          "logit_rms_gap_max": float(g.max()), "logit_rms_gap_n": int(g.size)})
        label = variant or ("control" if control else "served")
        print(json.dumps({label + "_gaps": stats}))
        return stats

    def judged(self, stats):
        """Of the statistics, those the configuration's limits name."""
        return {k: stats.get(k, float("nan")) for k in ("token_logit_gap", "logit_rms_gap", "router_gap")
                if k in self.config["limits"]}

    def check(self):
        short = sum(1 for r in self.results
                    if "tokens" in r and len(r["tokens"]) != self.requests[r["i"]]["max_new"])
        longest = max(len(r["prompt"]) for r in self.requests)
        stats = self.gaps()
        return {**self.judged(stats), "no_reply_compared": 0.0 if stats["tokens_compared"] else 1.0,
                "requests_untraced": float(stats["requests_untraced"]),
                "wrong_length_replies": float(short),
                "window_prompt_missing": 0.0 if any(
                    len(self.requests[i]["prompt"]) == longest for i, _ in self.checked) else 1.0}

    def control(self, variant=None):
        return self.judged(self.gaps(control=variant is None, variant=variant))
