"""`correct` of `zaya1_8b_l20.decode_reason` has to come out false when it
should: for the control (the reference in int8, in the program's place) and for
a timed path that is broken underneath, at the `tiny` sizes on the CPU
(`--rehearse`).  The reference follows the routing the served path kept
(`drivers/bridge_decode_zaya.py`), and two numbers decide: the widest gap of a
served token's logit below the reference's best, and the widest gap of a chosen
expert below the reference router's best.
"""

import contextlib
import importlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

CELL = "zaya1_8b_l20.decode_reason"
GAP, ROUTER = "token_logit_gap", "router_gap"


def result_of(seed=11, seconds=2.0, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                         "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_the_routing_metrics():
    r = result_of(trace=1)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r["compared"]) == {GAP, ROUTER, "no_reply_compared", "requests_untraced", "wrong_length_replies"}
    assert not any(k in r["metrics"] for k in ("tokens_per_s", "request_p95_ms", "setup_s"))
    # 3 slots over 4 experts: between a quarter and three quarters of the experts a layer-step
    assert 25.0 <= r["metrics"]["experts_touched_share.decode"]["value"] <= 75.0
    assert 0.0 <= r["metrics"]["expert_load_skew.decode"]["value"] <= 300.0


def test_token_altered_where_it_is_produced(monkeypatch):
    from tensorframes_tpu.models import kv_pager

    sound = kv_pager.paged_decode_step

    def altered(*args):
        nxt, *rest = sound(*args)
        cfg = args[6]
        return (nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), *rest)  # slot 0's token

    monkeypatch.setattr(kv_pager, "paged_decode_step", altered)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


def test_stale_convolution_state_is_not_correct(monkeypatch):
    """A prefill that leaves the slot's previous tenant's state in place."""
    from tensorframes_tpu.models import kv_pager

    sound = kv_pager.paged_prefill

    def stale(params, toks, table, last_pos, k_pages, v_pages, cfg, state, slot):
        tok, k, v, _, stats = sound(params, toks, table, last_pos, k_pages, v_pages, cfg, state, slot)
        return tok, k, v, state, stats

    monkeypatch.setattr(kv_pager, "paged_prefill", stale)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


def test_reply_cut_short(monkeypatch):
    from tensorframes_tpu.bridge import coalescer

    sound = coalescer.DecodeScheduler.submit_request

    def short(self, prompt, max_new, **kw):
        req = sound(self, prompt, max_new, **kw)
        del req.out[max(1, max_new - 1):]
        return req

    monkeypatch.setattr(coalescer.DecodeScheduler, "submit_request", short)
    r = result_of()
    assert r["correct"] is False
    assert r["compared"]["wrong_length_replies"]["value"] > 0


def test_control_in_lower_precision_is_not_correct():
    _, spec, config, traffic = run.load_cell(CELL, True)
    ctx = run.context(spec, config, traffic, 5)
    driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(ctx)
    try:
        driver.setup()
        driver.window(2.0)
    finally:
        driver.release()
    control = driver.control()
    assert set(control) == {GAP, ROUTER}
    assert control[GAP] > config["limits"][GAP] and control[ROUTER] > config["limits"][ROUTER], control


def test_expert_chosen_wrongly_is_not_correct(monkeypatch):
    """A router that sends slot 0's token to the next expert: the tokens may
    well stay the reference's best along that routing, the decision does not."""
    import jax.numpy as jnp
    from tensorframes_tpu.models import moe

    sound = moe.router_top1

    def wrong(bp, y, r_prev, live, eps):
        expert, gate, r = sound(bp, y, r_prev, live, eps)
        n = bp["router_bias"].shape[-1]
        return expert.at[0].set(jnp.where(expert[0] < n, (expert[0] + 1) % n, expert[0])), gate, r

    import jax

    monkeypatch.setattr(moe, "router_top1", wrong)
    jax.clear_caches()  # the serving executables may be traced already, with the sound router
    try:
        r = result_of(seed=13, seconds=3.0)
    finally:
        jax.clear_caches()
    assert r["correct"] is False
    assert r["compared"][ROUTER]["value"] > r["compared"][ROUTER]["limit"]
