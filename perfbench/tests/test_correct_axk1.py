"""`correct` of `axk1_l7_ep16.decode_grounded` has to come out false when it
should: for the control (the reference in int8, in the program's place) and for
a timed path that is broken underneath, at the `tiny` sizes on the CPU
(`--rehearse`).  The reference follows the picks the served path kept
(`drivers/bridge_decode_axk1.py`), and two numbers decide: the widest gap of a
served token's logit below the reference's best, and the widest gap of a served
pick's score below the reference router's k-th best.
"""

import contextlib
import importlib
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

CELL = "axk1_l7_ep16.decode_grounded"
GAP, ROUTER = "token_logit_gap", "router_gap"


def result_of(seed=11, seconds=2.0, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                         "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture()
def retraced():
    """The serving executables may be traced already, with the sound layer."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct_and_reports_the_share_metrics():
    r = result_of(trace=1)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r["compared"]) == {GAP, ROUTER, "no_reply_compared", "requests_untraced", "wrong_length_replies"}
    assert not any(k in r["metrics"] for k in ("tokens_per_s", "request_p95_ms", "setup_s"))
    # this share holds 4 of 16 experts: about a quarter of the picked pairs are computed here
    assert 15.0 <= r["metrics"]["expert_local_share.decode"]["value"] <= 35.0
    assert 25.0 <= r["metrics"]["experts_touched_share.decode"]["value"] <= 100.0
    assert r["metrics"]["expert_load_skew.decode"]["value"] >= 0.0
    # 3 slots of at most 44 tokens
    assert 3.0 <= r["metrics"]["kv_tokens_held.decode"]["value"] <= 3 * 44
    assert "paged_kernel_step_share.decode" not in r["metrics"]


def test_stale_latent_page_is_not_correct(monkeypatch, retraced):
    """A prefill that leaves the pages' previous rows in place: its own first
    token is sound (it attends over its own rows), every step after reads
    rows nobody wrote."""
    from tensorframes_tpu.models import kv_pager

    sound = kv_pager._page_write

    def stale(kp, vp, k, v, positions, tables, layer, from_zero=False):
        return (kp, vp) if from_zero else sound(kp, vp, k, v, positions, tables, layer)

    monkeypatch.setattr(kv_pager, "_page_write", stale)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


def test_dropped_shared_expert_is_not_correct(monkeypatch, retraced):
    from tensorframes_tpu.models import moe
    from tensorframes_tpu.models import transformer as tfm

    sound = moe.experts_topk

    def dropped(bp, y, live, cfg, experts, layer):
        out, *rest = sound(bp, y, live, cfg, experts, layer)
        return (out - tfm.swiglu(y, bp["ws_gate"], bp["ws_up"], bp["ws_down"], cfg.dtype), *rest)

    monkeypatch.setattr(moe, "experts_topk", dropped)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


def test_weights_normalised_over_the_local_picks_are_not_correct(monkeypatch, retraced):
    """A share that normalises a token's weights over the picks it holds, not
    over all k: each holder would then add a whole 2.5 of weight."""
    import jax.numpy as jnp
    from tensorframes_tpu.models import moe

    sound = moe._grouped_experts

    def local(yt, picks, gates, experts, layer, held, dt):
        mine = jnp.where(picks < held, gates, 0.0)
        total = jnp.sum(mine, axis=-1, keepdims=True)
        gates = jnp.where(total > 0, mine / jnp.maximum(total, 1e-20) * jnp.sum(gates, -1, keepdims=True), gates)
        return sound(yt, picks, gates, experts, layer, held, dt)

    monkeypatch.setattr(moe, "_grouped_experts", local)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


def test_pick_made_wrongly_is_not_correct(monkeypatch, retraced):
    """A router that gives slot 0's last pick to the next expert round: the
    tokens may stay the reference's best along those picks, the decision does
    not."""
    from tensorframes_tpu.models import moe

    sound = moe.router_sigmoid

    def wrong(bp, y, live, k, scale):
        picks, w = sound(bp, y, live, k, scale)
        n = bp["router"].shape[-1]
        taken = picks[0]
        nxt = (taken[-1] + 1 + (taken[:-1] == (taken[-1] + 1) % n).sum()) % n
        return picks.at[0, -1].set(nxt.astype(picks.dtype)), w

    monkeypatch.setattr(moe, "router_sigmoid", wrong)
    r = result_of(seed=13, seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][ROUTER]["value"] > r["compared"][ROUTER]["limit"]


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
    assert control[GAP] > config["limits"][GAP] or control[ROUTER] > config["limits"][ROUTER], control
