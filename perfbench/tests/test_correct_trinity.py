"""`correct` of `trinity_large_l5_ep8.decode_longmix` has to come out false
when it should: for the control (the reference in int8, in the program's
place) and for a timed path that is broken underneath in each of the ways the
configuration's mechanisms can be, at the `tiny` sizes on the CPU
(`--rehearse`): a window layer attending past its window, the full layer
rotated, the output gate dropped, picks made without the selection bias, and
attention computed in bfloat16 where the file says float32.  The requests
compared are those the window finished, and the reference follows the picks
the served path kept (`drivers/bridge_decode_trinity.py`); three numbers
decide: the widest gap of a served token's logit below the reference's best,
the median root mean square of the program's teacher-forced logits less the
reference's, and the widest gap of a served pick's biased score below the
reference router's k-th best.
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

CELL = "trinity_large_l5_ep8.decode_longmix"
GAP, RMS, ROUTER = "token_logit_gap", "logit_rms_gap", "router_gap"


def result_of(seed=11, seconds=2.0, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                         "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def failed_a_limit(r):
    return any(c["value"] > c["limit"] for k, c in r["compared"].items() if k in (GAP, RMS, ROUTER))


@pytest.fixture()
def retraced():
    """The serving executables may be traced already, with the sound layer."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct_and_reports_the_window_metrics():
    r = result_of(trace=1)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r["compared"]) == {GAP, RMS, ROUTER, "no_reply_compared", "requests_untraced",
                                  "wrong_length_replies", "window_prompt_missing"}
    assert not any(k in r["metrics"] for k in ("tokens_per_s", "request_p95_ms", "setup_s"))
    held = r["metrics"]["kv_tokens_held.decode"]["value"]
    window = r["metrics"]["window_tokens_held.decode"]["value"]
    assert 0 < window < held  # a row holds at most the window (8) in a window layer
    assert window <= 3 * 8
    assert r["metrics"]["window_kernel_step_share.decode"]["value"] == 0.0  # heads of 16
    assert 15.0 <= r["metrics"]["expert_local_share.decode"]["value"] <= 35.0  # 4 of 16


def test_attending_past_the_window_is_not_correct(monkeypatch, retraced):
    from tensorframes_tpu.models import transformer as tfm

    sound = tfm._cache_attention

    def unwindowed(q, ck, cv, positions_q, window=0, k_positions=None):
        return sound(q, ck, cv, positions_q, 0, k_positions)

    monkeypatch.setattr(tfm, "_cache_attention", unwindowed)
    r = result_of(seconds=3.0)
    assert r["correct"] is False and failed_a_limit(r)


def test_rotating_the_full_layer_is_not_correct(monkeypatch, retraced):
    from tensorframes_tpu.models import kv_pager

    monkeypatch.setattr(kv_pager, "_rotates", lambda cfg, site: True)
    r = result_of(seconds=3.0)
    assert r["correct"] is False and failed_a_limit(r)


def test_dropping_the_gate_is_not_correct(monkeypatch, retraced):
    import jax.numpy as jnp
    from tensorframes_tpu.models import transformer as tfm

    monkeypatch.setattr(tfm, "attn_gate", lambda bp, x, cfg: jnp.ones((), cfg.dtype))
    r = result_of(seconds=3.0)
    assert r["correct"] is False and failed_a_limit(r)


def test_picks_without_the_bias_are_not_correct(monkeypatch, retraced):
    from tensorframes_tpu.models import moe

    sound = moe.router_sigmoid

    def unbiased(bp, y, live, k, scale, bias=None):
        return sound(bp, y, live, k, scale)

    monkeypatch.setattr(moe, "router_sigmoid", unbiased)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][ROUTER]["value"] > r["compared"][ROUTER]["limit"]


def test_attention_in_bfloat16_is_not_correct(monkeypatch, retraced):
    from perfbench.tools.calibrate_trinity import bf16_cache_attention
    from tensorframes_tpu.models import transformer as tfm

    monkeypatch.setattr(tfm, "_cache_attention", bf16_cache_attention)
    r = result_of(seconds=3.0)
    assert r["correct"] is False and failed_a_limit(r)
    assert r["compared"][RMS]["value"] > r["compared"][RMS]["limit"]


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
    assert set(control) == {GAP, RMS, ROUTER}
    assert any(control[k] > config["limits"][k] for k in control), control


def test_the_requests_compared_are_the_last_the_window_finished():
    _, spec, config, traffic = run.load_cell(CELL, True)
    driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(
        run.context(spec, config, traffic, 7))
    try:
        driver.setup()
        driver.window(2.0)
    finally:
        driver.release()
    done = {r["i"]: r["done"] for r in driver.results if "tokens" in r}
    inside = sorted((t, i) for i, t in done.items() if t <= driver.closed_at)
    compared = [i for i, _ in driver.checked]
    assert len(compared) == traffic["check_requests"] and all(i in done for i in compared)
    assert all(done[i] <= driver.closed_at for i in compared)
    longest = max(len(r["prompt"]) for r in driver.requests)
    deep = [i for _, i in inside if len(driver.requests[i]["prompt"]) == longest]
    assert deep and deep[-1] in compared  # the last deep one to retire inside the window
    others = [i for _, i in inside if i != deep[-1]][-(len(compared) - 1):]
    assert set(compared) == set(others) | {deep[-1]}
    assert all(driver.routing[i] is not None for i in compared)  # the served picks, kept
