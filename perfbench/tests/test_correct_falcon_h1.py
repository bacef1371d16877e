"""`correct` of `falcon_h1_34b_l4.decode_crowd` has to come out false when it
should: for the control (the reference in int8, in the program's place), for a
state kept in bfloat16 and for a timed path that is broken underneath, at the
`tiny` sizes on the CPU (`--rehearse`).  The reference computes the mixer in
SSD's quadratic form over the whole sequence; the program keeps a state
(`drivers/bridge_decode_falcon_h1.py`).
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

CELL = "falcon_h1_34b_l4.decode_crowd"
GAP = "token_logit_gap"
STATE = "ssm_state_gap"


def result_of(seed=11, seconds=2.0, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                         "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_the_cells_metrics():
    r = result_of(trace=1)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r["compared"]) == {GAP, STATE, "no_reply_compared", "wrong_length_replies"}
    assert 0.0 < r["compared"][STATE]["value"] < 1e-5  # float32 on both sides, another algorithm
    assert not any(k in r["metrics"] for k in ("tokens_per_s", "request_p95_ms", "setup_s"))
    # the tiny state (d_state 16) is not the kernel's: the jnp step, counted as such
    assert r["metrics"]["ssm_kernel_step_share.decode"]["value"] == 0.0
    assert 0.0 < r["metrics"]["prefill_pad_share.decode"]["value"] < 50.0
    assert r["metrics"]["kv_tokens_held.decode"]["value"] > 0.0
    # trace and chip metrics are not a CPU's to report
    assert not any(k in r["metrics"] for k in ("ssm_step_roofline", "state_bytes_step_share.decode"))


def test_token_altered_where_it_is_produced(monkeypatch):
    from tensorframes_tpu.models import kv_pager

    sound = kv_pager.paged_decode_step

    def altered(*args, **kw):
        nxt, *rest = sound(*args, **kw)
        cfg = args[6]
        return (nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), *rest)  # slot 0's token

    monkeypatch.setattr(kv_pager, "paged_decode_step", altered)
    r = result_of(seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][GAP]["value"] > r["compared"][GAP]["limit"]


@pytest.mark.parametrize("fault", ["decay_dropped", "pad_folded_in"])
def test_state_kept_wrongly_is_not_correct(monkeypatch, fault):
    """A prefill that forgets the decay (every token weighs as the last), and
    one that folds its bucket's padding into the state."""
    import jax
    import jax.numpy as jnp
    from tensorframes_tpu.models import ssm

    sound_chunked, sound_prefill = ssm.chunked, ssm.mix_prefill

    def no_decay(x, B, C, dt, A, S, chunk):
        return sound_chunked(x, B, C, dt, jnp.zeros_like(A), S, chunk)

    def padded(bp, x, st, layer, slot, last_pos, cfg):
        return sound_prefill(bp, x, st, layer, slot, jnp.full_like(last_pos, x.shape[1] - 1), cfg)

    if fault == "decay_dropped":
        monkeypatch.setattr(ssm, "chunked", no_decay)
    else:
        monkeypatch.setattr(ssm, "mix_prefill", padded)
    jax.clear_caches()  # the serving executables may be traced already, with the sound form
    try:
        r = result_of(seed=13, seconds=3.0)
    finally:
        jax.clear_caches()
    assert r["correct"] is False
    assert r["compared"][STATE]["value"] > r["compared"][STATE]["limit"]


def test_state_kept_in_bfloat16_is_not_correct():
    """The configuration states the state in float32: one rounded to bfloat16
    after every update moves the tokens' logits little and the state much."""
    from perfbench.tools import calibrate_falcon_h1_state

    with calibrate_falcon_h1_state.rounded_state("bfloat16"):
        r = result_of(seed=13, seconds=3.0)
    assert r["correct"] is False
    assert r["compared"][STATE]["value"] > r["compared"][STATE]["limit"]


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
    assert set(control) == {GAP}
    assert control[GAP] > config["limits"][GAP], control
