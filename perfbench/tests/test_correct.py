"""`correct` has to come out false when it should: for the control (the
reference in the nearest lower precision, in the program's place) and for a
timed path that is broken underneath.  These drive the run's own code on the
CPU at the `tiny` sizes, past its look for a chip (`--rehearse`).
"""

import contextlib
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

SCORE, DECODE = "inception_v3.score_cached", "mistral_7b_l8.decode_chat"


def result_of(workload, seed=11, seconds=1.0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                         "--trace", "0", "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_runs_are_correct_and_name_the_cpu():
    for cell in (SCORE, DECODE):
        r = result_of(cell)
        assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
        assert r["device"]["platform"] == "cpu"
        assert not any(k in r["metrics"] for k in ("rows_per_s", "tokens_per_s", "request_p95_ms", "setup_s"))
        assert list(r)[-1] == "compared"


def test_scoring_answer_altered_where_it_is_produced(monkeypatch):
    from tensorframes_tpu.models import inception

    sound = inception.scoring_program

    def altered(*a, **kw):
        fn = sound(*a, **kw)

        def broken(image):
            out = fn(image)
            return {**out, "score": out["score"].at[0].add(0.5)}  # one row of each block
        return broken

    monkeypatch.setattr(inception, "scoring_program", altered)
    r = result_of(SCORE)
    assert r["correct"] is False
    assert r["compared"]["answer_rms_gap"]["value"] > r["compared"]["answer_rms_gap"]["limit"]


def test_scoring_half_of_the_batch_left_out(monkeypatch):
    import jax.numpy as jnp
    from tensorframes_tpu.models import inception

    sound = inception.scoring_program

    def halved(*a, **kw):
        fn = sound(*a, **kw)

        def broken(image):
            half = image.shape[0] // 2
            out = fn(image[:half])  # the other rows get the first half's answers
            return {k: jnp.concatenate([v, v]) for k, v in out.items()}
        return broken

    monkeypatch.setattr(inception, "scoring_program", halved)
    assert result_of(SCORE)["correct"] is False


def test_decode_token_altered_where_it_is_produced(monkeypatch):
    from tensorframes_tpu.models import kv_pager

    sound = kv_pager.paged_decode_step

    def altered(params, toks, tables, indices, k_pages, v_pages, cfg):
        nxt, k, v = sound(params, toks, tables, indices, k_pages, v_pages, cfg)
        return nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), k, v  # slot 0's token

    monkeypatch.setattr(kv_pager, "paged_decode_step", altered)
    r = result_of(DECODE, seconds=3.0)
    assert r["correct"] is False
    assert r["compared"]["token_logit_gap"]["value"] > r["compared"]["token_logit_gap"]["limit"]


def test_decode_reply_cut_short(monkeypatch):
    from tensorframes_tpu.bridge import coalescer

    sound = coalescer.DecodeScheduler.submit

    def short(self, prompt, max_new, **kw):
        return sound(self, prompt, max_new, **kw)[: max(1, max_new - 1)]

    monkeypatch.setattr(coalescer.DecodeScheduler, "submit", short)
    r = result_of(DECODE, seconds=2.0)
    assert r["correct"] is False
    assert r["compared"]["wrong_length_replies"]["value"] > 0


@pytest.mark.parametrize("cell", [SCORE, DECODE])
def test_control_in_lower_precision_is_not_correct(cell):
    """The reference in the configuration's `control_precision`, put in the
    program's place, fails one of the cell's numbers by the limits the
    configuration's file holds."""
    import importlib

    _, spec, config, traffic = run.load_cell(cell, True)
    ctx = run.context(spec, config, traffic, 5)
    driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(ctx)
    try:
        driver.setup()
        driver.window(2.0)
    finally:
        driver.release()
    control = driver.control()
    assert any(v > config["limits"][k] for k, v in control.items()), control
