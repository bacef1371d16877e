"""What the decode scheduler reports, by the block it serves: one case for
each kind of block the paged serving path takes — dense GQA, ``cca`` with a
top-1 expert layer, ``mla`` with top-k experts, ``retention``, GQA with a
state-space mixer, and window layers among full ones — each a tiny
configuration whose step takes its kind's decode kernel where it has one.

A scheduler takes one prompt and runs three steps.  The test pins, per kind,
the counters whose presence depends on the block: the values
``observability.counters_delta`` shows of the decode-kernel step counts and
the tokens the window layers held, which of the kind-dependent keys the
scheduler's counter bumps carry (zeros included: a counter of another kind's
kernel is bumped at 0, never left out), and the keys of ``pool.stats()``.  The
scheduler reads none of this off the model; ``kv_pager`` says it."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402

CAP, SLOTS, MAX_NEW = 64, 2, 4  # a prefill's token and three steps
KERNEL_COUNTERS = (
    "decode_kernel_steps", "decode_latent_kernel_steps",
    "decode_ssm_kernel_steps", "decode_window_kernel_steps",
    "decode_window_tokens_held",
)
# the scheduler's counter keys that a block's kind decides
KIND_KEYS = KERNEL_COUNTERS + (
    "decode_state_slots_held", "decode_prefill_resumes",
    "decode_proj_in_place_steps",
)
STATS = {
    "page_tokens", "pages_total", "pages_free", "pages_used", "page_bytes",
    "state_bytes", "allocated_total", "freed_total",
}
ALWAYS = {"decode_kernel_steps", "decode_latent_kernel_steps",
          "decode_ssm_kernel_steps", "decode_proj_in_place_steps"}

# kind: (published config whose tiny overlay it serves, or None for the
# dense block; what the tiny overlay is changed by; tokens a page; the
# counters' values; the kind-dependent keys beyond ALWAYS; pool.stats()
# keys beyond STATS)
CASES = {
    "dense_gqa": (None, {}, 8, {"decode_kernel_steps": 3}, set(), set()),
    "cca_top1": ("zaya1_8b_l20", {}, 4, {}, set(), set()),
    "mla_topk": ("axk1_l7_ep16", {"kv_lora_rank": 128}, 8,
                 {"decode_latent_kernel_steps": 3}, set(), set()),
    "retention": ("brumby_14b_l8", {}, None, {},
                  {"decode_state_slots_held", "decode_prefill_resumes"}, set()),
    "gqa_mixer": ("falcon_h1_34b_l4", {"mamba_d_state": 128}, 4,
                  {"decode_ssm_kernel_steps": 3}, {"decode_state_slots_held"}, set()),
    "window_full": ("trinity_large_l5_ep8", {}, 4,
                    {"decode_window_tokens_held": 23},
                    {"decode_window_kernel_steps", "decode_window_tokens_held"},
                    {"window_pages_total", "window_page_bytes"}),
}
HARNESS = {  # the benchmark's modules of each configuration: (serving, reference)
    "zaya1_8b_l20": ("bridge_decode_zaya", "zaya_decoder"),
    "axk1_l7_ep16": ("bridge_decode_axk1", "axk1_decoder"),
    "brumby_14b_l8": ("bridge_decode_brumby", "brumby_decoder"),
    "falcon_h1_34b_l4": ("bridge_decode_falcon_h1", "falcon_h1_decoder"),
    "trinity_large_l5_ep8": ("bridge_decode_trinity", "trinity_decoder"),
}


def _model(name, over):
    """``(weights, cfg)`` of a case, float32 at the capacity ``CAP``."""
    if name is None:  # heads of 128: the paged kernel takes its step
        cfg = tfm.TransformerConfig(
            vocab_size=97, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
            d_ff=64, max_seq=CAP, dtype=jnp.float32,
        )
        return tfm.init(jax.random.PRNGKey(0), cfg), cfg
    serving, ref = HARNESS[name]
    serving = importlib.import_module(f"perfbench.drivers.{serving}")
    ref = importlib.import_module(f"perfbench.refs.{ref}")
    with open(os.path.join(ROOT, "perfbench", "configs", f"{name}.json")) as f:
        m = json.load(f)
    m = bench_run.overlay(m, {**m["tiny"], **over})
    return ref.make_weights(7, m, jnp.float32), serving.transformer_config(m, CAP, jnp.float32)


@pytest.mark.parametrize("kind", sorted(CASES))
def test_each_kind_reports_its_own_counters_and_zeros_for_the_others(kind, monkeypatch):
    name, over, page, values, keys, stats = CASES[kind]
    weights, cfg = _model(name, over)
    bumped = set()
    note = obs.note_decode_driver

    def noted(deltas):
        bumped.update(deltas)
        note(deltas)

    monkeypatch.setattr(obs, "note_decode_driver", noted)
    sched = DecodeScheduler(weights, cfg, max_slots=SLOTS, tokens_per_page=page, max_seq=CAP)
    try:
        before = obs.counters()
        prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, size=6).astype(np.int32)
        assert len(sched.submit(prompt, MAX_NEW, timeout_s=300)) == MAX_NEW
        d = obs.counters_delta(before)
        assert set(sched.pool.stats()) == STATS | stats
    finally:
        sched.close()
    assert d["decode_steps"] == MAX_NEW - 1
    assert {k: d[k] for k in KERNEL_COUNTERS} == {**dict.fromkeys(KERNEL_COUNTERS, 0), **values}
    assert bumped & set(KIND_KEYS) == ALWAYS | keys
