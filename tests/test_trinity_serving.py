"""Trinity's block on the paged serving path, against its plain reference.

The stack (``BlockSpec(layer_types=(...), window=..., qk_norm=True,
attn_gate=True, sandwich=True, selection_bias=True, ffn="experts_topk",
dense_layers=1, ...)``) has what no earlier served stack has: layers whose
attention differs by layer, window layers among full ones, each kind's pages
in a pool of its own, a window layer holding a RING of pages a sequence at
any length; per-head RMSNorm on q and k, rotation on the
window layers only, an output gate, norms after both sublayers, and a sigmoid
top-k whose picks a per-expert bias steers.  Everything here runs at tiny
widths in float32 on the CPU (window 8, pages of 4, a full layer among three
window layers), with seeded weights in the layout the program consumes, made
by the reference (``perfbench/refs/trinity_decoder.py``, which imports
nothing of the program) and handed to both sides.

Tolerances.  Program and reference compute the same float32 arithmetic in
another order (paged attention against a whole-sequence softmax, sorted
grouped products against masked dense ones), so logits of unit scale agree
to a few 1e-6; ``ATOL`` 2e-4 leaves room for XLA:CPU's reassociation and
would still catch a query that reads past its window, a full layer rotated,
a gate or a norm left out, each of which moves logits by 1e-2 or more.
Routing is discontinuous, so the tests assert that both sides picked the
same experts.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402
from perfbench import work_trinity  # noqa: E402
from perfbench.drivers.bridge_decode_trinity import transformer_config  # noqa: E402
from perfbench.refs import trinity_decoder as ref  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import kv_pager, moe  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402
from tensorframes_tpu.ops import frame_cache  # noqa: E402

ATOL = 2e-4
PREFILL = jax.jit(kv_pager._prefill_forward, static_argnums=6)
STEP = jax.jit(kv_pager._step_forward, static_argnums=6)
PAGE, CAP = 4, 64
MAX_PAGES = CAP // PAGE
PUBLISHED = os.path.join(ROOT, "perfbench", "configs", "trinity_large_l5_ep8.json")


def _tiny():
    with open(PUBLISHED) as f:
        m = json.load(f)
    return bench_run.overlay(m, m["tiny"])


M = _tiny()
CFG = transformer_config(M, CAP, jnp.float32)
W = M["sliding_window"]
RING = kv_pager.ring_pages(CFG, PAGE)
LAYERS, DENSE, K = M["num_hidden_layers"], 1, M["num_experts_per_tok"]
HELD, ALL = M["num_experts"], M["num_experts"] * M["expert_share"]["of"]


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, M, jnp.float32)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, M["vocab_size"], size=n).astype(np.int32)


def _pool(slots=2):
    return kv_pager.PagePool(CFG, slots * MAX_PAGES + 1, tokens_per_page=PAGE, slots=slots)


def _row(pool, slot, n_tokens):
    """A slot's table row: its full pages (slot-major) and its ring."""
    row = np.zeros((MAX_PAGES + RING,), np.int32)
    n = kv_pager.pages_for(n_tokens, PAGE)
    row[:n] = 1 + slot * MAX_PAGES + np.arange(n)
    row[MAX_PAGES:] = pool.ring_of(slot)
    return row


def _serve(weights, prompt, n_new, slot=1, pool=None):
    """Prefill ``prompt`` into ``slot``'s pages, then ``n_new - 1`` decode
    steps of that row beside an idle one, teacher-forced with the
    reference's own argmax: the logits of every position fed after the
    prompt's last, and each dispatch's picks."""
    pool = pool or _pool()
    kp, vp, _ = pool.take()
    row = _row(pool, slot, len(prompt) + n_new)
    bucket = max(8, 1 << (len(prompt) - 1).bit_length())
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, kp, vp, _, (_, chosen) = PREFILL(
        weights, jnp.asarray(toks), jnp.asarray(row[None]),
        jnp.asarray([len(prompt) - 1], jnp.int32), kp, vp, CFG)
    out, picks = [np.asarray(logits[0])], [np.asarray(chosen)[:, : len(prompt)]]
    seq = list(prompt) + [int(np.argmax(out[-1]))]
    tables = np.zeros((2, MAX_PAGES + RING), np.int32)
    tables[slot] = row
    for _ in range(n_new - 1):
        fed = np.zeros((2, 1), np.int32)
        fed[slot] = seq[-1]
        idx = np.zeros((2,), np.int32)
        idx[slot] = len(seq) - 1
        logits, kp, vp, _, (_, chosen) = STEP(
            weights, jnp.asarray(fed), jnp.asarray(tables), jnp.asarray(idx), kp, vp, CFG)
        out.append(np.asarray(logits[slot, 0]))
        picks.append(np.asarray(chosen)[:, slot: slot + 1])
        seq.append(int(np.argmax(out[-1])))
    return np.stack(out), np.concatenate(picks, axis=1), seq, (kp, vp)


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_block_spec_states_the_pattern_and_is_validated():
    b = CFG.block
    assert b.layer_types == ("window", "window", "full", "window") and b.window == W == 8
    assert b.qk_norm and b.attn_gate and b.sandwich and b.selection_bias
    assert b.routes and not b.stateless and b.dense_layers == DENSE
    assert [b.kind_of(i) for i in range(LAYERS)] == list(b.layer_types)
    assert tfm.BlockSpec().kind_of(3) is None
    with pytest.raises(ValueError):  # a window layer needs a window
        tfm.BlockSpec(layer_types=("window", "full"))
    with pytest.raises(ValueError):  # and a window needs window layers
        tfm.BlockSpec(layer_types=("full",), window=8)
    with pytest.raises(ValueError):
        tfm.BlockSpec(layer_types=("sliding",), window=8)
    with pytest.raises(ValueError):  # all full is no pattern: leave it out
        tfm.BlockSpec(layer_types=("full", "full"))
    with pytest.raises(ValueError):  # the pattern is of plain attention
        tfm.BlockSpec(attention="cca", layer_types=("full",))
    with pytest.raises(ValueError):
        tfm.BlockSpec(selection_bias=True)
    with pytest.raises(ValueError):  # every layer named
        tfm.TransformerConfig(n_layers=3, block=tfm.BlockSpec(layer_types=("full", "full")))
    for dense in (dict(qk_norm=True), dict(attn_gate=True), dict(sandwich=True)):
        assert not tfm.BlockSpec(**dense).stateless


def test_published_cut_is_the_issues_layers():
    with open(PUBLISHED) as f:
        m = json.load(f)
    cfg = transformer_config(m, 16384, jnp.bfloat16)
    assert cfg.block.layer_types == ("window", "window", "full", "window", "window")
    assert cfg.block.dense_layers == 1 and cfg.experts_held == 32 and cfg.moe_experts == 256
    assert abs(cfg.block.multipliers.embedding - 55.42562584220407) < 1e-9
    assert kv_pager.ring_pages(cfg, 16) == 257
    assert kv_pager.paged_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)


def test_router_bias_changes_picks_and_not_weights(weights):
    """The bias decides which experts a token takes; the weights of the
    experts it takes are their unbiased scores, normalised and scaled."""
    bp = jax.tree_util.tree_map(lambda a: a[0], weights["blocks"])
    y = jax.random.normal(jax.random.PRNGKey(4), (64, M["hidden_size"]))
    live = jnp.ones((64,), bool)
    scale = float(M["route_scale"])
    s = np.asarray(jax.nn.sigmoid(y @ bp["router"]))
    bias = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (ALL,))) * 0.2
    plain, w_plain = moe.router_sigmoid(bp, y, live, K, scale)
    biased, w_biased = moe.router_sigmoid(bp, y, live, K, scale, jnp.asarray(bias))
    plain, biased = np.asarray(plain), np.asarray(biased)
    assert (np.sort(plain, -1) != np.sort(biased, -1)).any(axis=-1).mean() > 0.3
    for t in range(64):
        np.testing.assert_array_equal(biased[t], np.argsort(-(s[t] + bias), kind="stable")[:K])
        sp = s[t, biased[t]]
        np.testing.assert_allclose(np.asarray(w_biased)[t], sp / sp.sum() * scale, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_biased).sum(-1), scale, rtol=1e-5)
    # the layer reads the bias from its own params, and only under the spec
    cfg_nobias = transformer_config({**M, "score_func": "sigmoid"}, CAP, jnp.float32)
    assert cfg_nobias.block.selection_bias


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts that all shares of a layer give (4
    shares of 4 experts), with the shared expert counted once, equal what the
    uncut reference gives for the whole layer of 16, bias and all."""
    of = M["expert_share"]["of"]
    uncut = {**M, "num_experts": ALL, "expert_share": {"index": 0, "of": 1}}
    w = ref.make_weights(3, uncut, jnp.float32)
    bp = jax.tree_util.tree_map(lambda a: a[1], w["blocks"])
    assert bp["we_gate"].shape[0] == ALL and bp["expert_bias"].shape == (ALL,)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 11, M["hidden_size"]))
    live = jnp.ones((2, 11), bool)
    y = tfm._rms_norm(x, bp["ln2"], CFG.block.norm_eps)
    shared = tfm.swiglu(y, bp["ws_gate"], bp["ws_up"], bp["ws_down"], jnp.float32)
    total, pairs = -(of - 1) * shared, 0
    for index in range(of):
        cfg = transformer_config({**M, "expert_share": {"index": index, "of": of}}, CAP, jnp.float32)
        mine = {k: bp[k][index * HELD: (index + 1) * HELD] for k in ("we_gate", "we_up", "we_down")}
        out, counts, chosen = moe.experts_topk(bp, y, live, cfg, mine, 0)
        total, pairs = total + out, pairs + int(counts.sum())
    assert pairs == 2 * 11 * K  # every pick fell on exactly one share
    # the uncut layer's routed part, written out: the picks of s + b, the
    # weights of s, every one of the 16 experts
    s = jax.nn.sigmoid(y.reshape(22, -1) @ bp["router"])
    own = jax.lax.top_k(s + bp["expert_bias"], K)[1]
    np.testing.assert_array_equal(np.sort(chosen.reshape(22, K), -1), np.sort(np.asarray(own), -1))
    sp = jnp.take_along_axis(s, own, -1)
    wts = sp / (sp.sum(-1, keepdims=True) + 1e-20) * float(M["route_scale"])
    want = shared.reshape(22, -1)
    for e in range(ALL):
        mine = jnp.sum(jnp.where(own == e, wts, 0.0), -1, keepdims=True)
        want = want + mine * tfm.swiglu(y, bp["we_gate"][e], bp["we_up"][e],
                                        bp["we_down"][e], jnp.float32).reshape(22, -1)
    np.testing.assert_allclose(total.reshape(22, -1), want, atol=1e-5)
    assert float(jnp.abs(out.reshape(22, -1) - want).max()) > 1e-2  # one share alone is not it


def test_window_masks_and_ring_positions():
    """A query at t sees (t - W, t]; a ring slot's keys are the latest page
    congruent to it at or before the query's, and none before position 0."""
    pos = jnp.asarray([[0], [5], [13], [31]], jnp.int32)
    got = np.asarray(kv_pager._ring_positions(pos, PAGE, RING))
    big = np.iinfo(np.int32).max
    for row, t in enumerate([0, 5, 13, 31]):
        cur = t // PAGE
        for slot in range(RING):
            page = cur - (cur - slot) % RING
            want = np.arange(page * PAGE, page * PAGE + PAGE) if page >= 0 else np.full(PAGE, big)
            np.testing.assert_array_equal(got[row, slot * PAGE: (slot + 1) * PAGE], want)
    # every key a window needs is in the ring
    for t in range(40):
        held = set(np.asarray(kv_pager._ring_positions(jnp.asarray([[t]]), PAGE, RING))[0])
        assert set(range(max(0, t - W + 1), t + 1)) <= held


def test_flash_prefill_agrees_with_held_scores(monkeypatch, weights):
    """The prefill's two attention paths, the scores held whole and the flash
    kernel (interpreted here), on one chunk of a window layer and of a full
    one."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 40, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), jnp.float32)
    pos = jnp.arange(40)[None]
    held = [kv_pager._prefill_attention(q, k, v, pos, w) for w in (0, W)]
    monkeypatch.setattr(kv_pager, "PREFILL_SCORES_BYTES", 0)
    flashed = [kv_pager._prefill_attention(q, k, v, pos, w) for w in (0, W)]
    for a, b in zip(held, flashed):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.abs(held[0] - held[1]).max()) > 1e-2


# ---------------------------------------------------------------------------
# prefill, then decode, through both pools, against the reference's full
# forward: logits and picks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 30])
def test_prefill_then_decode_past_three_windows_is_the_references(weights, prompt_len):
    """A prompt, then decode steps out to position 40: three windows deep,
    the ring written over several times, the prompt itself longer than the
    ring for the longer cases."""
    prompt = _tokens(prompt_len, prompt_len)
    got, picks, seq, _ = _serve(weights, prompt, 41 - prompt_len)
    want, ref_picks, gaps = ref.logits(weights, M, np.asarray(seq[:-1]), with_routing=True)
    want = np.asarray(want)[prompt_len - 1:]
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_array_equal(np.sort(picks, -1), np.sort(np.asarray(ref_picks), -1))
    assert float(np.abs(np.asarray(gaps)).max()) == 0.0


def test_each_part_of_the_block_shows_in_the_logits(weights):
    """The reference's mutations move the logits the test above compares
    far past ATOL: that comparison sees every part."""
    seq = _tokens(36, 9)
    sound = np.asarray(ref.logits(weights, M, seq))
    for variant in ref.VARIANTS:
        if variant == "bf16_attention":
            continue  # tiny float32 widths: rounding, the chip's control measures it
        moved = np.asarray(ref.logits(weights, M, seq, variant=variant))
        assert np.abs(moved - sound).max() > 50 * ATOL, variant


def test_a_window_layer_holds_its_ring_and_no_more(weights):
    """Serving slot 1 deep past the window writes the window pool only in
    slot 1's ring, the full pool only in slot 1's pages, and nothing else."""
    pool = _pool()
    _, _, seq, (kp, vp) = _serve(weights, _tokens(20, 3), 21, slot=1, pool=pool)
    window, full = np.asarray(kp[1]), np.asarray(kp[0])
    assert window.shape[2] == pool.window_pages == 2 * RING + 1
    written = np.flatnonzero(np.abs(window).sum(axis=(0, 1, 3, 4)))
    assert set(written) <= set(pool.ring_of(1)) | {0}
    assert set(pool.ring_of(1)) <= set(written)
    n = kv_pager.pages_for(len(seq), PAGE)
    written = np.flatnonzero(np.abs(full).sum(axis=(0, 1, 3, 4)))
    assert set(written) <= set(1 + MAX_PAGES + np.arange(n)) | {0}


def test_page_pool_holds_two_pools_and_charges_both():
    pool = _pool(slots=3)
    (kf, kw), (vf, vw), state = pool.k_pages, pool.v_pages, pool.state
    assert state is None and kf.shape == vf.shape == (1, 2, 3 * MAX_PAGES + 1, PAGE, 16)
    assert kw.shape == vw.shape == (3, 2, 3 * RING + 1, PAGE, 16)
    per_layer = 2 * 2 * PAGE * 16 * 4
    assert pool.page_bytes == per_layer and pool.window_page_bytes == 3 * per_layer
    assert pool.ring_of(2) == list(range(1 + 2 * RING, 1 + 3 * RING))
    stats = pool.stats()
    assert stats["window_pages_total"] == 3 * RING and stats["window_page_bytes"] == 3 * per_layer
    for n in (2, RING, 9):  # the charge is what the sequence holds of both pools
        before = frame_cache._budget.total_bytes
        charge, pages = pool.allocate(n)
        assert len(pages) == n
        assert frame_cache._budget.total_bytes - before == (
            n * pool.page_bytes + min(n, RING) * pool.window_page_bytes)
        pool.free(charge)
        assert frame_cache._budget.total_bytes == before
    dense = tfm.TransformerConfig(vocab_size=61, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                                  d_ff=64, max_seq=CAP, dtype=jnp.float32)
    pair = kv_pager.PagePool(dense, 9, tokens_per_page=PAGE)
    assert pair.ring == 0 and pair.window_page_bytes == 0 and "window_pages_total" not in pair.stats()
    with pytest.raises(ValueError, match="ring"):
        kv_pager.PagePool(CFG, 9, tokens_per_page=PAGE)


# ---------------------------------------------------------------------------
# the scheduler: two tables a slot, the charge, the counters
# ---------------------------------------------------------------------------


def test_scheduler_serves_past_three_windows_as_the_reference_does(weights):
    """Three sequences at once through ``DecodeScheduler``, out to three to
    five windows: each served token is the reference's best along the picks
    the served path kept, and those picks are the reference router's own."""
    sched = DecodeScheduler(weights, CFG, max_slots=3, tokens_per_page=PAGE, max_seq=CAP,
                            routing_trace=8)
    try:
        import threading

        prompts = [_tokens(n, 20 + n) for n in (5, 17, 30)]
        out = {}
        threads = [threading.Thread(target=lambda i=i: out.__setitem__(
            i, sched.submit(prompts[i], 44 - len(prompts[i])))) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert sorted(out) == [0, 1, 2]
        for i, prompt in enumerate(prompts):
            served = out[i]
            seq = np.concatenate([prompt, served])
            routing = sched.routing_of(prompt)
            assert routing.shape == (LAYERS - DENSE, len(seq) - 1, K)
            want, picks, gaps = ref.logits(weights, M, seq[:-1], routing=routing, with_routing=True)
            want = np.asarray(want)[len(prompt) - 1:]
            np.testing.assert_array_equal(want.argmax(-1), served)
            assert float(np.asarray(gaps).max()) == 0.0
        snap = sched.snapshot()
        assert snap["pages_used"] == 0  # every page back
    finally:
        sched.close()


def test_scheduler_table_rows_carry_the_slots_ring(weights):
    sched = DecodeScheduler(weights, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP)
    try:
        assert sched.ring == RING and sched._inputs.tables.shape == (2, MAX_PAGES + RING)
        assert sched.pool.window_pages == 2 * RING + 1
        kernel = sched._step_counts  # heads of 16
        assert kernel["decode_kernel_steps"] == kernel["decode_window_kernel_steps"] == 0
        sched.submit(_tokens(6, 1), 3)
    finally:
        sched.close()


def test_window_counters_count_what_the_window_layers_read(weights):
    """``decode_window_tokens_held``: each live row's keys under the window,
    min(position + 1, W), summed over steps, beside ``decode_tokens_held``."""
    sched = DecodeScheduler(weights, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP)
    try:
        c0 = obs.counters()
        sched.submit(_tokens(5, 2), 8)
        d = obs.counters_delta(c0)
    finally:
        sched.close()
    # the prefill fed 5 positions; 7 steps fed positions 5 .. 11
    assert d["decode_steps"] == 7
    assert d["decode_tokens_held"] == sum(t + 1 for t in range(5, 12))
    assert d["decode_window_tokens_held"] == sum(min(t + 1, W) for t in range(5, 12))
    assert d.get("decode_window_kernel_steps", 0) == 0  # the gather path at heads of 16
    assert "tfs_decode_window_tokens_held_total" in obs.metrics_text()


# ---------------------------------------------------------------------------
# the benchmark's counts: the issue's numbers
# ---------------------------------------------------------------------------


def test_work_counts_of_the_published_cut():
    with open(PUBLISHED) as f:
        m = json.load(f)
    assert work_trinity.dense_layer_params(m) == 176_173_312
    assert work_trinity.expert_layer_params(m) == 997_995_008
    assert 2 * work_trinity.head_params(m) == 153_747_456
    assert work_trinity.held_params(m) == 4_321_903_872
    assert work_trinity.kv_bytes_per_key(m) == 4096
    assert work_trinity.layers_of(m) == (1, 4)


def test_many_pairs_go_through_the_grouped_products_a_run_at_a_time(weights, monkeypatch):
    """A prefill of more token-expert pairs than one grouped product takes
    at once is computed a run of tokens at a time: the same outputs, the
    same counts."""
    bp = jax.tree_util.tree_map(lambda a: a[0], weights["blocks"])
    experts = {k: bp[k] for k in ("we_gate", "we_up", "we_down")}
    rng = np.random.default_rng(8)
    T = 24
    yt = jnp.asarray(rng.standard_normal((T, M["hidden_size"])), jnp.float32)
    picks = jnp.asarray(rng.integers(0, HELD + 1, size=(T, K)), jnp.int32)
    gates = jnp.asarray(rng.uniform(0.1, 0.9, size=(T, K)), jnp.float32)
    whole = moe._grouped_experts(yt, picks, gates, experts, 0, HELD, jnp.float32)
    monkeypatch.setattr(moe, "PAIRS_AT_ONCE", T * K // 3)
    runs = moe._grouped_experts(yt, picks, gates, experts, 0, HELD, jnp.float32)
    np.testing.assert_allclose(runs[0], whole[0], atol=1e-5)
    np.testing.assert_array_equal(runs[1], whole[1])
    assert runs[1].dtype == whole[1].dtype
