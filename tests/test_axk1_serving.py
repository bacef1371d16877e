"""A.X-K1's block on the paged serving path, against its plain reference.

The block (``BlockSpec(attention="mla", ffn="experts_topk", dense_layers=1,
...)``) has what no earlier served block has: ONE pool of pages that holds a
token's latent row where K and V by head would be, a decode step that attends
over those rows in the latent space (``W_kvb`` absorbed into the query), a
prefill that attends over its own rows expanded by head, rotary frequencies
from a YaRN table, a leading dense layer run as a scan of its own before the
expert layers, and an expert layer that routes over all experts, takes the
k best by sigmoid score beside a shared expert, and computes the share of them
it holds.  Everything here runs at tiny widths in float32 on the CPU, with
seeded weights in the layout the program consumes, made by the reference
(``perfbench/refs/axk1_decoder.py``, which imports nothing of the program and
knows the expanded attention form only) and handed to both sides.

Tolerances.  Program and reference compute the same float32 arithmetic in
another order (absorbed against expanded attention, cached rows against a
whole-sequence softmax, sorted grouped products summed by scatter-add against
masked dense ones), so logits of unit scale agree to a few 1e-6; ``ATOL`` 2e-4
leaves room for XLA:CPU's reassociation and would still catch a wrong
frequency table, a softmax scale without its ``m^2``, a missing shared expert
or weights normalised over the held picks only, each of which moves logits by
1e-2 or more.  Routing is discontinuous, so a comparison is only meaningful if
both sides picked the same experts: the tests assert that they did.
"""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402
from perfbench.drivers.bridge_decode_axk1 import transformer_config  # noqa: E402
from perfbench.refs import axk1_decoder as ref  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeRefused, DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import kv_pager, mla, moe  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402

ATOL = 2e-4
PREFILL = jax.jit(kv_pager._prefill_forward, static_argnums=6)
STEP = jax.jit(kv_pager._step_forward, static_argnums=6)
PAGE, CAP, SLOTS = 4, 32, 3
MAX_PAGES = CAP // PAGE


def _tiny():
    with open(os.path.join(ROOT, "perfbench", "configs", "axk1_l7_ep16.json")) as f:
        m = json.load(f)
    return bench_run.overlay(m, m["tiny"])


M = _tiny()
CFG = transformer_config(M, CAP, jnp.float32)
LAYERS, DENSE, K = M["num_hidden_layers"], M["first_k_dense_replace"], M["num_experts_per_tok"]
HELD, ALL = M["n_routed_experts"], M["n_routed_experts"] * M["expert_share"]["of"]


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, M, jnp.float32)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, M["vocab_size"], size=n).astype(np.int32)


def _pool():
    return kv_pager.PagePool(CFG, SLOTS * MAX_PAGES + 1, tokens_per_page=PAGE)


def _table(first_page, n_tokens):
    row = np.zeros((MAX_PAGES,), np.int32)
    n = kv_pager.pages_for(n_tokens, PAGE)
    row[:n] = np.arange(first_page, first_page + n)
    return row


def _prefill(weights, pages, prompt, bucket, table):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, pages, _, _, stats = PREFILL(
        weights, jnp.asarray(toks), jnp.asarray(table[None]),
        jnp.asarray([len(prompt) - 1], jnp.int32), pages, None, CFG)
    return np.asarray(logits[0]), pages, stats


def _layer(weights, i):
    return jax.tree_util.tree_map(lambda a: a[i], weights["blocks"])


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_block_spec_states_the_latent_block_and_is_validated():
    b = CFG.block
    assert (b.attention, b.ffn, b.dense_layers, b.shared_experts) == ("mla", "experts_topk", 1, 1)
    assert b.experts_share == (0, 4) and CFG.moe_experts == ALL and CFG.experts_held == HELD
    assert b.routes and not b.stateless and b.routed_scale == 2.5
    assert b.latent == tfm.LatentSpec(q_rank=24, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8)
    with pytest.raises(ValueError):  # the kind and its sizes go together
        tfm.BlockSpec(attention="mla")
    with pytest.raises(ValueError):
        tfm.BlockSpec(latent=b.latent)
    with pytest.raises(ValueError):  # a share outside its holders
        tfm.BlockSpec(experts_share=(4, 4))
    with pytest.raises(ValueError):  # experts that do not divide over the holders
        tfm.TransformerConfig(moe_experts=6, block=tfm.BlockSpec(ffn="experts_topk", experts_share=(0, 4)))
    with pytest.raises(ValueError):  # more leading dense layers than layers
        tfm.TransformerConfig(n_layers=2, block=tfm.BlockSpec(dense_layers=3))
    with pytest.raises(NotImplementedError):
        tfm.apply({}, jnp.zeros((1, 4), jnp.int32), CFG)


def test_a_latent_block_has_no_one_head_size():
    """192 (a query head), 128 (a value head) or 576 (a page's row): the
    question is ambiguous, so the configuration does not answer it, and
    nothing on the serving path asks."""
    with pytest.raises(ValueError, match="latent"):
        CFG.head_dim
    assert mla.page_width(CFG) == 16 + 8 and mla.row_width(CFG) == 128
    published = transformer_config(json.load(open(
        os.path.join(ROOT, "perfbench", "configs", "axk1_l7_ep16.json"))), 3072, jnp.bfloat16)
    assert mla.page_width(published) == 576 and mla.row_width(published) == 640
    assert published.experts_held == 12 and published.moe_experts == 192


@pytest.mark.parametrize("factor", [1, 32])
def test_yarn_table_is_the_formula(factor):
    """The program's frequency table against the reference's own writing of
    the formula, at factor 1 (plain rotary: theta^(-2i/d)) and at the
    published 32 over 64 rotated dimensions."""
    sc = {"factor": factor, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1}
    yarn = tfm.Yarn(factor=float(factor), original_max=4096, mscale=1.0, mscale_all_dim=1.0)
    table = tfm.rope_table(10000.0, 64, yarn)
    np.testing.assert_allclose(table, ref.yarn_inv_freq(10000, 64, sc), rtol=1e-6)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    if factor == 1:
        np.testing.assert_allclose(table, plain, rtol=1e-6)
        np.testing.assert_allclose(tfm.rope_table(10000.0, 64), plain, rtol=1e-6)
    else:
        # corr(32) = 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47, corr(1) = 22.51: the 11
        # fastest dimensions keep their frequency, from the 24th on they are divided by 32
        np.testing.assert_allclose(table[:11], plain[:11], rtol=1e-6)
        np.testing.assert_allclose(table[23:], plain[23:] / 32, rtol=1e-6)
        assert np.all(table[11:23] < plain[11:23]) and np.all(table[11:23] > plain[11:23] / 32)
        assert abs(tfm.yarn_mscale(32, 1.0) - 1.3466) < 1e-4


def test_rope_takes_a_table_or_a_base():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    np.testing.assert_allclose(
        tfm._rope(x, pos, tfm.rope_table(1e4, 16)), tfm._rope(x, pos, 1e4), atol=1e-6)
    scaled = tfm._rope(x, pos, tfm.rope_table(1e4, 16, tfm.Yarn(factor=4.0, original_max=16)))
    assert float(jnp.abs(scaled - tfm._rope(x, pos, 1e4)).max()) > 1e-2
    np.testing.assert_array_equal(scaled[:, 0], x[:, 0])  # position 0 is not turned


def test_absorbed_attention_equals_expanded_on_the_same_rows(weights):
    """The decode form against the prefill form over the same latent rows:
    ``W_kvb`` on the query's side and on the output's gives what per-head
    keys and values give, for every query position of a causal chunk."""
    bp = _layer(weights, 1)
    length = 9
    x = jax.random.normal(jax.random.PRNGKey(2), (2, length, M["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(length), (2, length))
    q_n, q_r, row = mla.project(bp, x, pos, CFG)
    assert row.shape == (2, length, 1, mla.row_width(CFG))
    assert float(jnp.abs(row[..., mla.page_width(CFG):]).max()) == 0.0
    rows = row[:, :, 0]
    expanded = mla.attend_expanded(bp, q_n, q_r, rows, pos, CFG)
    absorbed = mla.attend_absorbed(bp, q_n, q_r, rows, pos, CFG)
    assert expanded.shape == (2, length, M["num_attention_heads"] * M["v_head_dim"])
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    # one query at a time against the rows up to it, as a decode step sees them
    for t in (0, 4, 8):
        one = mla.attend_absorbed(bp, q_n[:, t: t + 1], q_r[:, t: t + 1], rows, pos[:, t: t + 1], CFG)
        np.testing.assert_allclose(one[:, 0], expanded[:, t], atol=1e-5)


def test_latent_attention_is_the_references(weights):
    """Projections, YaRN, the scale with its m^2 and the expanded form
    against the reference's attention sublayer."""
    bp = _layer(weights, 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 10, M["hidden_size"]))
    pos = jnp.arange(10)[None]
    q_n, q_r, row = mla.project(bp, x, pos, CFG)
    att = mla.attend_expanded(bp, q_n, q_r, row[:, :, 0], pos, CFG)
    got = x + att @ bp["wo"]
    want = ref._fns_of(M, "float32")[1][3](x[0], bp)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    assert abs(mla.softmax_scale(CFG) - ref.softmax_scale(M)) < 1e-9
    assert abs(ref.softmax_scale(M) - 16 ** -0.5 * (0.1 * np.log(4) + 1) ** 2) < 1e-9


def test_router_weights_sum_to_the_scaling_factor(weights):
    bp = _layer(weights, 0)
    y = jax.random.normal(jax.random.PRNGKey(4), (12, M["hidden_size"]))
    live = np.ones((12,), bool)
    live[[3, 7]] = False
    picks, w = moe.router_sigmoid(bp, y, jnp.asarray(live), K, 2.5)
    assert picks.shape == w.shape == (12, K)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    assert np.all(np.asarray(picks)[~live] == ALL) and np.all(np.asarray(picks)[live] < ALL)
    s = np.asarray(jax.nn.sigmoid(y @ bp["router"]))
    for t in np.flatnonzero(live):  # the k best scores, best first
        np.testing.assert_array_equal(np.asarray(picks)[t], np.argsort(-s[t], kind="stable")[:K])


@pytest.mark.parametrize("split", ["all_held", "some_absent", "some_dead", "one_expert"])
def test_grouped_experts_equal_masked_dense(weights, split):
    """k rows a token, sorted by expert, three grouped products, a token's
    rows added up: against every held expert applied under a mask.  Pairs on
    absent experts and tokens that are not live go to no group."""
    bp = _layer(weights, 1)
    T, D = 14, M["hidden_size"]
    rng = np.random.default_rng(5)
    yt = rng.standard_normal((T, D)).astype(np.float32)
    picks = np.stack([rng.permutation(HELD + 3)[:3] for _ in range(T)]).astype(np.int32)
    if split == "all_held":
        picks %= HELD
    elif split == "one_expert":
        picks[:] = 2
    picks = np.minimum(picks, HELD)  # HELD: held elsewhere, no group here
    if split == "some_dead":
        picks[[0, 5, 13]] = HELD
    gates = rng.uniform(0.1, 0.9, size=picks.shape).astype(np.float32)
    out, sizes = moe._grouped_experts(
        jnp.asarray(yt), jnp.asarray(picks), jnp.asarray(gates), bp, 0, HELD, jnp.float32)
    want = np.zeros((T, D), np.float32)
    for e in range(HELD):
        h = np.asarray(jax.nn.silu(yt @ bp["we_gate"][e])) * (yt @ np.asarray(bp["we_up"][e]))
        want += np.where(picks == e, gates, 0).sum(-1, keepdims=True) * (h @ np.asarray(bp["we_down"][e]))
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_array_equal(sizes, np.bincount(picks.ravel(), minlength=HELD + 1)[:HELD])
    assert np.all(np.asarray(out)[(picks == HELD).all(-1)] == 0)


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts that all shares of a layer give (4
    shares of 4 experts), with the shared expert counted once, equal what the
    uncut reference gives for the whole layer of 16 experts.  Every share
    routes over all 16 and normalises over all its picks; a pick is computed
    by exactly one share."""
    of = M["expert_share"]["of"]
    uncut = {**M, "n_routed_experts": ALL, "expert_share": {"index": 0, "of": 1}}
    w = ref.make_weights(3, uncut, jnp.float32)
    bp = jax.tree_util.tree_map(lambda a: a[1], w["blocks"])
    assert bp["we_gate"].shape[0] == ALL and bp["router"].shape[-1] == ALL
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
    want, picks, _ = ref._fns_of(uncut, "float32")[1][4](
        x.reshape(22, -1), bp, -jnp.ones((22, K), jnp.int32))
    np.testing.assert_array_equal(np.sort(chosen.reshape(22, K), -1), np.sort(picks, -1))
    np.testing.assert_allclose((x + total).reshape(22, -1), want, atol=1e-5)
    # and one share alone is not the layer
    assert float(jnp.abs(x + out - want.reshape(x.shape)).max()) > 1e-2


def test_paged_kernel_refuses_the_latent_block():
    """The Pallas kernel's GQA form reads a K and a V pool by head; the
    latent block's one pool (keys 576 wide, values the keys' first 512) is
    its latent form's (``latent_kernel_fits``), at the tiny sizes and at
    the published ones alike."""
    assert not kv_pager.paged_kernel_fits(CFG, 16, 4, 1, jnp.float32)
    published = transformer_config(json.load(open(
        os.path.join(ROOT, "perfbench", "configs", "axk1_l7_ep16.json"))), 3072, jnp.bfloat16)
    assert not kv_pager.paged_kernel_fits(published, 16, 64, 1, jnp.bfloat16)
    sched = DecodeScheduler(ref.make_weights(1, M, jnp.float32), CFG, max_slots=2,
                            tokens_per_page=PAGE, max_seq=CAP)
    try:
        assert sched._step_counts["decode_kernel_steps"] == 0
    finally:
        sched.close()


def test_page_pool_accounts_for_the_one_pool():
    """``page_bytes`` and ``stats()`` come from the pool's own shapes: one
    pool of rows, not two of ``kvh x head_dim``."""
    pool = kv_pager.PagePool(CFG, 9, tokens_per_page=PAGE)
    assert pool.v_pages is None and pool.state is None
    assert pool.k_pages.shape == (LAYERS, 1, 9, PAGE, mla.row_width(CFG))
    assert pool.page_bytes == pool.stats()["page_bytes"] == LAYERS * PAGE * mla.row_width(CFG) * 4
    pages, none, state = pool.take()
    assert pages.shape[-1] == 128 and none is None and state is None and pool.k_pages is None
    # the pair, as before: K and V together
    dense = tfm.TransformerConfig(vocab_size=61, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                                  d_ff=64, max_seq=CAP, dtype=jnp.float32)
    pair = kv_pager.PagePool(dense, 9, tokens_per_page=PAGE)
    assert pair.page_bytes == 2 * 2 * PAGE * 2 * 8 * 4


# ---------------------------------------------------------------------------
# prefill, then decode, through the latent pages, against the reference's
# full (expanded) forward: logits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [(5, 8), (8, 8), (11, 16), (3, 16)])
def test_prefill_logits_come_from_the_last_real_position(weights, prompt_len, bucket):
    prompt = _tokens(prompt_len, 11)
    logits, _, (stats, chosen) = _prefill(weights, _pool().k_pages, prompt, bucket, _table(1, prompt_len + 4))
    want, picks, _ = ref.logits(weights, M, prompt, with_routing=True)
    np.testing.assert_allclose(logits, np.asarray(want)[-1], atol=ATOL)
    # the picks are the reference's; pads are routed nowhere and counted nowhere
    assert chosen.shape == (LAYERS - DENSE, bucket, K)
    np.testing.assert_array_equal(np.sort(chosen[:, :prompt_len], -1), np.sort(picks, -1))
    assert np.all(np.asarray(chosen)[:, prompt_len:] == ALL)
    stats = np.asarray(stats)
    local = int((np.asarray(picks) < HELD).sum())
    assert stats[0] == LAYERS - DENSE and stats[1] == local and stats[4] == (LAYERS - DENSE) * prompt_len * K
    assert stats[2] <= stats[1] and stats[3] <= (LAYERS - DENSE) * HELD


def test_decode_steps_match_reference_with_two_slots_at_different_positions(weights):
    """Two sequences prefilled at their own buckets (expanded attention over
    their own rows), then stepped together in one [slots] batch at different
    positions with an idle slot between them (absorbed attention over the
    gathered pages), teacher-forced: every step's logits are the reference's
    full forward's."""
    seqs = {0: _tokens(5 + 6, 21), 2: _tokens(11 + 6, 22)}
    lens = {0: 5, 2: 11}
    pages = _pool().k_pages
    tables = np.zeros((SLOTS, MAX_PAGES), np.int32)
    for slot, first in ((0, 1), (2, 9)):
        tables[slot] = _table(first, len(seqs[slot]))
        _, pages, _ = _prefill(weights, pages, seqs[slot][: lens[slot]], 16 if slot else 8, tables[slot])
    want = {s: ref.logits(weights, M, seqs[s], with_routing=True) for s in seqs}
    for step in range(6):
        toks = np.zeros((SLOTS,), np.int32)
        idx = np.zeros((SLOTS,), np.int32)
        for s in seqs:
            idx[s] = lens[s] + step
            toks[s] = seqs[s][idx[s]]
        logits, pages, _, _, (stats, chosen) = STEP(
            weights, jnp.asarray(toks)[:, None], jnp.asarray(tables), jnp.asarray(idx), pages, None, CFG)
        for s in seqs:
            np.testing.assert_allclose(logits[s, 0], np.asarray(want[s][0])[idx[s]], atol=ATOL)
            np.testing.assert_array_equal(np.sort(chosen[:, s], -1), np.sort(want[s][1][:, idx[s]], -1))
        assert np.asarray(stats)[4] == 2 * (LAYERS - DENSE) * K  # the idle slot picks nothing
        assert np.all(np.asarray(chosen)[:, 1] == ALL)


def test_every_layer_writes_and_reads_its_own_rows_of_the_one_pool(weights):
    """The leading dense layer (the scan's first run) and the expert layers
    (its second) index ONE stacked pool by the layer's absolute number: layer
    0's rows are the projection of the embeddings, every layer's rows lie in
    the sequence's pages and nowhere else, and a step reads each layer's rows
    from that layer's pages."""
    prompt = _tokens(9, 31)
    table = _table(3, 12)
    _, pages, _ = _prefill(weights, _pool().k_pages, prompt, 16, table)
    got = np.asarray(pages)
    assert got.shape == (LAYERS, 1, SLOTS * MAX_PAGES + 1, PAGE, mla.row_width(CFG))
    width = mla.page_width(CFG)
    mine = got[:, 0, 3:6].reshape(LAYERS, 3 * PAGE, -1)
    bp0 = jax.tree_util.tree_map(lambda a: a[0], weights["dense_blocks"])
    x = weights["embed"][prompt][None]
    row0 = mla.project(bp0, x, jnp.arange(9)[None], CFG)[2]
    np.testing.assert_allclose(mine[0, :9], row0[0, :, 0], atol=1e-6)
    for layer in range(LAYERS):
        assert np.all(np.abs(mine[layer, :9, :width]).max(-1) > 1e-3)  # written
        assert np.all(mine[layer, :, width:] == 0)                     # the tile's tail
        other = np.delete(got[layer, 0], [0, 3, 4, 5], axis=0)
        assert np.all(other == 0)                                      # and nowhere else
        assert not np.allclose(mine[layer, :9], mine[(layer + 1) % LAYERS, :9])

    def step(pool):
        tables = np.zeros((SLOTS, MAX_PAGES), np.int32)
        tables[1] = table
        idx, toks = np.array([0, 9, 0], np.int32), np.array([0, 17, 0], np.int32)
        return np.asarray(STEP(weights, jnp.asarray(toks)[:, None], jnp.asarray(tables),
                               jnp.asarray(idx), jnp.asarray(pool), None, CFG)[0][1, 0])

    sound = step(got)
    stale = got.copy()
    stale[:, 0, 7:] += 5.0  # pages no live table names: never read
    np.testing.assert_array_equal(step(stale), sound)
    for layer in range(LAYERS):
        moved = got.copy()
        moved[layer, 0, 3:5] = got[(layer + 1) % LAYERS, 0, 3:5]  # another layer's rows in this one's place
        assert np.abs(step(moved) - sound).max() > 1e-3, layer


def _gaps(weights, prompt, served, routing):
    seq = np.concatenate([prompt, served]).astype(np.int32)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    forced = -np.ones((LAYERS - DENSE, len(seq), K), np.int32)
    forced[:, : len(seq) - 1] = routing
    lg, _, rgap = ref.logits(weights, M, seq, routing=forced, with_routing=True)
    lg = np.asarray(lg, np.float64)[at]
    return lg.max(axis=-1) - lg[np.arange(len(at)), np.asarray(served)], np.asarray(rgap)


@pytest.mark.parametrize("slots", [1, 2, 3])
def test_serving_matches_reference(weights, slots):
    """Through ``DecodeScheduler``: prompts whose lengths are not their
    buckets, more requests than slots (a slot's pages are reused by a later
    request), streams at different positions in one step.  Every served token
    is the reference's best under teacher forcing, and the picks the served
    path kept are the reference's own, position for position."""
    sched = DecodeScheduler(weights, CFG, max_slots=slots, tokens_per_page=PAGE, max_seq=CAP,
                            routing_trace=8)
    spec = [(5, 7), (11, 4), (3, 9), (9, 6), (6, 5)]
    prompts = [_tokens(n, 30 + i) for i, (n, _) in enumerate(spec)]
    out = [None] * len(spec)

    def run(i):
        while out[i] is None:
            try:
                out[i] = sched.submit(prompts[i], spec[i][1], timeout_s=120)
            except DecodeRefused:  # the backlog holds two requests a slot
                time.sleep(0.05)

    try:
        assert sched._vp is None and sched._state is None and sched.pool.k_pages is None
        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(spec))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sched.snapshot()["pages_used"] == 0
    finally:
        sched.close()
    for i, (n, max_new) in enumerate(spec):
        assert len(out[i]) == max_new
        kept = sched.routing_of(prompts[i])
        assert kept.shape == (LAYERS - DENSE, n + max_new - 1, K)
        token_gaps, router_gaps = _gaps(weights, prompts[i], out[i], kept)
        assert token_gaps.max() <= ATOL, (i, out[i])
        assert router_gaps.max() == 0.0  # the served picks are the reference router's own
    assert sched.routing_of(_tokens(4, 99)) is None


def test_reference_follows_forced_picks_and_prices_them(weights):
    """`routing` replaces the reference router's picks where its first entry
    is >= 0; the router gap is 0 where the forced set is the router's own
    (in any order) and positive where it is not, and the logits follow."""
    tokens = _tokens(10, 50)
    base, picks, gaps = ref.logits(weights, M, tokens, with_routing=True)
    assert float(jnp.abs(gaps).max()) == 0.0
    same, _, gaps = ref.logits(weights, M, tokens, routing=np.asarray(picks)[..., ::-1], with_routing=True)
    np.testing.assert_allclose(same, base, atol=1e-5)
    assert float(jnp.abs(gaps).max()) == 0.0
    forced = -np.ones_like(picks)
    absent = next(e for e in range(ALL) if e not in set(np.asarray(picks[1, 4]).tolist()))
    forced[1, 4] = np.asarray(picks[1, 4])
    forced[1, 4, -1] = absent  # the router's last pick gives way to an expert it ranked lower
    moved, kept, gaps = ref.logits(weights, M, tokens, routing=forced, with_routing=True)
    assert int(kept[1, 4, -1]) == absent and float(gaps[1, 4]) > 0
    assert np.count_nonzero(np.asarray(gaps)) == 1  # every other token's picks are the router's own
    np.testing.assert_array_equal(moved[:4], base[:4])  # causal: earlier positions are untouched
    assert float(jnp.abs(moved[4] - base[4]).max()) > 1e-4


def test_counters_ride_the_dispatch(weights):
    """The four `moe_*` over the held experts, the pairs picked for live
    tokens and the tokens the steps held."""
    before = obs.counters()
    sched = DecodeScheduler(weights, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP)
    try:
        sched.submit(_tokens(6, 40), 5, timeout_s=120)
    finally:
        sched.close()
    d = obs.counters_delta(before)
    n1 = LAYERS - DENSE
    # one prefill of 6 tokens and 4 steps of one live slot (the idle slot counts nowhere)
    assert d["decode_prefill_batches"] == 1 and d["decode_steps"] == 4
    assert d["moe_route_calls"] == n1 * 5
    assert d["moe_picked_pairs"] == n1 * K * (6 + 4)
    assert 0 < d["moe_routed_tokens"] < d["moe_picked_pairs"]  # this share's part of the pairs
    assert d["moe_experts_touched"] <= min(d["moe_routed_tokens"], n1 * 5 * HELD)
    assert d["moe_busiest_expert_tokens"] <= d["moe_routed_tokens"]
    assert d["decode_tokens_held"] == 7 + 8 + 9 + 10  # the prompt's 6 and the tokens fed since
    assert d["decode_kernel_steps"] == 0
    assert d["decode_latent_kernel_steps"] == 0  # a latent of 16 takes the gather path


# ---------------------------------------------------------------------------
# the kernel's latent form on the served path
# ---------------------------------------------------------------------------

# the tiny file with a latent of one lane tile, which the kernel takes (pages
# of 8 in float32, rows of 256)
KM = bench_run.overlay(M, {"kv_lora_rank": 128})
KCFG = transformer_config(KM, CAP, jnp.float32)
KPAGE = 8


@pytest.fixture()
def fresh_traces():
    """The step traced anew before and after: the gate is asked when the
    step traces, and a jit of a module's function keeps its trace."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_latent_kernel_serves_the_gather_paths_tokens(fresh_traces, monkeypatch):
    """Through ``DecodeScheduler`` with two slots and four requests, so that
    retirements and admissions fall between steps: the tokens served through
    the kernel are the gather path's (the test steers; the program has no
    option), and ``decode_latent_kernel_steps`` counts every step."""
    weights = ref.make_weights(9, KM, jnp.float32)
    assert kv_pager.latent_kernel_fits(KCFG, KPAGE, 2, 1, jnp.float32)
    spec = [(5, 7), (11, 4), (3, 9), (9, 6)]
    prompts = [_tokens(n, 60 + i) for i, (n, _) in enumerate(spec)]

    def serve():
        out = [None] * len(spec)
        before = obs.counters()
        sched = DecodeScheduler(weights, KCFG, max_slots=2, tokens_per_page=KPAGE, max_seq=CAP)

        def run(i):
            while out[i] is None:
                try:
                    out[i] = sched.submit(prompts[i], spec[i][1], timeout_s=120)
                except DecodeRefused:
                    time.sleep(0.05)

        try:
            threads = [threading.Thread(target=run, args=(i,)) for i in range(len(spec))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sched.close()
        return out, obs.counters_delta(before), sched._step_counts["decode_latent_kernel_steps"]

    kernel, counted, flag = serve()
    assert flag == 1 and counted["decode_steps"] > 0
    assert counted["decode_latent_kernel_steps"] == counted["decode_steps"]
    monkeypatch.setattr(kv_pager, "latent_kernel_fits", lambda *a: False)
    jax.clear_caches()
    gather, counted, flag = serve()
    assert flag == 0 and counted["decode_latent_kernel_steps"] == 0
    assert [len(o) for o in kernel] == [n for _, n in spec]
    assert kernel == gather
    assert len({tuple(o[:4]) for o in kernel}) > 1  # not one stream four times
