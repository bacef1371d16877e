"""ZAYA1's block on the paged serving path, against its plain reference.

The block (``transformer.BlockSpec(attention="cca", ffn="experts_top1")``)
has two things the dense block has not: a fixed-size convolution state per
decode slot beside the K/V pages, and a dropless top-1 expert layer whose
router carries ``r`` from layer to layer.  Everything here runs at tiny
widths in float32 on the CPU, with seeded weights in the layout the program
consumes, made by the reference (``perfbench/refs/zaya_decoder.py``, which
imports nothing of the program) and handed to both sides.

Tolerances.  Program and reference compute the same float32 arithmetic in
another order (cached K/V against a whole-sequence softmax, sorted grouped
products against masked dense ones), so logits of unit scale agree to a few
1e-6; ``ATOL`` 2e-4 leaves room for XLA:CPU's reassociation and would still
catch a missing bias, a wrong shift or a stale state, each of which moves
logits by 1e-2 or more.  Routing is discontinuous, so a comparison is only
meaningful if both sides chose the same experts: the seeds below give
top-two score margins far above float32 rounding, and ``test_serving_
matches_reference`` would fail loudly, not subtly, if one flipped.
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

from perfbench.drivers.bridge_decode_zaya import transformer_config  # noqa: E402
from perfbench.refs import zaya_decoder as ref  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeRefused, DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import cca, kv_pager, moe  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402

ATOL = 2e-4
PREFILL = jax.jit(kv_pager._prefill_forward, static_argnums=6)
STEP = jax.jit(kv_pager._step_forward, static_argnums=6)
QKV_STEP = jax.jit(cca.qkv_step, static_argnums=4)
PAGE, CAP, SLOTS = 4, 32, 3
MAX_PAGES = CAP // PAGE


def _tiny():
    with open(os.path.join(ROOT, "perfbench", "configs", "zaya1_8b_l20.json")) as f:
        m = json.load(f)
    return {**m, **m["tiny"]}


M = _tiny()
CFG = transformer_config(M, CAP, jnp.float32)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, M, jnp.float32)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, M["vocab_size"], size=n).astype(np.int32)


def _pool():
    return kv_pager.PagePool(CFG, SLOTS * MAX_PAGES + 1, tokens_per_page=PAGE, slots=SLOTS)


def _table(first_page, n_tokens):
    row = np.zeros((MAX_PAGES,), np.int32)
    n = kv_pager.pages_for(n_tokens, PAGE)
    row[:n] = np.arange(first_page, first_page + n)
    return row


def _prefill(weights, pool_state, prompt, bucket, table, slot):
    kp, vp, st = pool_state
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, kp, vp, st, stats = PREFILL(
        weights, jnp.asarray(toks), jnp.asarray(table[None]),
        jnp.asarray([len(prompt) - 1], jnp.int32), kp, vp, CFG, st,
        jnp.asarray([slot], jnp.int32))
    return np.asarray(logits[0]), (kp, vp, st), np.asarray(stats[0])


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_block_spec_is_validated_and_defaults_to_the_dense_block():
    assert tfm.TransformerConfig().block == tfm.BlockSpec()
    assert tfm.BlockSpec().stateless and not CFG.block.stateless
    assert (CFG.head_dim, CFG.block.norm_eps, CFG.block.rotary_share) == (16, 1e-5, 0.5)
    with pytest.raises(ValueError):
        tfm.BlockSpec(attention="latent")
    with pytest.raises(ValueError):
        tfm.BlockSpec(ffn="dense")
    with pytest.raises(ValueError):  # experts without a router width
        tfm.TransformerConfig(moe_experts=4, block=tfm.BlockSpec(ffn="experts_top1"))
    with pytest.raises(NotImplementedError):
        tfm.apply({}, jnp.zeros((1, 4), jnp.int32), CFG)


def test_partial_rope_turns_its_share_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    half = tfm._rope(x, pos, 1e4, 0.5)
    np.testing.assert_array_equal(half[..., 8:], x[..., 8:])
    np.testing.assert_allclose(half[..., :8], tfm._rope(x[..., :8], pos, 1e4), rtol=0, atol=0)
    np.testing.assert_array_equal(tfm._rope(x, pos, 1e4, 1.0), tfm._rope(x, pos, 1e4))


def test_norm_epsilon_is_the_specs():
    x = jnp.full((1, 8), 1e-3)
    w = jnp.ones((8,))
    a, b = tfm._rms_norm(x, w, 1e-6), tfm._rms_norm(x, w, 1e-5)
    assert float(jnp.abs(a - b).max()) > 1e-2  # the epsilon matters at this scale
    np.testing.assert_array_equal(a, tfm._rms_norm(x, w))  # the dense block keeps 1e-6


def test_tied_head_reads_the_embedding(weights):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, M["hidden_size"]))
    tied = tfm.head(weights, x, CFG)
    np.testing.assert_allclose(tied, x @ weights["embed"].T, atol=1e-5)
    untied = tfm.head({**weights, "lm_head": weights["embed"].T}, x, CFG)
    np.testing.assert_allclose(tied, untied, atol=1e-5)
    assert "lm_head" not in weights


def test_cca_step_form_equals_sequence_form(weights):
    """Feeding positions one at a time through the convolution state gives
    the whole-sequence form's q, k, v, and leaves the state that form's
    `tail` holds at the same position."""
    bp = jax.tree_util.tree_map(lambda a: a[1], weights["blocks"])
    length = 9
    x = jax.random.normal(jax.random.PRNGKey(2), (2, length, M["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(length), (2, length))
    q, k, v, tail = cca.qkv_sequence(bp, x, pos, CFG)
    assert tail.shape == (2, length, cca.state_width(CFG))
    state = cca.init_state(CFG, 2)[1]
    for t in range(length):
        qt, kt, vt, state = QKV_STEP(bp, x[:, t: t + 1], pos[:, t: t + 1], state, CFG)
        for whole, one in ((q, qt), (k, kt), (v, vt)):
            np.testing.assert_allclose(one[:, 0], whole[:, t], atol=1e-5)
        np.testing.assert_allclose(state, tail[:, t], atol=1e-5)


@pytest.mark.parametrize("split", ["one_expert", "even", "one_expert_some_dead", "even_some_dead"])
def test_expert_layer_equals_masked_dense(weights, monkeypatch, split):
    """Sorted grouped products against every expert applied under a mask,
    with the router forced: every token to one expert, or tokens dealt
    round the experts; tokens that are not live come out exactly zero and
    are counted nowhere."""
    bp = jax.tree_util.tree_map(lambda a: a[0], weights["blocks"])
    E, T = M["num_experts"], 22
    y = jax.random.normal(jax.random.PRNGKey(3), (2, T // 2, M["hidden_size"]))
    live = np.ones((T,), bool)
    if split.endswith("dead"):
        live[[0, 7, 8, 21]] = False
    chosen = np.full((T,), 2) if split.startswith("one") else np.arange(T) % E
    gate = np.linspace(0.2, 0.9, T).astype(np.float32)

    def forced(bp_, yt, r_prev, live_, eps):
        return jnp.where(live_, jnp.asarray(chosen, jnp.int32), E), jnp.asarray(gate), r_prev

    monkeypatch.setattr(moe, "router_top1", forced)
    r0 = jnp.zeros((2, T // 2, M["router_hidden_size"]))
    out, _, counts, kept = moe.experts_top1(bp, y, r0, jnp.asarray(live).reshape(2, -1), CFG, bp, 0)
    np.testing.assert_array_equal(np.asarray(kept).ravel(), np.where(live, chosen, E))
    yt = np.asarray(y).reshape(T, -1)
    want = np.zeros_like(yt)
    for e in range(E):
        h = np.asarray(jax.nn.silu(yt @ bp["we_gate"][e])) * (yt @ np.asarray(bp["we_up"][e]))
        want += np.where(((chosen == e) & live)[:, None], gate[:, None] * (h @ np.asarray(bp["we_down"][e])), 0)
    np.testing.assert_allclose(np.asarray(out).reshape(T, -1), want, atol=1e-5)
    assert np.all(np.asarray(out).reshape(T, -1)[~live] == 0)
    np.testing.assert_array_equal(counts, np.bincount(chosen[live], minlength=E))


def test_router_and_experts_are_the_references(weights):
    """The program's router and expert layer on the reference's attention
    output, layer over layer with the carry: the same experts chosen, the
    same residual at the end."""
    tokens = _tokens(12, 5)
    want, chosen, _ = ref.forward(weights, M, tokens)
    _, (_, _, attention) = ref._fns_of(M, "float32")
    x = weights["embed"][tokens].astype(jnp.float32)
    r, live = jnp.zeros((12, M["router_hidden_size"])), jnp.ones((12,), bool)
    for i in range(M["num_hidden_layers"]):
        bp = jax.tree_util.tree_map(lambda a: a[i], weights["blocks"])
        x = attention(x, bp)
        y = tfm._rms_norm(x, bp["ln2"], CFG.block.norm_eps)
        e, _, _ = moe.router_top1(bp, y, r, live, CFG.block.norm_eps)
        np.testing.assert_array_equal(e, chosen[i])
        out, r, counts, kept = moe.experts_top1(bp, y[None], r[None], live[None], CFG, bp, 0)
        np.testing.assert_array_equal(kept[0], chosen[i])
        np.testing.assert_array_equal(counts, np.bincount(chosen[i], minlength=M["num_experts"]))
        x, r = x + out[0], r[0]
    np.testing.assert_allclose(x, want, atol=ATOL)


# ---------------------------------------------------------------------------
# prefill, then decode, against the reference's full forward: logits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len,bucket", [(5, 8), (8, 8), (11, 16), (3, 16)])
def test_prefill_logits_and_state_come_from_the_last_real_position(weights, prompt_len, bucket):
    prompt = _tokens(prompt_len, 11)
    pool = _pool()
    poisoned = pool.state + 37.0  # a previous tenant's leftovers
    logits, (kp, vp, st), stats = _prefill(
        weights, (pool.k_pages, pool.v_pages, poisoned), prompt, bucket, _table(1, prompt_len + 4), 1)
    want = np.asarray(ref.logits(weights, M, prompt))[-1]
    np.testing.assert_allclose(logits, want, atol=ATOL)
    # the slot's state row is what the whole-sequence form leaves at last_pos
    x = weights["embed"][prompt].astype(jnp.float32)[None]
    bp0 = jax.tree_util.tree_map(lambda a: a[0], weights["blocks"])
    tail = cca.qkv_sequence(bp0, x, jnp.arange(prompt_len)[None], CFG)[3]
    np.testing.assert_allclose(st[0, 1], tail[0, -1], atol=1e-5)
    # the other slots' rows are untouched, the admitted slot's fully overwritten
    np.testing.assert_array_equal(st[:, 0], poisoned[:, 0])
    np.testing.assert_array_equal(st[:, 2], poisoned[:, 2])
    assert float(jnp.abs(st[:, 1]).max()) < 30.0
    # pads are routed nowhere: every layer routed the prompt's tokens and no more
    layers = M["num_hidden_layers"]
    assert stats[0] == layers and stats[1] == layers * prompt_len
    assert stats[2] <= stats[1] and layers <= stats[3] <= layers * min(prompt_len, M["num_experts"])


def test_decode_steps_match_reference_with_two_slots_at_different_positions(weights):
    """Two sequences prefilled at their own buckets, then stepped together
    in one [slots] batch at different positions (an idle slot between
    them), teacher-forced: every step's logits are the reference's."""
    seqs = {0: _tokens(5 + 6, 21), 2: _tokens(11 + 6, 22)}
    lens = {0: 5, 2: 11}
    pool = _pool()
    state = (pool.k_pages, pool.v_pages, pool.state)
    tables = np.zeros((SLOTS, MAX_PAGES), np.int32)
    for slot, first in ((0, 1), (2, 9)):
        tables[slot] = _table(first, len(seqs[slot]))
        _, state, _ = _prefill(weights, state, seqs[slot][: lens[slot]], 16 if slot else 8, tables[slot], slot)
    want = {s: np.asarray(ref.logits(weights, M, seqs[s])) for s in seqs}
    kp, vp, st = state
    for step in range(6):
        toks = np.zeros((SLOTS,), np.int32)
        idx = np.zeros((SLOTS,), np.int32)
        for s in seqs:
            idx[s] = lens[s] + step
            toks[s] = seqs[s][idx[s]]
        logits, kp, vp, st, stats = STEP(
            weights, jnp.asarray(toks)[:, None], jnp.asarray(tables), jnp.asarray(idx), kp, vp, CFG, st)
        for s in seqs:
            np.testing.assert_allclose(logits[s, 0], want[s][idx[s]], atol=ATOL)
        stats, chosen = np.asarray(stats[0]), np.asarray(stats[1])
        assert stats[1] == 2 * M["num_hidden_layers"]  # the idle slot is routed nowhere
        assert np.all(chosen[:, 1] == M["num_experts"]) and np.all(chosen[:, [0, 2]] < M["num_experts"])


def _gaps(weights, prompt, served):
    seq = np.concatenate([prompt, served]).astype(np.int32)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    lg = np.asarray(ref.logits(weights, M, seq), np.float64)[at]
    return lg.max(axis=-1) - lg[np.arange(len(at)), np.asarray(served)]


@pytest.mark.parametrize("slots", [1, 2, 3])
def test_serving_matches_reference(weights, slots):
    """Through ``DecodeScheduler``: prompts whose lengths are not their
    buckets, more requests than slots (so a slot is reused by a later
    request after its first tenant retires, and with one slot every request
    follows another in the same row of the state), streams at different
    positions in one step.  Every served token is the reference's best
    under teacher forcing: the gap of its logit below the reference's
    maximum is 0 up to rounding."""
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
        assert _gaps(weights, prompts[i], out[i]).max() <= ATOL, (i, out[i])
        # the routing the served path kept is the reference's own, position for position
        seq = np.concatenate([prompts[i], out[i]]).astype(np.int32)
        _, chosen, _ = ref.forward(weights, M, seq)
        kept = sched.routing_of(prompts[i])
        assert kept.shape == (M["num_hidden_layers"], n + max_new - 1)
        np.testing.assert_array_equal(kept, np.asarray(chosen)[:, : n + max_new - 1])
    assert sched.routing_of(_tokens(4, 99)) is None


def test_reference_follows_a_forced_routing_and_prices_it(weights):
    """`routing` replaces the reference router's choices where it is >= 0;
    the router gap is 0 where the forced expert is the router's own and
    positive where it is not, and the logits follow the forced expert."""
    tokens = _tokens(10, 50)
    base, chosen, gaps = ref.logits(weights, M, tokens, with_routing=True)
    assert float(jnp.abs(gaps).max()) == 0.0
    same, _, gaps = ref.logits(weights, M, tokens, routing=chosen, with_routing=True)
    np.testing.assert_array_equal(same, base)
    assert float(jnp.abs(gaps).max()) == 0.0
    forced = -np.ones_like(chosen)
    forced[1, 4] = (int(chosen[1, 4]) + 1) % M["num_experts"]
    moved, kept, gaps = ref.logits(weights, M, tokens, routing=forced, with_routing=True)
    assert int(kept[1, 4]) == forced[1, 4] and float(gaps[1, 4]) > 0
    assert np.count_nonzero(np.asarray(gaps)) == 1  # every other choice is the router's own
    np.testing.assert_array_equal(moved[:4], base[:4])  # causal: earlier positions are untouched
    assert float(jnp.abs(moved[4] - base[4]).max()) > 1e-3


def test_moe_counters_ride_the_dispatch_and_a_dense_model_bumps_none(weights):
    keys = ("moe_route_calls", "moe_routed_tokens", "moe_busiest_expert_tokens", "moe_experts_touched")
    layers, E = M["num_hidden_layers"], M["num_experts"]
    before = obs.counters()
    sched = DecodeScheduler(weights, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP)
    try:
        sched.submit(_tokens(6, 40), 5, timeout_s=120)
    finally:
        sched.close()
    d = obs.counters_delta(before)
    assert all(k in d for k in keys)
    # one prefill of 6 tokens and 4 steps of one live slot (the other idle slot is not counted)
    assert d["decode_prefill_batches"] == 1 and d["decode_steps"] == 4
    assert d["moe_route_calls"] == layers * 5
    assert d["moe_routed_tokens"] == layers * (6 + 4)
    assert layers * 5 <= d["moe_experts_touched"] <= layers * (min(6, E) + 4)
    assert d["moe_experts_touched"] <= d["moe_routed_tokens"]
    assert d["moe_busiest_expert_tokens"] >= layers * 4 + layers * 2  # a step's one token; ceil(6 / E) of a prefill

    dense = tfm.TransformerConfig(vocab_size=61, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                                  d_ff=64, max_seq=CAP, dtype=jnp.float32)
    before = obs.counters()
    sched = DecodeScheduler(tfm.init(jax.random.PRNGKey(0), dense), dense, max_slots=2,
                            tokens_per_page=PAGE, max_seq=CAP)
    try:
        assert sched._state is None and sched.pool.state is None
        sched.submit(_tokens(6, 41) % 61, 5, timeout_s=120)
    finally:
        sched.close()
    d = obs.counters_delta(before)
    assert d["decode_steps"] == 4 and all(d[k] == 0 for k in keys)


def test_scheduler_takes_the_convolution_state_with_the_pools(weights):
    """Ownership (PR 33): the scheduler holds the pools AND the convolution state, the pool none of
    them; both executables donate the pools and hand the state on."""
    sched = DecodeScheduler(weights, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP)
    try:
        pool = sched.pool
        assert pool.k_pages is None and pool.v_pages is None and pool.state is None
        first = (sched._kp, sched._vp, sched._state)
        assert first[2].shape == (M["num_hidden_layers"], 2, cca.state_width(CFG))
        assert float(jnp.abs(first[2]).max()) == 0.0
        assert len(sched.submit(_tokens(6, 42), 3, timeout_s=120)) == 3
        assert first[0].is_deleted() and first[1].is_deleted()
        held = (sched._kp, sched._vp, sched._state)
        assert [a.shape for a in held] == [a.shape for a in first]
        assert not any(a.is_deleted() for a in held)
        assert float(jnp.abs(held[2]).max()) > 0.0  # the admitted slot's rows were written
    finally:
        sched.close()


def test_cca_pool_needs_its_slot_count():
    with pytest.raises(ValueError):
        kv_pager.PagePool(CFG, 9, tokens_per_page=PAGE)
    pool = kv_pager.PagePool(CFG, 9, tokens_per_page=PAGE, slots=5)
    assert pool.state.shape == (M["num_hidden_layers"], 5, cca.state_width(CFG))
    assert pool.k_pages.shape == (
        M["num_hidden_layers"], M["num_key_value_heads"], 9, PAGE, M["head_dim"])
