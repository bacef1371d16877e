"""Falcon-H1's block on the serving path, against its plain reference.

The block (``transformer.BlockSpec(attention="gqa", mixer=SSMSpec(...))``)
runs grouped-query attention over pages and a Mamba-2 mixer side by side on
the same normed input; the pool holds a sequence's pages AND its mixer's
state (``S`` float32 and the convolution's tail), stepped in one pass
(``parallel/ssm.py``, or the ``jnp`` step where the kernel does not fit) and
prefilled by SSD's chunked form.  Everything here runs at tiny widths in
float32 on the CPU, with seeded weights in the layout the program consumes,
made by the reference (``perfbench/refs/falcon_h1_decoder.py``, which
imports nothing of the program and computes the mixer in SSD's quadratic
form: no state, no chunk, no convolution cache) and handed to both sides.

Two tiny configurations: the configuration file's ``tiny`` (heads of 8,
``d_state`` 16: the ``jnp`` step) and the same with ``d_state`` 128, whose
state the kernel takes (interpreted on the CPU).

Tolerances.  Program and reference compute the same float32 mathematics by
other algorithms, so logits (of scale ``lm_head_multiplier``, 0.0078)
agree to a few 1e-8; ``ATOL`` 1e-6 would still catch a missing decay, a
stale state, a pad token folded into the state or a multiplier applied
twice, each of which moves them by 1e-5 or more.
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

from perfbench.drivers.bridge_decode_falcon_h1 import transformer_config  # noqa: E402
from perfbench.refs import falcon_h1_decoder as ref  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import kv_pager, ssm  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402
from tensorframes_tpu.ops import frame_cache  # noqa: E402
from tensorframes_tpu.parallel import ssm as kernel  # noqa: E402

ATOL = 1e-6
PREFILL = jax.jit(kv_pager._prefill_forward, static_argnums=6)
STEP = jax.jit(kv_pager._step_forward, static_argnums=6)
CAP, SLOTS, P = 64, 3, 4


def _tiny(**over):
    with open(os.path.join(ROOT, "perfbench", "configs", "falcon_h1_34b_l4.json")) as f:
        m = json.load(f)
    return {**m, **m["tiny"], **over}


M = _tiny()
M_K = _tiny(mamba_d_state=128)  # a state whose shape the kernel takes
CONFIGS = {"step": M, "kernel": M_K}


def _cfg(m):
    return transformer_config(m, CAP, jnp.float32)


@pytest.fixture(scope="module")
def weights():
    return {k: ref.make_weights(7, m, jnp.float32) for k, m in CONFIGS.items()}


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, M["vocab_size"], size=n).astype(np.int32)


def _pool(cfg):
    """``(kp, vp, state, table of slot 1)``: every page of slot 1 reserved."""
    max_pages = kv_pager.pages_for(CAP, P)
    pool = kv_pager.PagePool(cfg, SLOTS * max_pages + 1, tokens_per_page=P, slots=SLOTS)
    kp, vp, state = pool.take()
    table = np.zeros((SLOTS, max_pages), np.int32)
    table[1] = np.arange(1, max_pages + 1)
    return kp, vp, state, table


def _prefill(weights, cfg, pools, prompt, bucket, slot=1):
    kp, vp, state, table = pools
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, kp, vp, state, _ = PREFILL(
        weights, jnp.asarray(toks), jnp.asarray(table[slot:slot + 1]),
        jnp.asarray([len(prompt) - 1], jnp.int32), kp, vp, cfg, state,
        jnp.asarray([slot], jnp.int32))
    return np.asarray(logits[0]), (kp, vp, state, table)


def _ssm_inputs(rng, L, H=4, G=2, Pd=8, N=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5), size=(L, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    return f(L, H, Pd), f(L, G, N), f(L, G, N), dt, A


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_block_spec_takes_a_mixer_beside_attention():
    cfg = _cfg(M)
    assert cfg.block.attention == "gqa" and cfg.block.mixer is not None
    assert not cfg.block.stateless and not cfg.block.routes
    assert cfg.block.mixer.d_ssm == M["mamba_d_ssm"]
    assert cfg.block.mixer.conv_dim == 32 + 2 * 2 * 16
    with pytest.raises(ValueError, match="mixer"):
        tfm.BlockSpec(attention="cca", mixer=cfg.block.mixer)
    with pytest.raises(NotImplementedError):
        tfm.apply({}, jnp.zeros((1, 4), jnp.int32), cfg)
    assert not kv_pager.ssm_kernel_fits(cfg) and kv_pager.ssm_kernel_fits(_cfg(M_K))


@pytest.mark.parametrize("L", [1, 5, 8, 13, 24])
def test_chunked_recurrence_and_quadratic_form_agree(L):
    """SSD's chunked form (chunks of 8, so a prompt of 13 is one whole chunk
    and part of one), the recurrent step token by token and the reference's
    quadratic form give the same outputs, and the same final state as the
    reference's closed form."""
    rng = np.random.default_rng(L)
    x, B, C, dt, A = _ssm_inputs(rng, L)
    D = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    y_c, S_c = ssm.chunked(x, B, C, dt, A, jnp.zeros((4, 8, 16)), chunk=8)
    want = np.asarray(ref.ssd_quadratic(x, B, C, dt, A))
    np.testing.assert_allclose(y_c, want, atol=1e-5, rtol=1e-5)
    S = jnp.zeros((1, 4, 8, 16))
    for t in range(L):
        y, S = ssm.step(x[t][None], B[t][None], C[t][None], dt[t][None], A, D, S,
                        jnp.array([True]))
        np.testing.assert_allclose(y[0], want[t] + D[:, None] * x[t], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0], S_c, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S_c, ref.ssd_state(x, B, dt, A, L - 1), atol=1e-5, rtol=1e-5)


def test_chunked_from_a_state_and_padding_enter_nothing():
    """A state carried in is decayed and read like the tokens before it, and
    padding with dt 0 neither decays the state nor enters it."""
    rng = np.random.default_rng(3)
    x, B, C, dt, A = _ssm_inputs(rng, 20)
    y, S = ssm.chunked(x, B, C, dt, A, jnp.zeros((4, 8, 16)), chunk=8)
    y1, S1 = ssm.chunked(x[:9], B[:9], C[:9], dt[:9], A, jnp.zeros((4, 8, 16)), chunk=8)
    y2, S2 = ssm.chunked(x[9:], B[9:], C[9:], dt[9:], A, S1, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S2, S, atol=1e-5, rtol=1e-5)
    padded = dt.at[13:].set(0.0)
    _, S_pad = ssm.chunked(x, B, C, padded, A, jnp.zeros((4, 8, 16)), chunk=8)
    _, S_13 = ssm.chunked(x[:13], B[:13], C[:13], dt[:13], A, jnp.zeros((4, 8, 16)), chunk=8)
    np.testing.assert_allclose(S_pad, S_13, atol=1e-6, rtol=1e-6)


KERNEL = jax.jit(kernel.ssm_step, static_argnames=("interpret",))


@pytest.mark.parametrize("live", [(True, False, True), (False, False, False), (False, True, False)])
def test_kernel_is_the_jnp_step_in_place_at_a_layer(live):
    """The kernel at a layer index of the stacked state: the live rows' state
    decayed, updated and read out as ``ssm.step`` does; every other row and
    every other layer left as it lay; a row that is not live reads zeros."""
    rng = np.random.default_rng(sum(live))
    R, H, G, Pd, N = 3, 4, 2, 8, 128
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(R, H, Pd), f(R, G, N), f(R, G, N)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(R, H)), jnp.float32)
    A, D = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32), f(H)
    S = f(2, R, H, Pd, N)
    live = jnp.asarray(live)
    y, S1 = KERNEL(x, B, C, dt, A, D, S, live, 1, interpret=True)
    want_y, want_S = ssm.step(x, B, C, dt, A, D, S[1], live)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S1[1], want_S, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(S1[0], S[0])


def test_kernel_is_named_and_aliased():
    R, H, G, Pd, N = 2, 4, 2, 8, 128
    z = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda S: kernel.ssm_step(z(R, H, Pd), z(R, G, N), z(R, G, N), z(R, H), z(H), z(H), S,
                                  jnp.array([True, True]), 0, interpret=True)
    )(z(1, R, H, Pd, N))
    call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    assert kernel.KERNEL_NAME == "tfs_ssm_step" in str(call.params)
    assert kernel.fits(16, 128, 256) and not kernel.fits(16, 128, 16)


def test_pool_holds_pages_and_a_state_and_charges_both():
    cfg = _cfg(M)
    pool = kv_pager.PagePool(cfg, 9, tokens_per_page=P, slots=SLOTS)
    S, tail = pool.state
    assert S.shape == (2, SLOTS, 4, 8, 16) and S.dtype == jnp.float32
    assert tail.shape == (2, SLOTS, 3, 32 + 2 * 2 * 16) and tail.dtype == jnp.float32
    assert pool.k_pages.shape == (2, 2, 9, P, 16) and pool.v_pages.shape == pool.k_pages.shape
    assert pool.page_bytes == 2 * 2 * 2 * P * 16 * 4  # K and V of every layer, a page
    assert pool.state_bytes == 4 * (2 * 4 * 8 * 16 + 2 * 3 * 96)  # S and the tail, a slot
    assert pool.stats()["state_bytes"] == pool.state_bytes
    before = frame_cache._budget.tenant_bytes.get("h1", 0)
    charge, pages = pool.allocate(3, tenant="h1")
    assert len(pages) == 3
    assert frame_cache._budget.tenant_bytes["h1"] - before == 3 * pool.page_bytes + pool.state_bytes
    pool.free(charge)
    assert frame_cache._budget.tenant_bytes.get("h1", 0) == before
    *pages, taken = pool.take()
    assert taken[0] is S and pool.state is None


def test_state_and_pages_are_donated_by_both_executables(weights):
    cfg = _cfg(M)
    kp, vp, state, table = _pool(cfg)
    toks = jnp.asarray(np.pad(_tokens(6, 0), (0, 2))[None])
    tok, kp2, vp2, new, stats = kv_pager.paged_prefill(
        weights["step"], toks, jnp.asarray(table[1:2]), jnp.array([5], jnp.int32), kp, vp, cfg,
        slot=jnp.array([1], jnp.int32), retention=state)
    assert all(a.is_deleted() for a in (kp, vp, *state)) and tok.shape == (1,) and stats is None
    nxt, kp3, vp3, newer, _ = kv_pager.paged_decode_step(
        weights["step"], jnp.array([0, int(tok[0]), 0], jnp.int32), jnp.asarray(table),
        jnp.array([0, 6, 0], jnp.int32), kp2, vp2, cfg, retention=new)
    assert all(a.is_deleted() for a in (kp2, vp2, *new)) and nxt.shape == (SLOTS,)
    assert newer[0].dtype == jnp.float32 and not newer[0].is_deleted()


# ---------------------------------------------------------------------------
# the serving path against the reference's full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(CONFIGS))
@pytest.mark.parametrize("n_prompt,bucket", [(1, 8), (11, 16), (13, 32)])
def test_prefill_then_decode_matches_reference_logits(weights, kind, n_prompt, bucket):
    """Prefill a prompt (SSD in chunks of 8, padded to its bucket), then
    decode token by token through the pages and the slot's state (the jnp
    step, or the kernel), in the middle of three slots whose neighbours hold
    nothing: every step's logits are the reference's at that position."""
    m, w = CONFIGS[kind], weights[kind]
    cfg = _cfg(m)
    seq = _tokens(n_prompt + 6, n_prompt)
    want = np.asarray(ref.logits(w, m, seq))
    logits, (kp, vp, state, table) = _prefill(w, cfg, _pool(cfg), seq[:n_prompt], bucket)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL)
    for pos in range(n_prompt, len(seq)):
        toks = jnp.array([[0], [seq[pos]], [0]], jnp.int32)
        logits, kp, vp, state, _ = STEP(w, toks, jnp.asarray(table), jnp.array([0, pos, 0], jnp.int32),
                                        kp, vp, cfg, state)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[pos], atol=ATOL)
    S, _ = state
    assert not np.asarray(S[:, 0]).any() and not np.asarray(S[:, 2]).any()  # the idle rows' state
    np.testing.assert_allclose(  # the slot's state is the reference's closed form
        S[:, 1], ref.ssm_states(w, m, seq, len(seq) - 1), atol=1e-5, rtol=1e-4)


MULTIPLIERS = [
    ("embedding_multiplier", None), ("attention_in_multiplier", None), ("key_multiplier", None),
    ("attention_out_multiplier", None), ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
    ("lm_head_multiplier", None), ("mlp_multipliers", 0), ("mlp_multipliers", 1),
] + [("ssm_multipliers", i) for i in range(5)]


@pytest.mark.parametrize("key,index", MULTIPLIERS)
def test_each_multiplier_is_applied_once(weights, key, index):
    """Each of the nine named multipliers (the two of the SwiGLU and the five
    of the mixer's segments one by one) doubled: the program's logits move,
    by far more than they differ from the reference's, which applies each
    once where the model's config puts it."""
    w = weights["step"]
    prompt = _tokens(12, 77)
    base, _ = _prefill(w, _cfg(M), _pool(_cfg(M)), prompt, 16)
    value = M[key]
    if index is None:
        changed = value * 2.0
    else:
        changed = list(value)
        changed[index] *= 2.0
    m = {**M, key: changed}
    got, _ = _prefill(w, _cfg(m), _pool(_cfg(m)), prompt, 16)
    want = np.asarray(ref.logits(w, m, prompt))[-1]
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert np.abs(got - base).max() > 20 * max(np.abs(got - want).max(), 1e-8)


def test_a_reused_slot_carries_no_stale_state(weights):
    """Nothing of a slot's previous tenant survives admission: a prefill
    overwrites the slot's state and tail whole, whatever they held, and
    leaves the other slots' as they were."""
    cfg, w = _cfg(M), weights["step"]
    prompt = _tokens(10, 9)
    clean, _ = _prefill(w, cfg, _pool(cfg), prompt, 16)
    kp, vp, state, table = _pool(cfg)
    poisoned = tuple(a + 37.0 for a in state)
    reused, (_, _, state, _) = _prefill(w, cfg, (kp, vp, poisoned, table), prompt, 16)
    np.testing.assert_array_equal(reused, clean)
    for a in state:
        np.testing.assert_array_equal(np.asarray(a[:, 0]), 37.0)


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------


def _gap(w, m, prompt, served):
    seq = np.concatenate([prompt, served]).astype(np.int32)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    want = np.asarray(ref.logits(w, m, seq, at=at), np.float64)
    return float((want.max(-1) - want[np.arange(len(at)), np.asarray(served)]).max())


def test_scheduler_serves_the_reference_tokens_through_the_kernel_and_counts_them(weights):
    w, cfg = weights["kernel"], _cfg(M_K)
    sched = DecodeScheduler(w, cfg, max_slots=SLOTS, max_seq=CAP, tokens_per_page=P)
    try:
        assert sched._kp is not None and sched._state is not None and not sched._by_slot
        assert sched._step_counts["decode_ssm_kernel_steps"] == 1
        c0 = obs.counters()
        prompts = [_tokens(n, 20 + n) for n in (9, 4, 14)]
        outs = [sched.submit(p, 5, timeout_s=240) for p in prompts]
        for p, out in zip(prompts, outs):
            assert len(out) == 5 and _gap(w, M_K, p, out) < ATOL
        # the stale-state test: every slot's state poisoned, as a retired
        # sequence would leave it and worse; the next tenant is served as fresh
        sched._state = tuple(a + 37.0 for a in sched._state)
        prompt = _tokens(10, 41)
        out = sched.submit(prompt, 5, timeout_s=240)
        assert _gap(w, M_K, prompt, out) < ATOL
        d = obs.counters_delta(c0)
    finally:
        sched.close()
    assert sched.snapshot()["pages_used"] == 0
    assert d["kv_pages_allocated"] == d["kv_pages_freed"] > 4
    # one request at a time: 4 steps each with 1 live slot
    assert d["decode_steps"] == d["decode_ssm_kernel_steps"] == 16
    assert d["decode_state_slots_held"] == 16 and d["decode_tokens"] == 20
    assert "tfs_decode_ssm_kernel_steps_total" in obs.metrics_text()
