"""Brumby's block on the serving path, against its plain reference.

The block (``transformer.BlockSpec(attention="retention")``) keeps no pages:
a sequence's whole past is a float32 state of fixed size a layer, held per
decode slot, stepped in one pass (``parallel/retention.py``, or the ``jnp``
step where the kernel does not fit) and prefilled in chunks that resume from
it.  Everything here runs at tiny widths in float32 on the CPU, with seeded
weights in the layout the program consumes, made by the reference
(``perfbench/refs/brumby_decoder.py``, which imports nothing of the program
and computes retention in ATTENTION form: no state, no feature map, no
chunk) and handed to both sides.

Tolerances.  Program and reference compute the same float32 mathematics by
other algorithms (a state read through phi against squared scores over the
whole sequence), so logits of unit scale agree to a few 1e-6; ``ATOL`` 2e-4
leaves room for XLA:CPU's reassociation and would still catch a wrong
offset of phi, a missing decay, a stale state or a pad token folded into
the state, each of which moves logits by 1e-2 or more.
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

from perfbench.drivers.bridge_decode_brumby import transformer_config  # noqa: E402
from perfbench.refs import brumby_decoder as ref  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeRefused, DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import kv_pager, retention  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402
from tensorframes_tpu.ops import frame_cache  # noqa: E402
from tensorframes_tpu.parallel import retention as kernel  # noqa: E402

ATOL = 2e-4
PREFILL = jax.jit(kv_pager._prefill_forward, static_argnums=6)
STEP = jax.jit(kv_pager._step_forward, static_argnums=6)
CAP, SLOTS = 64, 3


def _tiny():
    with open(os.path.join(ROOT, "perfbench", "configs", "brumby_14b_l8.json")) as f:
        m = json.load(f)
    return {**m, **m["tiny"]}


M = _tiny()
CFG = transformer_config(M, CAP, jnp.float32)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, M, jnp.float32)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, M["vocab_size"], size=n).astype(np.int32)


def _prefill(weights, state, chunk, bucket, slot, start=0):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : len(chunk)] = chunk
    logits, _, _, state, _ = PREFILL(
        weights, jnp.asarray(toks), None, jnp.asarray([len(chunk) - 1], jnp.int32), None, None,
        CFG, state, jnp.asarray([slot], jnp.int32), jnp.asarray([start], jnp.int32))
    return np.asarray(logits[0]), state


def _rand_qkv(rng, L, h, kvh, dh):
    q, k, v = (jnp.asarray(rng.normal(size=(L, n, dh)), jnp.float32) for n in (h, kvh, kvh))
    return q, k, v, jnp.asarray(np.log(rng.uniform(0.8, 0.999, size=(L, kvh))), jnp.float32)


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_block_spec_takes_the_fourth_kind():
    assert CFG.block.attention == "retention" and not CFG.block.stateless and not CFG.block.routes
    assert (CFG.head_dim, CFG.block.norm_eps) == (16, 1e-6)
    with pytest.raises(ValueError, match="retention"):
        tfm.BlockSpec(attention="linear")
    with pytest.raises(NotImplementedError):
        tfm.apply({}, jnp.zeros((1, 4), jnp.int32), CFG)


@pytest.mark.parametrize("dh", [8, 16, 128])
def test_phi_is_the_symmetric_square(dh):
    """phi(u) . phi(w) = (u . w)^2 on D = dh (dh + 1) / 2 entries, stored by
    offset on (dh / 2 + 1) rows of dh: not the dh x dh full square."""
    rng = np.random.default_rng(dh)
    u, w = (jnp.asarray(rng.normal(size=(5, dh)), jnp.float32) for _ in range(2))
    got = jnp.sum(retention.expand(u) * retention.expand(w), axis=(-1, -2))
    # float32 sums of D products: to rounding at the scale of |u|^2 |w|^2
    scale = float(jnp.max(jnp.sum(u * u, -1) * jnp.sum(w * w, -1)))
    np.testing.assert_allclose(got, jnp.sum(u * w, -1) ** 2, rtol=2e-5, atol=1e-6 * scale)
    c = retention.coef(dh)
    assert int((c > 0).sum()) == retention.phi_dim(dh) == dh * (dh + 1) // 2
    assert c.shape == (retention.offsets(dh), dh) == (dh // 2 + 1, dh) and c.size < dh * dh
    if dh == 128:
        assert (retention.phi_dim(dh), c.size) == (8256, 8320)
    # every unordered pair once: the pairs (a, a + o) that carry a coefficient
    pairs = {frozenset((a, (a + o) % dh)) for o in range(c.shape[0]) for a in range(dh) if c[o, a] > 0}
    assert len(pairs) == retention.phi_dim(dh)


def _attention_form(q, k, v, log_g):
    """The equations as ISSUE 37 writes them, in numpy float64."""
    q, k, v, a = (np.asarray(t, np.float64) for t in (q, k, v, np.cumsum(np.asarray(log_g), 0)))
    L, h, dh = q.shape
    g = h // k.shape[1]
    y = np.zeros((L, h, dh))
    for t in range(L):
        for i in range(h):
            j = i // g
            w = (q[t, i] @ k[: t + 1, j].T / np.sqrt(dh)) ** 2 * np.exp(a[t, j] - a[: t + 1, j])
            y[t, i] = w @ v[: t + 1, j] / (w.sum() + retention.EPS)
    return y


@pytest.mark.parametrize("chunk", [4, 8, 24])
def test_recurrent_chunked_and_attention_forms_agree(chunk):
    rng = np.random.default_rng(chunk)
    L, h, kvh, dh = 24, 4, 2, 16
    q, k, v, log_g = _rand_qkv(rng, L, h, kvh, dh)
    O = retention.offsets(dh)
    S0, z0 = jnp.zeros((kvh, O, dh, dh)), jnp.zeros((kvh, O, dh))
    y_c, S_c, z_c = retention.chunked(q, k, v, log_g, S0, z0, jnp.ones(L, bool), chunk=chunk)
    S, z, ys = S0[None], z0[None], []
    for t in range(L):
        y, S, z = retention.step(q[t][None], k[t][None], v[t][None], log_g[t][None], S, z,
                                 jnp.array([True]))
        ys.append(y[0])
    want = _attention_form(q, k, v, log_g)
    np.testing.assert_allclose(jnp.stack(ys), want, atol=2e-5)
    np.testing.assert_allclose(y_c, want, atol=2e-5)
    np.testing.assert_allclose(S_c, S[0], atol=1e-5)
    np.testing.assert_allclose(z_c, z[0], atol=1e-5)
    # the reference's own attention form, blocked over the queries
    np.testing.assert_allclose(ref.retention(q, k, v, log_g), want, atol=2e-5)


def test_chunked_resumes_and_padding_stays_out_of_the_state():
    rng = np.random.default_rng(3)
    L, h, kvh, dh = 24, 4, 2, 16
    q, k, v, log_g = _rand_qkv(rng, L, h, kvh, dh)
    O = retention.offsets(dh)
    S0, z0 = jnp.zeros((kvh, O, dh, dh)), jnp.zeros((kvh, O, dh))
    real = 21
    valid = jnp.arange(L) < real
    y, S, z = retention.chunked(q, k, v, log_g, S0, z0, valid, chunk=8)
    # the same 21 tokens with nothing after them
    y21, S21, z21 = retention.chunked(q[:real], k[:real], v[:real], log_g[:real], S0, z0,
                                      jnp.ones(real, bool), chunk=real)
    np.testing.assert_allclose(y[:real], y21, atol=2e-5)
    np.testing.assert_allclose(S, S21, atol=1e-5)
    np.testing.assert_allclose(z, z21, atol=1e-5)
    # in two calls, the second from the first's state
    y1, S1, z1 = retention.chunked(q[:16], k[:16], v[:16], log_g[:16], S0, z0, jnp.ones(16, bool), chunk=8)
    y2, S2, z2 = retention.chunked(q[16:], k[16:], v[16:], log_g[16:], S1, z1, valid[16:], chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2])[:real], y[:real], atol=1e-6)
    np.testing.assert_allclose(S2, S, atol=1e-6)


# ---------------------------------------------------------------------------
# the kernel (interpret mode) against the jnp step
# ---------------------------------------------------------------------------

KERNEL = jax.jit(kernel.retention_step, static_argnames=("interpret",))


def _kernel_case(seed=0, B=3, h=4, kvh=2, dh=128, layers=2):
    rng = np.random.default_rng(seed)
    O = retention.offsets(dh)
    q, k, v = (jnp.asarray(rng.normal(size=(B, n, dh)), jnp.float32) for n in (h, kvh, kvh))
    log_g = jnp.asarray(np.log(rng.uniform(0.8, 0.999, size=(B, kvh))), jnp.float32)
    S = jnp.asarray(rng.normal(size=(layers, B, kvh, O, dh, dh)), jnp.float32)
    z = jnp.asarray(np.abs(rng.normal(size=(layers, B, kvh, O, dh))) + 1.0, jnp.float32)
    return q, k, v, log_g, S, z


@pytest.mark.parametrize("live", [(1, 0, 1), (1, 1, 1), (0, 1, 0), (0, 0, 0)])
def test_kernel_is_the_jnp_step_and_moves_live_rows_only(live):
    q, k, v, log_g, S, z = _kernel_case()
    live = jnp.array(live, bool)
    y, S1, z1 = KERNEL(retention.scaled(q), retention.scaled(k), v, log_g, S, z, live, 1,
                       interpret=True)
    want_y, want_S, want_z = retention.step(q, k, v, log_g, S[1], z[1], live)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(S1[1], want_S, atol=1e-5)
    np.testing.assert_allclose(z1[1], want_z, atol=1e-5)
    np.testing.assert_array_equal(S1[0], S[0])  # the other layer as it lay
    np.testing.assert_array_equal(z1[0], z[0])
    for b in np.flatnonzero(~np.asarray(live)):  # a row without a sequence: bit for bit
        np.testing.assert_array_equal(S1[1, b], S[1, b])
        assert not np.asarray(y[b]).any()


def test_kernel_writes_the_state_where_it_lies():
    """State in and out are one buffer: the call aliases S and z (operands
    5 and 6, after the three scalar-prefetch ones and the two tiles) to its
    first two results, and is named for the trace."""
    q, k, v, log_g, S, z = _kernel_case(B=2)
    jaxpr = jax.make_jaxpr(
        lambda *a: kernel.retention_step(*a, jnp.array([True, True]), 0, interpret=True)
    )(retention.scaled(q), retention.scaled(k), v, log_g, S, z)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((5, 0), (6, 1))
    assert kernel.KERNEL_NAME == "tfs_retention_step" in str(call.params)
    assert kernel.fits(128) and not kernel.fits(16) and not kernel.fits(128, jnp.bfloat16)
    assert not kv_pager.retention_kernel_fits(CFG)  # heads of 16: the jnp step


# ---------------------------------------------------------------------------
# the pool: no pages, a state a slot
# ---------------------------------------------------------------------------


def test_pool_holds_no_pages_and_counts_state_bytes():
    pool = kv_pager.PagePool(CFG, SLOTS + 1, slots=SLOTS)
    assert pool.k_pages is None and pool.v_pages is None
    S, z = pool.state
    O, dh, kvh = retention.offsets(16), 16, CFG.n_kv_heads
    assert S.shape == (CFG.n_layers, SLOTS, kvh, O, dh, dh) and z.shape == (CFG.n_layers, SLOTS, kvh, O, dh)
    assert S.dtype == z.dtype == jnp.float32  # whatever the compute dtype
    per_slot = 4 * CFG.n_layers * kvh * O * dh * (dh + 1)
    assert pool.page_bytes == per_slot == retention.state_bytes_per_slot(CFG)
    before = frame_cache.budget_bytes_resident()
    charge, pages = pool.allocate(1, tenant="t")
    assert len(pages) == 1 and frame_cache.budget_bytes_resident() - before == per_slot
    pool.free(charge)
    pool.free(None)  # a request no slot was given holds nothing
    assert frame_cache.budget_bytes_resident() == before
    none, nothing, taken = pool.take()
    assert none is None and nothing is None
    assert taken[0] is S and pool.state is None
    with pytest.raises(ValueError, match="slots"):
        kv_pager.PagePool(CFG, SLOTS + 1)
    bf16 = kv_pager.PagePool(transformer_config(M, CAP, jnp.bfloat16), SLOTS + 1, slots=SLOTS)
    assert bf16.state[0].dtype == jnp.float32


def test_state_is_donated_by_both_executables(weights):
    state = retention.init_state(CFG, SLOTS)
    toks = jnp.asarray(np.pad(_tokens(6, 0), (0, 2))[None])
    tok, new = kv_pager.paged_prefill(
        weights, toks, None, jnp.array([5], jnp.int32), None, None, CFG,
        slot=jnp.array([1], jnp.int32), retention=state, start=jnp.array([0], jnp.int32))
    assert all(a.is_deleted() for a in state) and tok.shape == (1,)
    live = jnp.array([[0], [1], [0]], jnp.int32)
    nxt, newer = kv_pager.paged_decode_step(
        weights, jnp.array([0, int(tok[0]), 0], jnp.int32), live, jnp.array([0, 6, 0], jnp.int32),
        None, None, CFG, retention=new)
    assert all(a.is_deleted() for a in new) and nxt.shape == (SLOTS,)
    assert all(a.dtype == jnp.float32 and not a.is_deleted() for a in newer)


# ---------------------------------------------------------------------------
# the serving path against the reference's full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_prompt,bucket", [(11, 16), (5, 8), (16, 16)])
def test_prefill_then_decode_matches_reference_logits(weights, n_prompt, bucket):
    """Prefill a prompt (chunked form, padded to its bucket), then decode
    token by token through the slot's state (recurrent form), in the middle
    of three slots whose neighbours hold nothing: every step's logits are
    the reference's at that position, which it gets in attention form over
    the whole sequence."""
    seq = _tokens(n_prompt + 6, n_prompt)
    want = np.asarray(ref.logits(weights, M, seq))
    logits, state = _prefill(weights, retention.init_state(CFG, SLOTS), seq[:n_prompt], bucket, 1)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=ATOL)
    live = jnp.array([[0], [1], [0]], jnp.int32)
    for pos in range(n_prompt, len(seq)):
        toks = jnp.array([[0], [seq[pos]], [0]], jnp.int32)
        logits, _, _, state, _ = STEP(weights, toks, live, jnp.array([0, pos, 0], jnp.int32),
                                      None, None, CFG, state)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[pos], atol=ATOL)
    S, z = state
    assert not np.asarray(S[:, 0]).any() and not np.asarray(S[:, 2]).any()  # the idle rows' state


def test_prompt_in_two_dispatches_is_the_prompt_in_one(weights):
    prompt = _tokens(21, 5)
    want = np.asarray(ref.logits(weights, M, prompt))[-1]
    one, s_one = _prefill(weights, retention.init_state(CFG, SLOTS), prompt, 32, 2)
    _, s_two = _prefill(weights, retention.init_state(CFG, SLOTS), prompt[:16], 16, 2)
    two, s_two = _prefill(weights, s_two, prompt[16:], 8, 2, start=16)
    np.testing.assert_allclose(one, want, atol=ATOL)
    np.testing.assert_allclose(two, want, atol=ATOL)
    for a, b in zip(s_one, s_two):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_dispatch_that_starts_a_sequence_ignores_what_the_slot_held(weights):
    """Nothing of a slot's previous tenant survives admission: a chunk at
    position 0 starts from zeros whatever the slot holds.  A chunk that
    resumes reads the slot's state, so the same poison there shows."""
    prompt = _tokens(12, 9)
    clean, _ = _prefill(weights, retention.init_state(CFG, SLOTS), prompt, 16, 0)
    poisoned = tuple(a + 37.0 for a in retention.init_state(CFG, SLOTS))
    reused, state = _prefill(weights, poisoned, prompt, 16, 0)
    np.testing.assert_array_equal(reused, clean)
    np.testing.assert_array_equal(np.asarray(state[0][:, 1]), 37.0)  # the other slots untouched
    resumed, _ = _prefill(weights, poisoned, prompt, 16, 0, start=12)
    assert np.abs(resumed - clean).max() > 1e-2


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------


def _gap(weights, prompt, served):
    """The widest gap by which a served token's logit lies below the
    reference's best, teacher-forced."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    want = np.asarray(ref.logits(weights, M, seq, at=at), np.float64)
    return float((want.max(-1) - want[np.arange(len(at)), np.asarray(served)]).max())


@pytest.fixture()
def sched(weights):
    s = DecodeScheduler(weights, CFG, max_slots=SLOTS, max_seq=CAP)
    yield s
    s.close()


def test_scheduler_serves_the_reference_tokens_and_counts_them(weights, sched):
    assert sched.pool.k_pages is None and sched.pool.state is None and sched._kp is None
    assert sched.cap == CAP and sched.max_pages == 1 and sched._step_counts["decode_kernel_steps"] == 0
    c0 = obs.counters()
    prompts = [_tokens(n, 20 + n) for n in (9, 4, 14)]
    outs = [sched.submit(p, 5, timeout_s=120) for p in prompts]
    for p, out in zip(prompts, outs):
        assert len(out) == 5 and _gap(weights, p, out) < ATOL
    d = obs.counters_delta(c0)
    snap = sched.snapshot()
    assert snap["pages_used"] == 0 and snap["pages_capacity"] == SLOTS
    assert d["kv_pages_allocated"] == d["kv_pages_freed"] == 3  # one "page" a sequence: its slot's state
    # one request at a time: 4 steps each with 1 live slot
    assert d["decode_steps"] == 12 and d["decode_kernel_steps"] == 0
    assert d["decode_tokens"] == 15
    assert d["decode_prefill_batches"] == 3 and d.get("decode_prefill_resumes", 0) == 0
    assert "tfs_decode_prefill_resumes_total" in obs.metrics_text()


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_a_served_state_reads_out_what_the_reference_sums(weights, sched, monkeypatch, kept):
    """What the benchmark's ``state_readout_gap`` compares: the state a served
    request leaves in its slot, read with the reference's queries at the last
    fed position, against the reference's attention form over the whole
    sequence there, every layer.  A state kept in bfloat16 reads out
    thousands of times further off than the float32 one."""
    monkeypatch.setattr(retention, "PREFILL_TOKENS", 8)  # 8 + 8 + 5, then 4 steps
    def rounding(fn):
        def wrapped(*args, **kw):
            toks, state = fn(*args, **kw)
            return toks, jax.tree.map(lambda a: jax.lax.reduce_precision(a, 8, 7), state)
        return wrapped

    if kept == "bfloat16":
        for name in ("paged_prefill", "paged_decode_step"):
            monkeypatch.setattr(kv_pager, name, rounding(getattr(kv_pager, name)))
    prompt = _tokens(21, 41)
    served = sched.submit(prompt, 5, timeout_s=120)
    slot = sched._free[-1]
    S, z = (a[:, slot] for a in sched._state)
    fed = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    q, want = ref.read_outs(weights, M, fed, len(fed) - 1)
    got = retention.read_out(q, S, z)
    gap = float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2)))
    assert (gap < 1e-5) if kept == "float32" else (1e-3 < gap < 1e-1), gap


def test_scheduler_feeds_a_long_prompt_in_dispatches_that_resume(weights, sched, monkeypatch):
    prompt = _tokens(21, 31)
    whole = sched.submit(prompt, 6, timeout_s=120)
    monkeypatch.setattr(retention, "PREFILL_TOKENS", 8)
    c0 = obs.counters()
    parts = sched.submit(prompt, 6, timeout_s=120)
    d = obs.counters_delta(c0)
    assert parts == whole and _gap(weights, prompt, parts) < ATOL
    assert d["decode_prefill_batches"] == 3 and d["decode_prefill_resumes"] == 2  # 8 + 8 + 5
    assert d["decode_prefill_prompt_tokens"] == 21 and d["decode_prefill_run_tokens"] == 24
    assert d["decode_admitted"] == d["decode_first_tokens"] == 1


def test_a_retired_slots_state_does_not_reach_its_next_tenant(weights, sched):
    """The stale-state test: every slot's state poisoned, as a retired
    sequence would leave it and worse; the next tenant's tokens are what a
    fresh scheduler serves.  (Fed as a resumed chunk the same state changes
    them: ``test_a_dispatch_that_starts_a_sequence_ignores_what_the_slot_
    held``.)"""
    prompt = _tokens(10, 41)
    first = sched.submit(_tokens(13, 40), 4, timeout_s=120)
    assert len(first) == 4
    sched._state = tuple(a + 37.0 for a in sched._state)
    out = sched.submit(prompt, 5, timeout_s=120)
    assert _gap(weights, prompt, out) < ATOL


def test_admission_is_by_slot_and_length_refuses_nothing(weights, sched, monkeypatch):
    # a sequence as long as the capacity costs what a short one does
    out = sched.submit(_tokens(CAP - 3, 50), 3, timeout_s=240)
    assert len(out) == 3 and sched.snapshot()["refused_pages"] == 0
    with pytest.raises(ValueError, match="capacity"):
        sched.submit(_tokens(CAP, 51), 1)
    # the budget that cannot pay a slot's state refuses, typed
    monkeypatch.setattr(frame_cache._budget, "charge", lambda *a, **k: False)
    with pytest.raises(DecodeRefused) as e:
        sched.submit(_tokens(5, 52), 2, timeout_s=60)
    assert e.value.reason == "pages" and sched.snapshot()["refused_pages"] == 1
