"""Paged KV-cache continuous decode (round 22).

The contract under test, at every layer:

* **bit-identity** — the paged, gathered attention path produces
  token streams bit-identical to the contiguous ``decode.generate``
  pinned at the scheduler's capacity (``cache_len=cap``): the gathered
  extent equals the contiguous cache's, masked slots carry exact-zero
  softmax weight, and rows under the batched einsums are independent,
  so neither paging, batching with strangers, nor joining mid-run may
  change a single token.
* **refusal, not OOM** — the full page span is reserved at admission;
  exhaustion surfaces as a typed :class:`DecodeRefused` (mapped to
  ``server_busy`` on the wire) with ``retry_after_ms``, never as a
  mid-step failure.
* **retirement frees** — deadline expiry, cancellation, and normal
  completion all release pages at a step boundary; neighbors are
  unaffected (their outputs stay bit-identical to an uninterrupted
  run), including under injected transient dispatch faults.
"""

import collections
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu import cancellation
from tensorframes_tpu import observability as obs
from tensorframes_tpu.bridge.client import BridgeClient, ServerBusy
from tensorframes_tpu.bridge.coalescer import DecodeRefused, DecodeScheduler
from tensorframes_tpu.bridge.server import serve
from tensorframes_tpu.models import decode, kv_pager
from tensorframes_tpu.models import transformer as tfm
from tensorframes_tpu.ops import bucketing

CFG = tfm.TransformerConfig(
    vocab_size=97,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,  # GQA: pages store kvh < h heads
    d_ff=64,
    max_seq=64,
    dtype=jnp.float32,
)
PAGE = 8
CAP = 64


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), CFG)


def _reference(params, prompt, max_new, cap=CAP):
    """The contiguous-cache greedy continuation at the paged capacity."""
    out = decode.generate(
        params,
        jnp.asarray(np.asarray(prompt, np.int32)[None]),
        CFG,
        max_new,
        cache_len=cap,
    )
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def _prompts(spec, seed=1):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab_size, size=(L,)).astype(np.int32), mn)
        for L, mn in spec
    ]


# ---------------------------------------------------------------------------
# pager layer: gather-based attention over pages
# ---------------------------------------------------------------------------


def test_paged_attention_bit_identical_to_contiguous(params):
    """Disaggregated prefill + batched decode over pages, mixed prompt
    lengths sharing one pool, equals per-sequence contiguous generate
    token for token."""
    cp = decode.cast_params(params, CFG.dtype)
    max_pages = CAP // PAGE
    jobs = _prompts(((5, 6), (11, 6), (7, 6)), seed=0)
    B = len(jobs)
    refs = [_reference(params, p, mn) for p, mn in jobs]

    pool = kv_pager.PagePool(CFG, n_pages=max_pages * B + 1, tokens_per_page=PAGE)
    kp, vp = pool.k_pages, pool.v_pages
    tables = kv_pager.init_tables(B, max_pages)
    for b, (p, mn) in enumerate(jobs):
        _, pages = pool.allocate(
            kv_pager.pages_for(p.size + mn, PAGE), tenant=f"t{b}"
        )
        for s, pg in enumerate(pages):
            tables = tables.at[b, s].set(pg)

    # prefill lane: each sequence in its own batch (only its pages are
    # written; other rows' tables are absent from the batch entirely)
    outs = [[] for _ in range(B)]
    last = [0] * B
    for b, (p, _) in enumerate(jobs):
        logits, kp, vp = kv_pager.apply_paged(
            cp, jnp.asarray(p[None]), tables[b : b + 1],
            jnp.zeros((1,), jnp.int32), kp, vp, CFG,
        )
        last[b] = int(jnp.argmax(logits[0, -1]))
        outs[b].append(last[b])

    # decode lane: one fixed-shape batched step, per-row frontiers
    indices = jnp.asarray([p.size for p, _ in jobs], jnp.int32)
    toks = jnp.asarray(last, jnp.int32)
    for _ in range(jobs[0][1] - 1):
        toks, kp, vp = kv_pager.paged_decode_step(
            cp, toks, tables, indices, kp, vp, CFG
        )
        indices = indices + 1
        for b in range(B):
            outs[b].append(int(toks[b]))

    for b in range(B):
        assert outs[b] == refs[b], f"row {b} diverged from contiguous"


# (prompt length, max_new): under, at and over the 16 bucket's edge with
# the whole bucket reserved, and one whose bucket (32) overruns its
# reservation (3 pages = 24 positions), so pads 24..31 take the trash page
PREFILL_CASES = {
    "under_edge": (15, 9),
    "at_edge": (16, 8),
    "over_edge": (17, 15),
    "bucket_overruns_reservation": (17, 3),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_paged_prefill_matches_apply_paged_and_spares_neighbors(params, case):
    """The one-request prefill against the general chunk path on the
    same row: the same first token, the same k/v at the prompt's
    positions of the request's pages, and a live neighbour's pages left
    bit for bit as they were."""
    lp, max_new = PREFILL_CASES[case]
    cp = decode.cast_params(params, CFG.dtype)
    max_pages = CAP // PAGE
    pool = kv_pager.PagePool(CFG, n_pages=2 * max_pages + 1, tokens_per_page=PAGE)
    (neighbor, _), (prompt, _) = _prompts(((21, 4), (lp, max_new)), seed=11)

    def table_row(n_tokens):
        _, pages = pool.allocate(kv_pager.pages_for(n_tokens, PAGE))
        row = np.zeros((1, max_pages), np.int32)
        row[0, : len(pages)] = pages
        return pages, jnp.asarray(row)

    # a live neighbour: its prompt's k/v sit in its pages
    neighbor_pages, n_row = table_row(neighbor.size + 4)
    _, kp0, vp0 = kv_pager.apply_paged(
        cp, jnp.asarray(neighbor[None]), n_row, jnp.zeros((1,), jnp.int32),
        pool.k_pages, pool.v_pages, CFG,
    )
    assert np.abs(np.asarray(kp0)[:, :, neighbor_pages]).max() > 0

    pages, row = table_row(lp + max_new)
    lb = bucketing.bucket_for(lp)
    assert (lb > len(pages) * PAGE) == (case == "bucket_overruns_reservation")
    toks = np.zeros((1, lb), np.int32)
    toks[0, :lp] = prompt
    toks = jnp.asarray(toks)

    logits, kp_ref, vp_ref = kv_pager.apply_paged(
        cp, toks, row, jnp.zeros((1,), jnp.int32), kp0, vp0, CFG
    )
    # the executable donates the pools it is given: what they held is
    # read before the call, what they hold after it from what it returns
    kp_before, vp_before = np.asarray(kp0), np.asarray(vp0)
    tok0, kp, vp = kv_pager.paged_prefill(
        cp, toks, row, jnp.asarray([lp - 1], jnp.int32), kp0, vp0, CFG
    )
    assert tok0.shape == (1,)
    assert int(tok0[0]) == int(jnp.argmax(logits[0, lp - 1]))

    def prompt_kv(pages_arr):
        # [n_layers, kvh, lp, Dh]: the request's pages in sequence order
        a = np.asarray(pages_arr)[:, :, pages]
        return a.reshape(*a.shape[:2], -1, a.shape[-1])[:, :, :lp]

    for got, want in ((kp, kp_ref), (vp, vp_ref)):
        got, want = prompt_kv(got), prompt_kv(want)
        # layer 0's k/v are computed before any attention
        assert np.array_equal(got[0], want[0])
        # above it they differ by the softmax's reduction extent only
        np.testing.assert_allclose(got[1:], want[1:], rtol=2e-5, atol=2e-6)
    for got, before in ((kp, kp_before), (vp, vp_before)):
        got = np.asarray(got)
        assert np.array_equal(
            got[:, :, neighbor_pages], before[:, :, neighbor_pages]
        )
        # nothing but the request's pages and the trash page was written
        others = [
            i for i in range(pool.n_pages) if i != 0 and i not in pages
        ]
        assert np.array_equal(got[:, :, others], before[:, :, others])


def _eqn_avals(jaxpr):
    """Every value a jaxpr computes, nested jaxprs (pjit, scan, custom
    rules) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqn_avals(sub)


def test_paged_prefill_scales_with_the_bucket_alone(params):
    """Shape guard: traced at two capacities and two slot counts, same
    bucket, every intermediate of ``paged_prefill`` other than the page
    pools (the stacks: no layer's is sliced out) and the table row has
    the same shape in all four, and the only vocabulary-wide ones are one
    position's ``[1, vocabulary]`` — neither the slot batch, nor the
    gathered capacity, nor every position's logits can come back
    unseen."""
    bucket = 32
    kvh, dh, n = CFG.n_kv_heads, CFG.head_dim, CFG.n_layers
    seen = []
    for cap in (64, 128):
        for slots in (2, 5):
            max_pages = cap // PAGE
            n_pages = slots * max_pages + 1
            pools = jax.ShapeDtypeStruct(
                (n, kvh, n_pages, PAGE, dh), CFG.dtype
            )
            i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
            closed = jax.make_jaxpr(
                lambda *a: kv_pager.paged_prefill(*a, CFG)
            )(params, i32(1, bucket), i32(1, max_pages), i32(1), pools, pools)
            exempt = {
                (n, kvh, n_pages, PAGE, dh),
                # the stacked pool as pages of a head, which a prefill's
                # page write scatters whole
                (n * kvh * n_pages, PAGE, dh), (1, max_pages),
            }
            avals = [
                a for a in _eqn_avals(closed.jaxpr)
                if hasattr(a, "shape") and a.shape not in exempt
            ]
            assert len(avals) > 50  # the walk went inside the jit
            # the logits are of one position: [1, vocabulary], never
            # [1, bucket, vocabulary]
            wide = [a.shape for a in avals if CFG.vocab_size in a.shape]
            assert wide and set(wide) == {(1, CFG.vocab_size)}
            seen.append(collections.Counter(
                (a.shape, str(a.dtype)) for a in avals
            ))
    assert all(s == seen[0] for s in seen[1:])


def test_page_pool_exhaustion_is_typed_and_free_restores():
    pool = kv_pager.PagePool(CFG, n_pages=4, tokens_per_page=PAGE)
    assert pool.stats()["pages_free"] == 3  # page 0 is the trash page
    charge, pages = pool.allocate(3, tenant="a")
    assert len(pages) == 3 and 0 not in pages
    with pytest.raises(kv_pager.PagesExhausted) as ei:
        pool.allocate(2, tenant="b")
    assert ei.value.reason == "pool"
    assert ei.value.retry_after_ms > 0
    assert ei.value.needed == 2 and ei.value.free == 0
    pool.free(charge)
    assert pool.stats()["pages_free"] == 3
    charge2, _ = pool.allocate(3, tenant="b")  # freed pages are reusable
    pool.free(charge2)


# ---------------------------------------------------------------------------
# scheduler layer: continuous batching over page tables
# ---------------------------------------------------------------------------


def test_scheduler_concurrent_mixed_streams_bit_identical(params):
    """Six concurrent mixed short/long streams over four slots: every
    stream's tokens equal its solo contiguous run; late arrivals join
    at step boundaries; retirement returns every page."""
    jobs = _prompts(((5, 6), (11, 3), (7, 10), (3, 4), (9, 2), (13, 7)))
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in jobs]
        results = [None] * len(jobs)
        errs = []

        def worker(i):
            try:
                p, mn = jobs[i]
                results[i] = sched.submit(
                    p, mn, tenant=f"t{i % 2}", timeout_s=120
                )
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        c0 = obs.counters()
        ts = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(jobs))
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        d = obs.counters_delta(c0)
        for i in range(len(jobs)):
            assert results[i] == refs[i], f"stream {i} diverged"
        snap = sched.snapshot()
        assert snap["retired"] == len(jobs)
        assert snap["pages_used"] == 0, "pages leaked past retirement"
        assert snap["prefill_batches"] >= 1
        # six streams over four slots: someone joined a running batch
        assert snap["joined_mid_run"] >= 1
        assert d["decode_tokens"] == sum(mn for _, mn in jobs)
        assert d["kv_pages_allocated"] == d["kv_pages_freed"] > 0
        assert d["decode_prefill_batches"] == snap["prefill_batches"]
    finally:
        sched.close()


@pytest.mark.parametrize("case", ["alone", "among_strangers"])
def test_scheduler_until_stop_retires_at_that_token(params, case):
    """A stream whose ``until`` fires retires AT that token — no token
    past it is emitted or paid for, and its pages come back — and its
    tokens are the prefix of the same request's solo contiguous run,
    whether it is served alone (one slot, one request at a time) or
    beside strangers that run to ``max_new``."""
    jobs = _prompts(((6, 12), (9, 12), (4, 12)), seed=7)
    strangers = (
        _prompts(((5, 9), (12, 5)), seed=8)
        if case == "among_strangers"
        else []
    )
    sched = DecodeScheduler(
        params, CFG, max_slots=4 if strangers else 1,
        tokens_per_page=PAGE, max_seq=CAP,
    )
    try:
        refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in jobs]
        # stop at the token the solo run emits third (or wherever that
        # value shows up first): early, never by max_new
        stops = [r[2] for r in refs]
        want = [r[: r.index(s) + 1] for r, s in zip(refs, stops)]
        want += [
            _reference(params, p, mn, cap=sched.cap) for p, mn in strangers
        ]
        work = [
            (p, mn, lambda tok, s=s: tok == s)
            for (p, mn), s in zip(jobs, stops)
        ] + [(p, mn, None) for p, mn in strangers]
        results = [None] * len(work)
        errs = []

        def worker(i):
            try:
                p, mn, until = work[i]
                results[i] = sched.submit(p, mn, until=until, timeout_s=120)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        c0 = obs.counters()
        ts = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(work))
        ]
        for t in ts:
            t.start()
            if not strangers:
                t.join(timeout=120)  # alone: one request at a time
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive()
        if errs:
            raise errs[0]
        d = obs.counters_delta(c0)
        assert results == want
        for got, (_, mn) in zip(results, jobs):
            assert len(got) < mn  # stopped by `until`, not by max_new
        snap = sched.snapshot()
        assert snap["retired"] == len(work)
        assert snap["pages_used"] == 0, "pages leaked past retirement"
        assert d["decode_tokens"] == sum(len(w) for w in want)
        assert d["kv_pages_allocated"] == d["kv_pages_freed"] > 0
    finally:
        sched.close()


def test_scheduler_prefills_each_admitted_request_alone(params):
    """Three requests admitted at ONE boundary make three dispatches,
    each at its own prompt's bucket, in admission order."""
    jobs = _prompts(((5, 3), (20, 4), (9, 2)), seed=12)
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    reqs = [None] * len(jobs)
    errs = []

    def worker(i):
        try:
            reqs[i] = sched.submit_request(*jobs[i], timeout_s=120)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    try:
        refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in jobs]
        c0 = obs.counters()
        # no driver until all three are queued, in this order: they are
        # then admitted together, at the driver's first boundary
        start_driver = sched._ensure_driver
        sched._ensure_driver = lambda: None
        ts = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(jobs))
        ]
        for i, t in enumerate(ts):
            t.start()
            deadline = time.monotonic() + 30
            while sched.snapshot()["pending"] <= i and not errs:
                assert time.monotonic() < deadline, "submit never queued"
                time.sleep(0.001)
        sched._ensure_driver = start_driver
        with sched._cv:
            sched._ensure_driver()
        for t in ts:
            t.join(timeout=180)
            assert not t.is_alive()
        if errs:
            raise errs[0]
        sched.close()  # the driver's last bump happens before it exits
        d = obs.counters_delta(c0)
    finally:
        sched.close()
    lengths = [int(p.size) for p, _ in jobs]
    buckets = [bucketing.bucket_for(n) for n in lengths]
    assert len(set(buckets)) == 3  # 8, 32, 16: nobody pays the longest's
    assert sched.snapshot()["prefill_batches"] == 3
    assert d["decode_prefill_batches"] == 3
    assert d["decode_prefill_prompt_tokens"] == sum(lengths)
    assert d["decode_prefill_run_tokens"] == sum(buckets)
    assert d["decode_admitted"] == d["decode_first_tokens"] == 3
    # one boundary admitted all three before the first prefill ended ...
    assert max(r.t_admit for r in reqs) <= min(r.t_first for r in reqs)
    assert sched.snapshot()["joined_mid_run"] == 0
    # ... and each first token followed its own dispatch, in that order
    assert [r.t_admit for r in reqs] == sorted(r.t_admit for r in reqs)
    assert reqs[0].t_first < reqs[1].t_first < reqs[2].t_first
    for i, r in enumerate(reqs):
        assert r.out == refs[i], f"stream {i} diverged"


def test_scheduler_admission_refusals_are_typed(params):
    # page-pool refusal: the span cannot be reserved
    small = DecodeScheduler(
        params, CFG, max_slots=2, tokens_per_page=PAGE,
        max_seq=CAP, pool_pages=3,
    )
    try:
        with pytest.raises(DecodeRefused) as ei:
            small.submit(np.arange(5, dtype=np.int32), 30, timeout_s=10)
        assert ei.value.reason == "pages"
        assert ei.value.retry_after_ms > 0
        assert small.snapshot()["refused_pages"] == 1
        # nothing was admitted, so the refusal happened while slots idled
        assert small.snapshot()["refused_while_idle"] == 1
    finally:
        small.close()

    # backlog refusal: active + pending at twice the slot count
    one = DecodeScheduler(
        params, CFG, max_slots=1, tokens_per_page=PAGE, max_seq=CAP,
        pool_pages=16,
    )
    try:
        jobs = _prompts(((6, 12), (6, 12)), seed=3)
        ts = [
            threading.Thread(
                target=lambda p=p, mn=mn: one.submit(p, mn, timeout_s=120)
            )
            for p, mn in jobs
        ]
        for t in ts:
            t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            s = one.snapshot()
            if s["active"] + s["pending"] >= 2:
                break
            time.sleep(0.01)
        else:
            pytest.fail("streams never occupied the backlog")
        with pytest.raises(DecodeRefused) as ei:
            one.submit(np.arange(4, dtype=np.int32), 4, timeout_s=10)
        assert ei.value.reason == "slots"
        assert ei.value.retry_after_ms > 0
        for t in ts:
            t.join()
    finally:
        one.close()


def test_scheduler_deadline_expiry_frees_pages_neighbors_bit_identical(
    params,
):
    """An expired deadline cancels at a step boundary: the victim's
    submit raises ``DeadlineExceeded``, its pages return to the pool,
    and the neighbors' streams are bit-identical to an uninterrupted
    run."""
    neighbors = _prompts(((5, 8), (9, 8)), seed=4)
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in neighbors]
        results = [None] * len(neighbors)
        victim_err = []
        errs = []

        def neighbor(i):
            try:
                p, mn = neighbors[i]
                results[i] = sched.submit(p, mn, timeout_s=120)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        def victim():
            scope = cancellation.CancelScope(deadline_s=0.0, label="victim")
            try:
                with cancellation.activate(scope):
                    sched.submit(
                        np.arange(7, dtype=np.int32), 12, timeout_s=120
                    )
            except BaseException as e:  # noqa: BLE001 — asserted below
                victim_err.append(e)

        c0 = obs.counters()
        ts = [threading.Thread(target=neighbor, args=(i,)) for i in (0, 1)]
        ts.append(threading.Thread(target=victim))
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        d = obs.counters_delta(c0)
        assert len(victim_err) == 1
        assert isinstance(victim_err[0], cancellation.Cancelled)
        for i in range(len(neighbors)):
            assert results[i] == refs[i], f"neighbor {i} diverged"
        snap = sched.snapshot()
        assert snap["pages_used"] == 0, "cancelled stream leaked pages"
        assert d["kv_pages_allocated"] == d["kv_pages_freed"] > 0
        assert d["bridge_deadline_exceeded"] >= 1
    finally:
        sched.close()


def test_scheduler_drain_mid_stream_completes_in_flight(params):
    """close() mid-stream drains: already-submitted streams run to
    retirement bit-identically; later submits are refused outright.

    Deterministic since the scheduler counts admissions: close() comes
    only once every stream has been ADMITTED (``decode_admitted`` in the
    snapshot), so no submit can still be on its way in and lose the
    race with close() — the way this test used to fail under load —
    and the streams are long enough that the batch is still live."""
    jobs = _prompts(((5, 40), (8, 40), (11, 40)), seed=6)
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in jobs]
    results = [None] * len(jobs)
    errs = []

    def worker(i):
        try:
            p, mn = jobs[i]
            results[i] = sched.submit(p, mn, timeout_s=120)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [
        threading.Thread(target=worker, args=(i,)) for i in range(len(jobs))
    ]
    for t in ts:
        t.start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if sched.snapshot()["decode_admitted"] == len(jobs):
            break
        time.sleep(0.001)
    else:
        pytest.fail("not every stream was admitted")
    sched.close()  # every stream is in flight (or already retired)
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    for i in range(len(jobs)):
        assert results[i] == refs[i], f"stream {i} diverged across drain"
    snap = sched.snapshot()
    assert snap["pages_used"] == 0
    assert snap["decode_first_tokens"] == len(jobs)
    with pytest.raises(RuntimeError):
        sched.submit(np.arange(4, dtype=np.int32), 2, timeout_s=5)


def test_scheduler_chaos_transients_bit_identical(params, monkeypatch):
    """Chaos leg: injected transient dispatch faults at step boundaries
    are retried (functional page state makes the retry recompute the
    identical step) — streams stay bit-identical and no page leaks."""
    monkeypatch.setenv(
        "TFS_FAULT_INJECT",
        "transient:block=1:attempt=0;transient:block=2:attempt=0",
    )
    jobs = _prompts(((5, 6), (9, 5), (7, 4)), seed=7)
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        first_pools = (sched._kp, sched._vp)
        refs = [_reference(params, p, mn, cap=sched.cap) for p, mn in jobs]
        results = [None] * len(jobs)
        errs = []

        def worker(i):
            try:
                p, mn = jobs[i]
                results[i] = sched.submit(p, mn, timeout_s=120)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        c0 = obs.counters()
        ts = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(jobs))
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        d = obs.counters_delta(c0)
        assert d["faults_injected"] >= 1, "chaos plan never fired"
        for i in range(len(jobs)):
            assert results[i] == refs[i], f"stream {i} diverged under chaos"
        assert sched.snapshot()["pages_used"] == 0
        # the retried dispatches ran on DONATED pools: the transient fires
        # before the executable is called, so a retry finds them whole
        assert all(a.is_deleted() for a in first_pools)
        assert not sched._kp.is_deleted() and not sched._vp.is_deleted()
    finally:
        sched.close()


def test_scheduler_takes_the_pool_arrays_and_the_executables_donate_them(
    params,
):
    """Ownership (PR 33): a scheduler built on a pool leaves the pool
    holding no array — shapes, free list and accounting stay — and every
    dispatch consumes the pools it is given and hands back the one pair
    there is."""
    sched = DecodeScheduler(
        params, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        pool = sched.pool
        assert pool.k_pages is None and pool.v_pages is None
        assert pool.state is None
        assert pool.free_count() == pool.capacity == 2 * (CAP // PAGE)
        first = (sched._kp, sched._vp)
        shape = (CFG.n_layers, CFG.n_kv_heads, pool.n_pages, PAGE, CFG.head_dim)
        assert {a.shape for a in first} == {shape}
        ((p, mn),) = _prompts(((5, 4),), seed=3)
        assert sched.submit(p, mn, timeout_s=120) == _reference(
            params, p, mn, cap=sched.cap
        )
        assert all(a.is_deleted() for a in first)
        assert not sched._kp.is_deleted() and sched._kp.shape == shape
        assert not sched._vp.is_deleted() and sched._vp.shape == shape
        assert pool.k_pages is None and pool.free_count() == pool.capacity
    finally:
        sched.close()


def test_scheduler_never_recalls_a_dispatch_that_started(params, monkeypatch):
    """A failure AFTER the executable was called is not retried, whatever
    it is — the call consumed the pools it was given — and fails the
    waiters; the scheduler then serves the next request on fresh pools."""
    from tensorframes_tpu import faults

    sound = kv_pager.paged_decode_step
    calls = []

    def consumed(*args):
        calls.append(sound(*args))
        raise faults.InjectedTransient("UNAVAILABLE: after the call started")

    ((p, mn),) = _prompts(((6, 4),), seed=5)
    sched = DecodeScheduler(
        params, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        monkeypatch.setattr(kv_pager, "paged_decode_step", consumed)
        with pytest.raises(faults.InjectedTransient):
            sched.submit(p, mn, timeout_s=120)
        assert len(calls) == 1
        assert sched.snapshot()["pages_used"] == 0
        monkeypatch.setattr(kv_pager, "paged_decode_step", sound)
        assert sched.submit(p, mn, timeout_s=120) == _reference(
            params, p, mn, cap=sched.cap
        )
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# serving layer: the gated decode RPC
# ---------------------------------------------------------------------------


def test_decode_rpc_end_to_end(params):
    """BridgeClient.decode → scheduler → bit-identical tokens, with
    speculative opt-in, per-tenant token billing, health and metrics
    surfacing the round-22 families."""
    dcfg = tfm.TransformerConfig(
        vocab_size=CFG.vocab_size, d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=2, d_ff=32, max_seq=CAP, dtype=jnp.float32,
    )
    dparams = tfm.init(jax.random.PRNGKey(1), dcfg)
    srv = serve(
        port=0,
        decode_model=dict(
            params=params, cfg=CFG, draft_params=dparams, draft_cfg=dcfg,
            max_slots=4, tokens_per_page=PAGE, max_seq=CAP,
        ),
    )
    host, port = srv.server_address
    client = BridgeClient(host=host, port=port, tenant="acme")
    try:
        prompt = [int(t) for t in _prompts(((7, 5),), seed=8)[0][0]]
        ref = _reference(params, prompt, 5, cap=srv.decode_scheduler.cap)

        r = client.decode(prompt, max_new=5)
        assert r["tokens"] == ref
        assert r["generated"] == 5 and r["speculative"] is False

        rs = client.decode(prompt, max_new=5, speculative=True)
        assert rs["speculative"] is True
        assert rs["tokens"] == ref, "draft/verify diverged from greedy"

        h = client.call("health")
        dsnap = h["decode"]
        assert dsnap["retired"] >= 1 and dsnap["pages_used"] == 0
        for key in (
            "decode_tokens",
            "kv_pages_allocated",
            "kv_pages_freed",
            "decode_prefill_batches",
        ):
            assert key in h["counters"], key
        assert h["counters"]["decode_tokens"] >= 10

        text = client.call("metrics")["text"]
        for family in (
            "tfs_decode_tokens_total",
            "tfs_kv_pages_allocated_total",
            "tfs_kv_pages_freed_total",
            "tfs_decode_prefill_batches_total",
            "tfs_kv_pages_free",
            "tfs_kv_pages_capacity",
            "tfs_decode_slots_free",
        ):
            assert family in text, family
        # decode bills generated tokens per tenant
        assert 'tfs_request_rows_total{tenant="acme"' in text
    finally:
        client.close()
        srv.close(drain_s=2.0)


def test_decode_rpc_exhaustion_maps_to_server_busy(params):
    srv = serve(
        port=0,
        decode_model=dict(
            params=params, cfg=CFG, max_slots=2,
            tokens_per_page=PAGE, max_seq=CAP, pool_pages=3,
        ),
    )
    host, port = srv.server_address
    client = BridgeClient(host=host, port=port, busy_retries=0)
    try:
        with pytest.raises(ServerBusy) as ei:
            client.decode(list(range(5)), max_new=30)
        assert ei.value.retry_after_ms > 0
    finally:
        client.close()
        srv.close(drain_s=1.0)


def test_decode_rpc_unconfigured_is_refused(params):
    srv = serve(port=0)
    host, port = srv.server_address
    client = BridgeClient(host=host, port=port)
    try:
        with pytest.raises(Exception) as ei:
            client.decode([1, 2, 3], max_new=2)
        assert "decode" in str(ei.value).lower()
    finally:
        client.close()
        srv.close(drain_s=1.0)


def test_decode_env_knob_routing(params):
    """A scheduler built WITHOUT explicit knobs takes its page size and
    slot count from TFS_DECODE_PAGE_TOKENS / TFS_DECODE_MAX_SLOTS (the
    main suite pins both inert -> defaults 16/8; run_tests.sh's decode
    tier re-runs this file with the knobs LIVE to prove the routing)."""
    import os

    raw_p = (os.environ.get("TFS_DECODE_PAGE_TOKENS") or "").strip()
    raw_s = (os.environ.get("TFS_DECODE_MAX_SLOTS") or "").strip()
    exp_p = int(raw_p) if raw_p else 16
    exp_s = int(raw_s) if raw_s else 8
    assert kv_pager.page_tokens() == exp_p
    sched = DecodeScheduler(params, CFG)
    try:
        assert sched.pool.tokens_per_page == exp_p
        assert sched.max_slots == exp_s
        # env-sized schedulers keep the bit-identity contract too: the
        # gathered extent is still whole pages covering cfg.max_seq
        assert sched.cap == kv_pager.pages_for(CFG.max_seq, exp_p) * exp_p
        prompt = np.arange(5, dtype=np.int32) % CFG.vocab_size
        got = sched.submit(prompt, 4, timeout_s=120)
        ref = decode.generate(
            params, jnp.asarray(prompt[None]), CFG, 4, cache_len=sched.cap
        )
        assert got == [int(t) for t in np.asarray(ref)[0, prompt.size :]]
    finally:
        sched.close()
