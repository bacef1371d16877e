"""The decode step's inputs live on the device from step to step (PR 40).

``coalescer._StepInputs`` keeps the fed tokens, the positions and the page
tables as host mirrors AND as a copy on the device.  A step is dispatched on
the copy; right after its call the copy is advanced on the device
(``kv_pager.advance_step_inputs``), and only a boundary that wrote a mirror
(an admission, a first token, a retirement, a failure) makes the next step
upload.  Held here, for the three branches of ``DecodeScheduler._run`` (the
dense block: pools; ``cca`` + experts: pools, state, routing; retention: a
state a slot and ``tables [slots, 1]``):

* a population that changes mid-run is served the tokens it is served when
  every step uploads, and the block kind's own reference's tokens;
* after every ``decode.step.emit`` the copy equals the mirrors, idle slots
  included;
* ``span_n.decode.step.upload`` counts the steps whose boundary changed
  something, step for step, and nothing over a fixed population;
* what a dispatch was handed is not what the host writes next;
* a dispatch that fails after it started leaves no copy behind;
* a token altered where it is produced is the token the next step is fed.
"""

import json
import os
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.drivers import bridge_decode_brumby, bridge_decode_zaya  # noqa: E402
from perfbench.refs import brumby_decoder, zaya_decoder  # noqa: E402
from tensorframes_tpu import faults  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeRefused, DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import decode, kv_pager  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402

ATOL = 2e-4
SLOTS = 3
UPLOADS = "span_n.decode.step.upload"
# (prompt tokens, new tokens, the step count at which it is submitted): the
# first three fill the slots, the others join as earlier ones retire
SCRIPT = ((5, 9, 0), (11, 4, 0), (3, 12, 0), (9, 6, 2), (6, 5, 4), (4, 7, 7))


def _tiny(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        m = json.load(f)
    return {**m, **m["tiny"]}


def _dense():
    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype=jnp.float32,
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)

    def check(prompt, out, sched):
        want = decode.generate(
            params, jnp.asarray(prompt[None]), cfg, len(out), cache_len=sched.cap
        )
        assert out == [int(t) for t in np.asarray(want)[0, len(prompt):]]

    return types.SimpleNamespace(
        params=params, cfg=cfg, check=check,
        serve=dict(tokens_per_page=8, max_seq=64),
    )


def _teacher_forced(logits_at):
    """Every served token is the reference's best under teacher forcing:
    ``logits_at(seq, at)`` are the reference's logits at positions ``at``."""

    def check(prompt, out, sched):
        seq = np.concatenate([prompt, out]).astype(np.int32)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        lg = np.asarray(logits_at(seq, at), np.float64)
        assert (lg.max(-1) - lg[np.arange(len(at)), out]).max() <= ATOL

    return check


def _zaya():
    m = _tiny("zaya1_8b_l20")
    weights = zaya_decoder.make_weights(7, m, jnp.float32)
    return types.SimpleNamespace(
        params=weights, cfg=bridge_decode_zaya.transformer_config(m, 32, jnp.float32),
        check=_teacher_forced(lambda seq, at: zaya_decoder.logits(weights, m, seq)[at]),
        serve=dict(tokens_per_page=4, max_seq=32),
    )


def _brumby():
    m = _tiny("brumby_14b_l8")
    weights = brumby_decoder.make_weights(7, m, jnp.float32)
    return types.SimpleNamespace(
        params=weights, cfg=bridge_decode_brumby.transformer_config(m, 64, jnp.float32),
        check=_teacher_forced(lambda seq, at: brumby_decoder.logits(weights, m, seq, at=at)),
        serve=dict(max_seq=64),
    )


KINDS = {"dense": _dense, "cca_experts": _zaya, "retention": _brumby}


@pytest.fixture(scope="module", params=sorted(KINDS))
def kind(request):
    return KINDS[request.param]()


def _scheduler(kind, slots=SLOTS):
    return DecodeScheduler(kind.params, kind.cfg, max_slots=slots, **kind.serve)


def _prompt(kind, n, seed):
    return np.random.default_rng(seed).integers(
        0, kind.cfg.vocab_size, size=n
    ).astype(np.int32)


class _Watch:
    """What the driver thread did, step by step, seen from where
    ``kv_pager.paged_decode_step`` is called and from ``_flush_tally``
    (which follows ``decode.step.emit``).  Nothing is asserted on the
    driver's thread: a failure there would fail every waiter instead of
    the test."""

    def __init__(self, sched, monkeypatch, after_call=None, drop_copy=False):
        self.sched, self.inputs = sched, sched._inputs
        self.calls = []  # a step: uploads so far, boundary events so far, what it was fed
        self.after_emit = []  # (retired at the call, retired now, copy, mirrors)
        self.after_call = after_call
        self.drop_copy = drop_copy  # mark the copy stale before each step
        self._gates = {}  # step count -> Event set when that many steps were called
        self._lock = threading.Lock()
        self._sound = kv_pager.paged_decode_step
        self._flush = sched._flush_tally
        self._stepped = False
        monkeypatch.setattr(kv_pager, "paged_decode_step", self._step)
        monkeypatch.setattr(sched, "_flush_tally", self._flushed)

    def gate(self, n):
        with self._lock:
            ev = self._gates.setdefault(n, threading.Event())
            if n <= len(self.calls):
                ev.set()
        return ev

    def _step(self, *args, **kw):
        s = self.sched
        call = types.SimpleNamespace(
            uploads=obs.counters().get(UPLOADS, 0),
            events=s.retired + s.prefill_batches, retired=s.retired,
            active=len(s._active),
            fed=[None if a is None else np.array(a) for a in args[1:4]],
        )
        with self._lock:
            self.calls.append(call)
            for n, ev in self._gates.items():
                if n <= len(self.calls):
                    ev.set()
        self._stepped = True
        out = self._sound(*args, **kw)
        return self.after_call(out, args) if self.after_call else out

    def _flushed(self):
        if self._stepped:  # a prefill flushes too
            self._stepped = False
            i = self.inputs
            copy = i.device and [np.array(a) for a in i.device]
            mirrors = [m.copy() for m in (i.toks, i.tables, i.indices)]
            self.after_emit.append(
                (self.calls[-1].retired, self.sched.retired, copy, mirrors)
            )
            if self.drop_copy:
                i.device = None
        self._flush()


def _serve(kind, sched, watch, script=SCRIPT, seed=40):
    """The script through the scheduler: ``(prompts, replies)``."""
    prompts = [_prompt(kind, n, seed + i) for i, (n, _, _) in enumerate(script)]
    out = [None] * len(script)

    def run(i):
        _, max_new, at = script[i]
        assert watch.gate(at).wait(timeout=120)
        while out[i] is None:
            try:
                out[i] = sched.submit(prompts[i], max_new, timeout_s=120)
            except DecodeRefused:
                time.sleep(0.02)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(script))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    return prompts, out


@pytest.fixture(scope="module")
def served(kind):
    """The script served twice by one scheduler: as shipped, under watch,
    and with the copy marked stale before every step, so that each uploads."""
    mp = pytest.MonkeyPatch()
    sched = _scheduler(kind)
    try:
        watch = _Watch(sched, mp)
        prompts, resident = _serve(kind, sched, watch)
        used = sched.snapshot()["pages_used"]
        mp.undo()
        every_step = _Watch(sched, mp, drop_copy=True)
        _, uploaded = _serve(kind, sched, every_step)
    finally:
        mp.undo()
        sched.close()
    return types.SimpleNamespace(
        sched=sched, watch=watch, every_step=every_step, prompts=prompts,
        resident=resident, uploaded=uploaded, pages_used=used,
    )


def test_a_changing_population_is_served_what_uploading_every_step_serves(kind, served):
    assert [len(o) for o in served.resident] == [m for _, m, _ in SCRIPT]
    assert served.resident == served.uploaded
    assert served.pages_used == 0
    for prompt, out in zip(served.prompts, served.resident):
        kind.check(prompt, out, served.sched)
    # the second serving did upload at every step, the first did not
    ups = [c.uploads for c in served.every_step.calls]
    assert np.all(np.diff(ups) == 1)
    w = served.watch.calls
    assert w[-1].uploads - w[0].uploads < len(w) - 1
    # and some request did join a population that was running
    assert served.sched.joined_mid_run > 0


def test_after_every_emit_the_copy_equals_the_mirrors(kind, served):
    seen = served.watch.after_emit
    assert len(seen) == len(served.watch.calls)
    compared = 0
    for retired_before, retired_after, copy, mirrors in seen:
        if retired_after != retired_before:
            assert copy is None  # a retirement wrote a mirror: the copy is dropped
            continue
        assert copy is not None
        compared += 1
        for dev, host in zip(copy, mirrors):
            assert dev.dtype == host.dtype
            np.testing.assert_array_equal(dev, host)
        toks, tables, indices = mirrors
        idle = tables[:, 0] == 0
        assert not toks[idle].any() and not indices[idle].any() and not tables[idle].any()
    assert compared > len(seen) // 2
    # idle slots were among what was compared: the script's tail drains the slots
    assert any(c is not None and (m[1][:, 0] == 0).any() for _, _, c, m in seen)


def test_upload_span_counts_the_steps_whose_boundary_changed_something(kind, served):
    calls = served.watch.calls
    changed = [True] + [b.events != a.events for a, b in zip(calls, calls[1:])]
    uploaded = [True] + [b.uploads - a.uploads == 1 for a, b in zip(calls, calls[1:])]
    assert uploaded == changed
    assert 0 < sum(changed) < len(calls)
    # what a step was fed is the mirrors of that moment whichever way it came:
    # live rows one position on from the step before, the same tables
    for a, b, same in zip(calls, calls[1:], changed[1:]):
        if not same:
            np.testing.assert_array_equal(b.fed[1], a.fed[1])
            live = a.fed[1][:, 0] > 0
            np.testing.assert_array_equal(b.fed[2], a.fed[2] + live)


def test_a_fixed_population_uploads_once(kind, monkeypatch):
    sched = _scheduler(kind)
    try:
        watch = _Watch(sched, monkeypatch)
        c0 = obs.counters()
        prompts, out = _serve(kind, sched, watch, script=((7, 9, 0),))
        d = obs.counters_delta(c0)
    finally:
        sched.close()
    kind.check(prompts[0], out[0], sched)
    # 8 steps for 9 tokens: the one after the prefill uploads, the rest do not
    assert d["decode_steps"] == 8 and d[UPLOADS] == 1
    assert d["span_n.decode.step.advance"] == d["span_n.decode.step.dispatch"] == 8
    assert np.all(np.diff([c.uploads for c in watch.calls]) == 0)


def _aligned(shape, align=64):
    """Zeros whose memory starts on an ``align``-byte boundary: what the CPU
    backend takes as a device buffer WITHOUT copying it."""
    n = int(np.prod(shape)) * 4
    raw = np.zeros(n + align, np.uint8)
    off = -raw.ctypes.data % align
    return raw[off:off + n].view(np.int32).reshape(shape)


def test_writing_a_mirror_after_a_dispatch_does_not_reach_the_step(monkeypatch):
    kind = _dense()
    sched = _scheduler(kind)
    i = sched._inputs
    # mirrors an upload could alias: whether numpy's are depends on the allocator
    i.toks, i.tables, i.indices = (_aligned(m.shape) for m in (i.toks, i.tables, i.indices))
    aliased = []

    def scribble(out, args):
        mirrors = (i.toks, i.tables, i.indices)
        aliased.append(any(
            np.shares_memory(np.asarray(a), m) for a, m in zip(args[1:4], mirrors)
        ))
        kept = [m.copy() for m in mirrors]
        for m in mirrors:
            m[:] = 5
        jax.block_until_ready(out)
        for m, k in zip(mirrors, kept):
            m[:] = k
        return out

    try:
        watch = _Watch(sched, monkeypatch, after_call=scribble)
        prompts, out = _serve(kind, sched, watch, script=((6, 7, 0), (9, 5, 1)))
    finally:
        sched.close()
    assert aliased and not any(aliased)
    for p, o in zip(prompts, out):
        kind.check(p, o, sched)


def test_a_dispatch_that_fails_after_it_started_leaves_no_copy(kind, monkeypatch):
    """A step of two running streams that uploaded nothing is called and then
    raises: the pools it consumed and the copy go together, and the next
    request decodes on fresh ones."""
    sched = _scheduler(kind)
    failed = []

    def fail_once(out, args):
        calls = watch.calls
        if not failed and len(calls) > 1 and calls[-1].active == 2 and (
            calls[-1].uploads == calls[-2].uploads
        ):
            failed.append(len(calls))
            raise faults.InjectedTransient("UNAVAILABLE: after the call started")
        return out

    try:
        watch = _Watch(sched, monkeypatch, after_call=fail_once)
        errors = []

        def run(n, max_new, at):
            assert watch.gate(at).wait(timeout=120)
            try:
                sched.submit(_prompt(kind, n, 60 + n), max_new, timeout_s=120)
            except faults.InjectedTransient as e:
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=a) for a in ((5, 20, 0), (8, 12, 1))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(errors) == 2 and failed == [len(watch.calls)]
        i = sched._inputs
        assert i.device is None
        assert not i.toks.any() and not i.tables.any() and not i.indices.any()
        assert sched.snapshot()["pages_used"] == 0
        prompts, out = _serve(kind, sched, watch, script=((6, 5, 0),))
        kind.check(prompts[0], out[0], sched)
    finally:
        sched.close()


def test_a_token_altered_where_it_is_produced_is_what_the_next_step_is_fed(kind, monkeypatch):
    """As ``perfbench/tests/test_correct*.py`` alter it: the step's executable
    wrapped through the module attribute, slot 0's token changed in what it
    returns.  The altered array is the one fed back."""
    sched = _scheduler(kind, slots=1)
    vocab = kind.cfg.vocab_size
    returned = []

    def alter(out, args):
        nxt, *rest = out
        nxt = nxt.at[0].set((nxt[0] + 1) % vocab)
        returned.append(int(nxt[0]))
        return (nxt, *rest)

    try:
        watch = _Watch(sched, monkeypatch, after_call=alter)
        prompts, out = _serve(kind, sched, watch, script=((5, 7, 0),))
    finally:
        sched.close()
    calls = watch.calls
    assert len(calls) == 6 and out[0][1:] == returned
    for k in range(1, 6):
        assert calls[k].uploads == calls[0].uploads  # nothing uploaded in between
        assert int(calls[k].fed[0][0]) == returned[k - 1]


def test_params_committed_to_a_device_add_no_executable_after_the_warm_up():
    """jax keys an executable by whether each argument is committed to a
    device.  With params that are, a step's tokens come back committed and so
    does the copy advanced from them; an upload is placed the same way, so
    the step the warm-up's second two-token request compiled is the step of
    every later request, uploaded or resident."""
    kind = _dense()
    kind.params = jax.device_put(kind.params, jax.devices()[0])
    sched = _scheduler(kind)
    try:
        for seed in (1, 2):  # the warm-up a server runs: one prefill, one step
            sched.submit(_prompt(kind, 6, seed), 2, timeout_s=120)
        c0 = obs.counters()
        p = _prompt(kind, 6, 3)
        out = sched.submit(p, 8, timeout_s=120)
        d = obs.counters_delta(c0)
    finally:
        sched.close()
    kind.check(p, out, sched)
    assert d["decode_steps"] == 7 and d[UPLOADS] == 1
    assert d["backend_compiles"] == 0


def test_advance_feeds_live_rows_their_token_and_leaves_idle_rows_at_zero():
    tables = jnp.asarray(np.array([[3, 4], [0, 0], [1, 0]], np.int32))
    toks, indices = kv_pager.advance_step_inputs(
        jnp.asarray(np.array([11, 12, 13], np.int32)), tables,
        jnp.asarray(np.array([7, 0, 2], np.int32)),
    )
    assert toks.dtype == indices.dtype == jnp.int32
    np.testing.assert_array_equal(toks, [11, 0, 13])
    np.testing.assert_array_equal(indices, [8, 0, 3])
