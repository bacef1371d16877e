"""Spans on the profiler's clock, and the time counters taken at their
boundaries (docs/OBSERVABILITY.md, "Spans").

* the decode scheduler's time counters add up, its request-life stamps
  are ordered, and the bridge's ``decode`` reply carries them;
* a profiler session around a ``map_blocks`` and a decode request holds
  the ``tfs:`` spans, nested as documented, arguments as event stats,
  one request's spans under one ``cid``;
* with the recorder off and no session a span leaves nothing behind but
  its entry in the span table, and costs microseconds;
* the span table: exact under threads, carried by ``counters()`` /
  ``counters_delta`` / ``metrics_text``, fed by jax's compile durations,
  and equal to each of PR 26's six time counters to the nanosecond;
* every per-layer metric the benchmark reads from the counters and the
  span table loads and yields a number.
"""

import glob
import json
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import observability as obs
from tensorframes_tpu.bridge.client import BridgeClient
from tensorframes_tpu.bridge.coalescer import DecodeScheduler
from tensorframes_tpu.bridge.server import serve
from tensorframes_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq=64, dtype=jnp.float32,
)
PAGE = 8
CAP = 64

PREFILL_TOKEN_COUNTERS = (
    "decode_prefill_prompt_tokens", "decode_prefill_run_tokens",
)
DECODE_COUNTERS = (
    "decode_steps", "decode_kernel_steps", "decode_host_ns",
    "decode_step_wait_ns",
    "decode_prefill_ns", "decode_busy_ns", "decode_admitted",
    "decode_queue_wait_ns", "decode_first_tokens", "decode_ttft_ns",
    "decode_stream_ns", "decode_stream_tokens",
)
ENGINE_COUNTERS = (
    "map_verbs", "map_verb_ns", "map_head_ns", "map_tail_ns",
    "dispatch_blocks", "dispatch_host_ns", "readback_wait_ns",
)
NEW_METRICS = (
    "sched_host_ms.decode", "sched_step_wait_ms.decode",
    "prefill_stall_share.decode", "queue_wait_ms.decode",
    "ttft_ms.decode", "itl_ms.decode", "dispatch_host_ms.score",
    "verb_head_ms.score", "verb_tail_ms.score",
    "readback_wait_share.score", "prefill_pad_share.decode",
    "paged_kernel_step_share.decode", "params_resident_share.score",
    "proj_in_place_step_share.decode",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _recorder_follows_env():
    def reset():
        obs.clear_trace()
        obs._trace_state["override"] = None
        obs.trace_enabled()  # re-resolve: spans read the kept answer

    reset()
    yield
    reset()


def _jobs(spec, seed=3):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab_size, size=(n,)).astype(np.int32), mn)
        for n, mn in spec
    ]


def _frame(n=64, blocks=4):
    return tfs.analyze(
        tfs.TensorFrame.from_arrays(
            {"x": np.arange(float(n))}, num_blocks=blocks
        )
    )


def _serve_all(sched, jobs):
    """Every job through ``submit_request`` from its own thread."""
    reqs = [None] * len(jobs)
    errs = []

    def worker(i):
        try:
            reqs[i] = sched.submit_request(*jobs[i], timeout_s=120)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [
        threading.Thread(target=worker, args=(i,)) for i in range(len(jobs))
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
        assert not t.is_alive()
    if errs:
        raise errs[0]
    return reqs


# ---------------------------------------------------------------------------
# (a) the decode scheduler's counters and stamps
# ---------------------------------------------------------------------------


def test_decode_time_counters_add_up(params):
    """Over any window the loop's busy time is its host time, its wait
    for steps' tokens and its prefills; every request served is counted
    once at admission and once at its first token; one bump a step."""
    jobs = _jobs(((5, 12), (9, 20), (3, 7), (12, 16), (7, 9), (6, 1)))
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        before = obs.counters()
        reqs = _serve_all(sched, jobs)
        sched.close()  # the driver's last bump happens before it exits
        d = obs.counters_delta(before)
    finally:
        sched.close()
    parts = (
        d["decode_host_ns"] + d["decode_step_wait_ns"]
        + d["decode_prefill_ns"]
    )
    assert d["decode_busy_ns"] > 0
    assert abs(parts - d["decode_busy_ns"]) <= 0.01 * d["decode_busy_ns"]
    for key in ("decode_host_ns", "decode_step_wait_ns", "decode_prefill_ns"):
        assert d[key] > 0, key
    assert d["decode_admitted"] == d["decode_first_tokens"] == len(jobs)
    tokens = sum(r.emitted for r in reqs)
    assert tokens == sum(mn for _, mn in jobs)
    # tokens are counted with the step or prefill that made them
    assert d["decode_tokens"] == tokens
    assert d["decode_stream_tokens"] == tokens - len(jobs)
    assert d["decode_steps"] == sched.snapshot()["steps"]
    # the stamps behind the means
    assert d["decode_queue_wait_ns"] == sum(
        r.t_admit - r.t_submit for r in reqs
    )
    assert d["decode_ttft_ns"] == sum(r.t_first - r.t_submit for r in reqs)
    assert d["decode_stream_ns"] == sum(r.t_done - r.t_first for r in reqs)
    # snapshot() carries the same totals
    snap = sched.snapshot()
    for key in DECODE_COUNTERS:
        assert snap[key] == d[key], key


def test_decode_request_stamps_are_ordered(params):
    jobs = _jobs(((4, 6), (10, 3), (6, 1)), seed=5)
    sched = DecodeScheduler(
        params, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        reqs = _serve_all(sched, jobs)
    finally:
        sched.close()
    for r in reqs:
        assert 0 < r.t_submit <= r.t_admit <= r.t_first <= r.t_done
        t = r.timing()
        assert 0 <= t["queued_ms"] <= t["ttft_ms"] <= t["total_ms"]
        assert t["ttft_ms"] == (r.t_first - r.t_submit) / 1e6
    # two slots, three requests: somebody waited out a whole stream
    assert max(r.timing()["queued_ms"] for r in reqs) > 0
    one = next(r for r in reqs if r.max_new == 1)
    assert one.t_first <= one.t_done and one.emitted == 1


def test_decode_rpc_reply_carries_the_stamps(params):
    """``timing`` in the bridge reply equals what the scheduler stamped:
    the instants it emitted for the request's cid say the same, to the
    microsecond they are rounded to."""
    obs.enable_trace()
    srv = serve(
        port=0,
        decode_model=dict(
            params=params, cfg=CFG, max_slots=2, tokens_per_page=PAGE,
            max_seq=CAP,
        ),
    )
    host, port = srv.server_address
    client = BridgeClient(host=host, port=port)
    try:
        prompt = [int(t) for t in _jobs(((7, 5),), seed=8)[0][0]]
        r = client.decode(prompt, max_new=5)
        cid = client.last_correlation_id
    finally:
        client.close()
        srv.close(drain_s=2.0)
    assert r["generated"] == 5
    t = r["timing"]
    assert set(t) == {"queued_ms", "ttft_ms", "total_ms"}
    assert 0 <= t["queued_ms"] <= t["ttft_ms"] <= t["total_ms"]
    mine = [
        e for e in obs.trace_events()
        if e.get("args", {}).get("cid") == cid
    ]
    by_name = {e["name"]: e for e in mine}
    assert {
        "decode.request", "decode.admit", "decode.first_token",
        "decode.retire", "bridge.execute",
    } <= set(by_name)
    assert by_name["decode.admit"]["args"]["wait_us"] == int(
        t["queued_ms"] * 1e3
    )
    assert by_name["decode.first_token"]["args"]["ttft_us"] == int(
        t["ttft_ms"] * 1e3
    )
    assert by_name["decode.retire"]["args"]["tokens"] == 5
    assert by_name["decode.request"]["args"]["prompt_tokens"] == 7
    # the request span (handler thread) spans the whole of its life
    assert by_name["decode.request"]["dur"] >= t["total_ms"] * 1e3 * 0.99


def test_new_counters_reach_delta_and_metrics_text():
    before = obs.counters()
    d = obs.counters_delta(before)
    text = obs.metrics_text()
    for key in DECODE_COUNTERS + ENGINE_COUNTERS + PREFILL_TOKEN_COUNTERS:
        assert key in before, key
        assert d[key] == 0, key
        assert f"tfs_{key}_total " in text, key


def test_map_verb_time_counters():
    """A serial map verb: one verb, one block-loop iteration a block,
    head and tail inside the verb's time."""
    fr = _frame(64, 4)
    tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)  # compile outside
    before = obs.counters()
    tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)
    d = obs.counters_delta(before)
    assert d["map_verbs"] == 1 and d["dispatch_blocks"] == 4
    assert d["map_head_ns"] > 0 and d["map_tail_ns"] > 0
    assert d["dispatch_host_ns"] > 0
    assert (
        d["map_head_ns"] + d["dispatch_host_ns"] + d["map_tail_ns"]
        <= d["map_verb_ns"]
    )
    # an empty frame is a verb with no block loop and no tail
    empty = tfs.analyze(
        tfs.TensorFrame.from_arrays({"x": np.zeros((0,))}, num_blocks=1)
    )
    before = obs.counters()
    tfs.map_blocks(lambda x: {"z": x + 1.0}, empty)
    d = obs.counters_delta(before)
    assert d["map_verbs"] == 1 and d["dispatch_blocks"] == 0
    assert d["map_tail_ns"] == 0 and 0 < d["map_head_ns"] <= d["map_verb_ns"]


# ---------------------------------------------------------------------------
# (b) the spans in a profiler session
# ---------------------------------------------------------------------------


def _tfs_events(trace_dir):
    """{thread line: [(name, start_ns, end_ns, stats)]} of the ``tfs:``
    host events in the session's xplane."""
    files = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert files, "the profiler session wrote no xplane"
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tfs:"):
                    out.setdefault(line.name, []).append((
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats),
                    ))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_session_holds_the_tfs_spans(params, tmp_path):
    obs.disable_trace()  # the session alone decides: the ring stays off
    fr = _frame(64, 4)
    tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)  # compile outside
    sched = DecodeScheduler(
        params, CFG, max_slots=2, tokens_per_page=PAGE, max_seq=CAP
    )
    prompt, max_new = _jobs(((6, 4),), seed=9)[0]
    try:
        sched.submit(prompt, 2)  # compile outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)
            with obs.request_ledger() as led:
                out = sched.submit(prompt, max_new)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.close()
    assert len(out) == max_new and obs.trace_depth() == 0
    lines = _tfs_events(str(tmp_path))
    flat = [e for evs in lines.values() for e in evs]
    names = {e[0] for e in flat}
    assert {
        "tfs:engine.map", "tfs:engine.head", "tfs:engine.block",
        "tfs:engine.tail", "tfs:decode.request", "tfs:decode.boundary",
        "tfs:decode.prefill", "tfs:decode.prefill.wait", "tfs:decode.step",
        "tfs:decode.step.dispatch", "tfs:decode.step.wait",
        "tfs:decode.step.emit", "tfs:decode.admit",
        "tfs:decode.first_token", "tfs:decode.retire",
    } <= names, names

    # the map verb, on the calling thread's line: head, four blocks and
    # tail inside engine.map, in that order, arguments as stats
    line = next(
        evs for evs in lines.values()
        if any(e[0] == "tfs:engine.map" for e in evs)
    )
    verb = next(e for e in line if e[0] == "tfs:engine.map")
    assert verb[3]["verb"] == "map_blocks"
    assert verb[3]["rows"] == 64 and verb[3]["blocks"] == 4
    inner = sorted(
        (
            e for e in line
            if e[0] in (
                "tfs:engine.head", "tfs:engine.block", "tfs:engine.tail"
            )
        ),
        key=lambda e: e[1],
    )
    assert [e[0] for e in inner] == (
        ["tfs:engine.head"] + ["tfs:engine.block"] * 4 + ["tfs:engine.tail"]
    )
    assert all(_inside(e, verb) for e in inner)
    blocks = [e for e in inner if e[0] == "tfs:engine.block"]
    assert [b[3]["block"] for b in blocks] == [0, 1, 2, 3]
    assert all(
        b[3]["rows"] == 16 and b[3]["verb"] == "map_blocks" for b in blocks
    )

    # the decode driver's line: each step holds its three children, the
    # prefill its wait
    drv = next(
        evs for evs in lines.values()
        if any(e[0] == "tfs:decode.step" for e in evs)
    )
    steps = [e for e in drv if e[0] == "tfs:decode.step"]
    assert len(steps) == max_new - 1  # the first token is the prefill's
    for child in ("dispatch", "wait", "emit"):
        kids = [e for e in drv if e[0] == f"tfs:decode.step.{child}"]
        assert len(kids) == len(steps)
        assert all(any(_inside(k, s) for s in steps) for k in kids)
    # one dispatch for the one request, at its own prompt's bucket
    prefill = [e for e in drv if e[0] == "tfs:decode.prefill"]
    assert len(prefill) == 1 and prefill[0][3]["admitted"] == 1
    assert prefill[0][3]["bucket"] == 8 and prefill[0][3]["slots"] in (0, 1)
    wait = next(e for e in drv if e[0] == "tfs:decode.prefill.wait")
    assert _inside(wait, prefill[0])
    assert all("step" in s[3] and s[3]["active"] == 1 for s in steps)

    # one request, one cid: the handler thread's span and the driver's
    # three stamps
    cid = led.correlation_id
    mine = {e[0] for e in flat if e[3].get("cid") == cid}
    assert mine == {
        "tfs:decode.request", "tfs:decode.admit", "tfs:decode.first_token",
        "tfs:decode.retire",
    }
    req = next(e for e in flat if e[0] == "tfs:decode.request")
    assert req[3]["prompt_tokens"] == 6 and req[3]["max_new"] == max_new
    stamps = [
        next(e for e in drv if e[0] == n and e[3].get("cid") == cid)
        for n in (
            "tfs:decode.admit", "tfs:decode.first_token", "tfs:decode.retire"
        )
    ]
    assert all(_inside(s, req) for s in stamps)
    assert [s[1] for s in stamps] == sorted(s[1] for s in stamps)
    # the first token follows the request's own dispatch: its stamp lies
    # in that prefill's span, after the wait for the token
    assert _inside(stamps[1], prefill[0]) and wait[2] <= stamps[1][1]


# a profiler session stores an annotation's argument that parses as a
# number as a number: an all-digit cid would come back an int, and
# "123e4567..." a float (inf).  Which of the suite's runs got such an id
# was the draw of the process's random prefix, about 1 in 25 — the way
# the session test above used to fail.  The prefix is now led by "c".
@pytest.mark.parametrize("prefix", ["c1234567", "c123e456"])
def test_cid_reads_back_from_a_session_as_written(prefix, tmp_path, monkeypatch):
    obs.disable_trace()
    with pytest.raises(ValueError):
        float(obs.new_correlation_id())  # a fresh id is never a number
    assert obs._cid_prefix[0] == prefix[0]
    monkeypatch.setattr(obs, "_cid_prefix", prefix)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.request_ledger() as led:
            with obs.span("engine.block", "serial", block=3):
                pass
    finally:
        jax.profiler.stop_trace()
    assert led.correlation_id.startswith(prefix)
    (ev,) = [e for evs in _tfs_events(str(tmp_path)).values() for e in evs]
    assert ev[0] == "tfs:engine.block" and ev[3]["block"] == 3
    assert ev[3]["cid"] == led.correlation_id


# ---------------------------------------------------------------------------
# (c) off: nothing recorded, microseconds spent
# ---------------------------------------------------------------------------

# generous: an idle span measures ~1.7 us alone on the CPU dev box, of
# which its two adds into the thread's span table are ~0.15 (the budget
# on the chip's host: 300 ns over PR 26's 1,954; PERF.md has both
# readings); the bound only has to catch a span that started doing real
# work (an env read, a lock, an allocation a block) while six workers
# share the box
SPAN_OFF_BOUND_US = 50.0


def test_span_off_records_nothing_and_is_cheap():
    obs.disable_trace()
    before = obs.counters()
    with obs.span("engine.block", "serial", verb="map_blocks", block=0):
        pass
    obs.instant("engine.retry", "faults", block=0)
    sp = obs.span("engine.block", "serial", block=1)
    assert sp.end(shard_hit=True) >= 0 and sp.ns >= 0
    assert obs.trace_depth() == 0 and obs.trace_events() == []

    n, best = 2000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with obs.span(
                "engine.block", "serial",
                verb="map_blocks", block=i, rows=1024, device=0,
            ):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best * 1e6 < SPAN_OFF_BOUND_US, f"{best * 1e6:.2f} us a span"
    assert obs.trace_depth() == 0
    # off is off for the ring and the session; the table counted them all
    d = obs.counters_delta(before)
    assert d["span_n.engine.block"] == 2 + 5 * n
    assert d["span_n.engine.retry"] == 1 and d["span_ns.engine.retry"] == 0
    assert d["span_ns.engine.block"] >= sp.ns


def test_span_on_feeds_the_ring_with_stable_names():
    obs.enable_trace()
    with obs.request_ledger() as led:
        with obs.span("engine.block", "serial", block=3, rows=7):
            pass
        sp = obs.span("engine.block", "device/0", block=4)
        sp.track = "device/1"  # a redirect learned inside the span
        sp.end(device=1, shard_hit=False)
        obs.instant("engine.retry", "faults", block=3)
    evs = obs.trace_events()
    assert [e["name"] for e in evs] == [
        "engine.block", "engine.block", "engine.retry"
    ]
    assert [e["ph"] for e in evs] == ["X", "X", "i"]
    assert evs[0]["args"] == {
        "block": 3, "rows": 7, "cid": led.correlation_id
    }
    assert evs[1]["track"] == "device/1"
    assert evs[1]["args"]["shard_hit"] is False
    assert all(e["args"]["cid"] == led.correlation_id for e in evs)
    assert evs[0]["dur"] >= 0 and "dur" not in evs[2]


# ---------------------------------------------------------------------------
# (d) the span table: every span a counter, declared nowhere else
# ---------------------------------------------------------------------------


def test_span_table_is_exact_under_threads():
    """8 threads x 1,000 spans read 8,000, and the time is the sum of
    what each span returned: a lost update would break either."""
    threads, each = 8, 1000
    sums = [0] * threads
    start = threading.Barrier(threads)

    def worker(i):
        start.wait(timeout=60)
        for k in range(each):
            with obs.span("test.exact", "t", k=k) as sp:
                pass
            sums[i] += sp.ns
            if k % 100 == 0:
                obs.instant("test.exact.mark")

    before = obs.counters()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
        for t in ts:
            t.start()
        mid = obs.counters()  # a snapshot among live writers raises nothing
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert 0 <= mid.get("span_n.test.exact", 0) <= threads * each
    d = obs.counters_delta(before)
    assert d["span_n.test.exact"] == threads * each
    assert d["span_ns.test.exact"] == sum(sums)
    assert d["span_n.test.exact.mark"] == threads * 10
    assert d["span_ns.test.exact.mark"] == 0
    # the threads are gone and their tables folded: read again, same
    assert obs.counters_delta(before)["span_n.test.exact"] == threads * each
    assert all(t.is_alive() for t, _ in obs._span_tables)


# PR 26's time counters, each beside the span whose time it is by
# construction (a later benchmark issue repoints the six metric files at
# the span table and the hand bumps go: ROADMAP D9)
MAP_TWINS = {
    "dispatch_host_ns": "engine.block",
    "map_head_ns": "engine.head",
    "map_tail_ns": "engine.tail",
    "map_verb_ns": None,  # taken beside engine.map, not at its boundaries
}


@pytest.mark.parametrize("pooled", [False, True], ids=["serial", "pooled"])
def test_map_time_counters_equal_their_spans(pooled, monkeypatch):
    monkeypatch.setenv("TFS_DEVICE_POOL", "4" if pooled else "0")
    fr = _frame(64, 8)
    tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)  # compile outside
    before = obs.counters()
    out = tfs.map_blocks(lambda x: {"z": x + 1.0}, fr)
    np.asarray(out.column("z").data)
    d = obs.counters_delta(before)
    assert d["dispatch_blocks"] == d["span_n.engine.block"] == 8
    assert d["map_verbs"] == d["span_n.engine.head"] == 1
    for counter, name in MAP_TWINS.items():
        if name is not None:
            assert d[counter] == d["span_ns." + name] > 0, counter
    assert d["pool_blocks"] == (8 if pooled else 0)
    if pooled:
        assert d["span_n.pool.readback"] == 8
        assert d["readback_wait_ns"] == d["span_ns.pool.readback"] > 0
    else:
        assert d["readback_wait_ns"] == 0
        assert "span_ns.pool.readback" not in d or (
            d["span_ns.pool.readback"] == 0
        )
    # the whole verb's span holds its parts
    assert d["span_ns.engine.map"] >= (
        d["map_head_ns"] + d["dispatch_host_ns"] + d["map_tail_ns"]
    )


def test_decode_time_counters_equal_their_spans(params):
    jobs = _jobs(((5, 12), (9, 20), (3, 7), (12, 16), (7, 9), (6, 1)))
    sched = DecodeScheduler(
        params, CFG, max_slots=4, tokens_per_page=PAGE, max_seq=CAP
    )
    try:
        before = obs.counters()
        _serve_all(sched, jobs)
        sched.close()  # the driver's last bump happens before it exits
        d = obs.counters_delta(before)
    finally:
        sched.close()
    assert d["decode_step_wait_ns"] == d["span_ns.decode.step.wait"] > 0
    assert d["decode_prefill_ns"] == d["span_ns.decode.prefill"] > 0
    steps = d["decode_steps"]
    assert steps > 0
    for child in ("", ".dispatch", ".wait", ".emit"):
        assert d["span_n.decode.step" + child] == steps, child
    assert d["span_n.decode.prefill"] == d["decode_prefill_batches"]
    assert d["span_n.decode.request"] == len(jobs)
    assert d["span_n.decode.admit"] == d["decode_admitted"] == len(jobs)
    assert d["span_n.decode.first_token"] == d["decode_first_tokens"]
    # what the remainder ``decode_host_ns`` is a remainder OF: the three
    # measured parts of the host's time fit inside it
    measured = (
        d["span_ns.decode.boundary"] + d["span_ns.decode.step.dispatch"]
        + d["span_ns.decode.step.emit"]
    )
    assert 0 < measured <= d["decode_host_ns"]
    assert 0 < d["span_ns.decode.prefill.wait"] <= d["decode_prefill_ns"]


def test_compile_durations_reach_the_span_table():
    snap = obs.counters()
    for name in ("compile.frontend", "compile.backend", "compile.cache_load"):
        assert "span_n." + name in snap and "span_ns." + name in snap, name
    salt = float(time.perf_counter_ns() % 9973)  # a jaxpr nobody compiled

    inner = jax.jit(lambda v: v * salt + 1.0)

    def fresh(x):
        return inner(inner(x) * 2.0) - salt

    x = jnp.arange(7.0)
    before = obs.counters()
    t0 = time.perf_counter_ns()
    jax.jit(fresh)(x).block_until_ready()
    wall = time.perf_counter_ns() - t0
    d = obs.counters_delta(before)
    assert d["span_n.compile.backend"] == d["backend_compiles"] == 1
    assert d["span_ns.compile.backend"] > 0
    # the outer trace and the lowering: ``inner``'s trace, reported from
    # inside the outer one's, is held by the outer one's time
    assert d["span_n.compile.frontend"] == 2
    assert d["span_ns.compile.frontend"] > 0
    assert d["span_ns.compile.cache_load"] >= 0
    # one thread's time, each stretch once: the parts fit in the call
    assert (
        d["span_ns.compile.frontend"] + d["span_ns.compile.backend"]
        <= wall + 2_000_000  # jax's clock is time.time(), not ours
    )


def test_counter_is_declared_once(monkeypatch):
    """A key in ``_counters`` and nowhere else reaches ``counters()``,
    ``counters_delta`` and the Prometheus text; the gauge stays out of
    the delta."""
    monkeypatch.setitem(obs._counters, "declared_once", 0)
    before = obs.counters()
    obs._bump("declared_once", 3)
    d = obs.counters_delta(before)
    assert d["declared_once"] == 3
    assert "peak_host_bytes" not in d and "by_verb" not in d
    assert set(d) == (
        {k for k in obs._counters if k != "peak_host_bytes"}
        | {k for k in obs.counters() if k.startswith(("span_n.", "span_ns."))}
    )
    assert "tfs_declared_once_total 3\n" in obs.metrics_text()


def test_metrics_text_carries_the_span_families():
    with obs.span("test.metrics", "t"):
        pass
    obs.instant("test.metrics.mark")
    text = obs.metrics_text()
    types = re.findall(r"^# TYPE (\S+) (\S+)$", text, flags=re.M)
    assert len({name for name, _ in types}) == len(types), "duplicate TYPE"
    assert ("tfs_span_total", "counter") in types
    assert ("tfs_span_seconds_total", "counter") in types
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]\w*="[^"]*"'
        r'(,[a-zA-Z_]\w*="[^"]*")*\})? \S+$'
    )
    for line in text.splitlines():
        assert line.startswith("# TYPE ") or sample.match(line), line
    snap = obs.counters()
    n = int(re.search(
        r'^tfs_span_total\{span="test\.metrics"\} (\d+)$', text, flags=re.M
    ).group(1))
    assert 1 <= n <= snap["span_n.test.metrics"]
    secs = float(re.search(
        r'^tfs_span_seconds_total\{span="test\.metrics"\} (\S+)$',
        text, flags=re.M,
    ).group(1))
    assert 0 < secs <= snap["span_ns.test.metrics"] / 1e9
    assert re.search(
        r'^tfs_span_seconds_total\{span="test\.metrics\.mark"\} 0$',
        text, flags=re.M,
    )
    # no span key leaks into the plain counter families
    assert "tfs_span_n" not in text and "tfs_span_ns" not in text


# ---------------------------------------------------------------------------
# (e) the benchmark's metric files over the counters and the span table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_metric_file_reads_the_counters(name):
    from perfbench.run import read_metric

    spec = json.load(
        open(os.path.join(ROOT, "perfbench", "metrics", name + ".json"))
    )
    assert spec["reader"] == "ratio"
    assert spec["num"].startswith("counters.")
    assert spec["den"].startswith("counters.")
    entry = next(
        m
        for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
            "per_layer"
        ]
        if m["name"] == name
    )
    assert entry["source"] == "program_counter"
    # a synthetic window: 30 ms a step, a third of it in prefill ...
    obs_ = {
        "counters.decode_steps": 500,
        "counters.decode_kernel_steps": 500,
        "counters.decode_proj_in_place_steps": 500,
        "counters.decode_host_ns": 1_250_000_000,
        "counters.decode_step_wait_ns": 5_500_000_000,
        "counters.decode_prefill_ns": 8_250_000_000,
        "counters.decode_busy_ns": 15_000_000_000,
        "counters.decode_admitted": 40,
        "counters.decode_queue_wait_ns": 4_000_000_000,
        "counters.decode_first_tokens": 40,
        "counters.decode_ttft_ns": 14_000_000_000,
        "counters.decode_stream_ns": 150_000_000_000,
        "counters.decode_stream_tokens": 5000,
        # ... 40 prompts of 300 tokens on the 512 bucket ...
        "counters.decode_prefill_prompt_tokens": 12_000,
        "counters.decode_prefill_run_tokens": 20_480,
        # ... and 25 epochs of 8 blocks
        "counters.map_verbs": 25,
        "counters.map_verb_ns": 50_000_000_000,
        "counters.map_head_ns": 75_000_000,
        "counters.map_tail_ns": 125_000_000,
        "counters.dispatch_blocks": 200,
        "counters.param_replica_hits": 150,
        "counters.dispatch_host_ns": 100_000_000,
        "counters.readback_wait_ns": 5_000_000_000,
    }
    want = {
        "sched_host_ms.decode": 2.5,
        "sched_step_wait_ms.decode": 11.0,
        "prefill_stall_share.decode": 55.0,
        "queue_wait_ms.decode": 100.0,
        "ttft_ms.decode": 350.0,
        "itl_ms.decode": 30.0,
        "dispatch_host_ms.score": 0.5,
        "verb_head_ms.score": 3.0,
        "verb_tail_ms.score": 5.0,
        "readback_wait_share.score": 10.0,
        "prefill_pad_share.decode": 41.40625,
        "paged_kernel_step_share.decode": 100.0,
        "params_resident_share.score": 75.0,
        "proj_in_place_step_share.decode": 100.0,
    }[name]
    assert read_metric(name, obs_) == pytest.approx(want)
    # the parent commit has no such counter: nothing to read, no raise
    assert read_metric(name, {"counters.decode_tokens": 7}) is None


SPAN_METRICS = {
    # a synthetic window of 500 steps and 40 prefills ...
    "sched_boundary_ms.decode": 0.15,
    "sched_dispatch_ms.decode": 0.9,
    "sched_emit_ms.decode": 0.25,
    "prefill_wait_share.decode": 80.0,
    # ... after a set-up that compiled for 3.5 s and loaded for 1.25
    "setup_compile_frontend_s": 2.0,
    "setup_compile_backend_s": 3.5,
    "setup_cache_load_s": 1.25,
    "setup_cache_place_s.score": 0.0625,
    # PR 40: 80 of the 500 steps followed a boundary that wrote a mirror
    "step_inputs_resident_share.decode": 84.0,
}


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_benchmark_metric_file_reads_the_span_table(name):
    from perfbench.run import read_metric

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_counter"
    # appended: after the last entry the file had before the span table
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) > names.index("params_resident_share.score")
    spec = json.load(
        open(os.path.join(ROOT, "perfbench", "metrics", name + ".json"))
    )
    assert spec["reader"] in ("ratio", "value")
    obs_ = {
        "counters.decode_steps": 500,
        "counters.decode_prefill_ns": 8_250_000_000,
        "counters.span_ns.decode.boundary": 75_000_000,
        "counters.span_ns.decode.step.dispatch": 450_000_000,
        "counters.span_ns.decode.step.emit": 125_000_000,
        "counters.span_n.decode.step.upload": 80,
        "counters.span_ns.decode.prefill.wait": 6_600_000_000,
        "setup.span_ns.compile.frontend": 2_000_000_000,
        "setup.span_ns.compile.backend": 3_500_000_000,
        "setup.span_ns.compile.cache_load": 1_250_000_000,
        "setup.span_ns.cache.place": 62_500_000,
    }
    assert read_metric(name, obs_) == pytest.approx(SPAN_METRICS[name])
    # a set-up in which jax reported no such duration reads 0, not nothing
    if spec["reader"] == "value":
        assert read_metric(name, {spec["key"]: 0}) == 0.0
    # the parent commit has no span table: nothing to read, no raise
    assert read_metric(
        name, {"counters.decode_steps": 500, "counters.decode_prefill_ns": 9}
    ) is None
