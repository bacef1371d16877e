"""Request-scoped telemetry (round 15, docs/OBSERVABILITY.md):
end-to-end correlation ids, per-request cost-attribution ledgers,
``explain(analyze=True)``, and the ``tfs.doctor()`` perf advisor.

The acceptance contract under test: a bridge verb executed with a
deadline and injected transient faults yields a ledger whose
per-request h2d_bytes/retries/blocks match the process-global
counters-delta for that run bit-for-bit, with the same correlation id
on its bridge, engine, and fault trace events; ``explain(analyze=True)``
reports measured wall time and bytes for every fused group; and the
ledger-off hot path costs one contextvar read per block.

The main suite runs these with the round-15 knobs pinned off
(conftest); run_tests.sh's attribution tier re-runs the file with
``TFS_SLOW_REQUEST_MS`` / ``TFS_TRACE`` live on the forced 8-device
host, proving the env wiring end to end.
"""

import json
import logging
import threading

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import observability
from tensorframes_tpu.doctor import render as doctor_render
from tensorframes_tpu.bridge import BridgeClient, serve
from tensorframes_tpu.graphdef.builder import GraphBuilder


@pytest.fixture(autouse=True)
def _telemetry_reset():
    _follow_env()
    yield
    _follow_env()
    observability.disable()


def _follow_env():
    observability.clear_trace()
    observability._trace_state["override"] = None
    observability.trace_enabled()  # re-resolve: spans read the kept answer
    observability.reset_request_metrics()


def _frame(n=64, blocks=4, extra_cols=()):
    cols = {"x": np.arange(float(n))}
    for name in extra_cols:
        cols[name] = np.ones(n)
    return tfs.analyze(
        tfs.TensorFrame.from_arrays(cols, num_blocks=blocks)
    )


def _add3_graph():
    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.const("three", np.float64(3.0))
    g.op("Add", "z", ["x", "three"])
    return g.to_bytes()


# ---------------------------------------------------------------------------
# the ledger: counters-delta attribution
# ---------------------------------------------------------------------------


def test_ledger_matches_counters_delta_bit_for_bit():
    """The core attribution invariant: everything a request executes —
    staging-lane h2d bytes included — lands in its ledger with exactly
    the values the process-global counters moved by."""
    frame = _frame(64, 4)
    before = observability.counters()
    with observability.request_ledger(tenant="t-delta") as led:
        out = tfs.map_blocks(lambda x: {"z": x * 2.0}, frame)
        np.asarray(out.column("z").data)
        tfs.reduce_blocks(lambda x_input: {"x": x_input.sum(0)}, frame)
    delta = observability.counters_delta(before)
    snap = led.snapshot()
    for key in (
        "h2d_bytes_staged",
        "program_traces",
        "pool_blocks",
        "block_retries",
        "cache_shard_hits",
    ):
        assert snap["counters"].get(key, 0) == delta[key], key
    # serial path: every block attributed to device 0, rows add up
    assert snap["blocks_per_device"] == {"0": 8}  # 4 map + 4 reduce
    assert snap["rows"] == 128
    # per-verb latency attribution rode along
    assert snap["latency"]["verb:map_blocks"]["count"] == 1
    assert snap["latency"]["verb:reduce_blocks"]["count"] == 1
    assert snap["wall_s"] > 0


def test_ledger_nesting_keeps_outer_attribution_exact():
    frame = _frame(32, 2)
    with observability.request_ledger() as outer:
        tfs.map_blocks(lambda x: {"z": x + 1.0}, frame)
        mid = dict(outer.snapshot()["counters"])
        with observability.request_ledger() as inner:
            tfs.map_blocks(lambda x: {"w": x - 1.0}, frame)
        inner_c = inner.snapshot()["counters"]
    outer_c = outer.snapshot()["counters"]
    assert inner_c.get("h2d_bytes_staged", 0) > 0
    # the outer ledger saw BOTH phases: its total is mid + inner
    assert outer_c["h2d_bytes_staged"] == (
        mid.get("h2d_bytes_staged", 0)
        + inner_c["h2d_bytes_staged"]
    )


def test_no_active_request_is_inert():
    assert observability.current_request() is None
    # the per-block hot-path hook is a no-op without a ledger
    observability.note_request_block(3, 100)
    with observability.request_ledger() as led:
        assert observability.current_request() is led
    assert observability.current_request() is None


def test_span_and_trace_events_carry_cid():
    observability.enable_trace()
    observability.enable()
    try:
        with observability.request_ledger() as led:
            tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(32, 2))
        cid = led.correlation_id
        spans = observability.last_spans(2)
        assert any(s.get("cid") == cid for s in spans)
        evs = [
            e
            for e in observability.trace_events()
            if e.get("args", {}).get("cid") == cid
        ]
        tracks = {e["track"] for e in evs}
        assert "serial" in tracks  # engine block events
        assert "verbs" in tracks  # whole-verb event
        assert any(t.startswith("lane/") for t in tracks)  # staging lane
    finally:
        observability.disable()


# ---------------------------------------------------------------------------
# slow-request log + tenant metrics
# ---------------------------------------------------------------------------


def test_slow_request_structured_log(monkeypatch, caplog):
    monkeypatch.setenv("TFS_SLOW_REQUEST_MS", "0.0001")
    with caplog.at_level(logging.WARNING, logger="tensorframes_tpu"):
        with observability.request_ledger(
            correlation_id="slowcid123", tenant="slowpoke", method="unit"
        ):
            tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(32, 2))
    recs = [r for r in caplog.records if "slow_request" in r.getMessage()]
    assert recs, "expected a slow_request log line"
    body = json.loads(recs[-1].getMessage().split("slow_request ", 1)[1])
    assert body["correlation_id"] == "slowcid123"
    assert body["tenant"] == "slowpoke"
    assert body["counters"]["h2d_bytes_staged"] > 0
    assert body["wall_s"] > 0


def test_slow_request_log_off_by_default(monkeypatch, caplog):
    monkeypatch.setenv("TFS_SLOW_REQUEST_MS", "")
    with caplog.at_level(logging.WARNING, logger="tensorframes_tpu"):
        with observability.request_ledger():
            tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(32, 2))
    assert not [
        r for r in caplog.records if "slow_request" in r.getMessage()
    ]


def test_tenant_metrics_bounded_labels(monkeypatch):
    monkeypatch.setenv("TFS_TENANT_LABELS", "2")
    observability.reset_request_metrics()
    for tenant in ("alpha", "beta", "gamma", "delta"):
        with observability.request_ledger(tenant=tenant):
            pass
    agg = observability.request_metrics()
    assert set(agg) == {"alpha", "beta", "other"}
    assert agg["other"]["requests"] == 2  # gamma + delta folded
    text = observability.metrics_text()
    assert 'tfs_request_requests_total{tenant="alpha"} 1' in text
    assert 'tfs_request_requests_total{tenant="other"} 2' in text
    assert 'tenant="gamma"' not in text


def test_nested_ledgers_fold_once_into_tenant_metrics():
    """Only ROOT ledgers fold into tfs_request_*: a nested ledger's
    deltas already mirror into its parent, so folding both would bill
    the same bytes twice (review fix, round 15)."""
    observability.reset_request_metrics()
    with observability.request_ledger(tenant="outer"):
        with observability.request_ledger():  # e.g. explain_analyze
            tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(32, 2))
    agg = observability.request_metrics()
    assert set(agg) == {"outer"}  # the inner (default) never folded
    assert agg["outer"]["requests"] == 1
    assert agg["outer"]["h2d_bytes"] > 0


def test_idem_retry_does_not_overwrite_attribution():
    """A dedup-served retry arrives under the SAME cid as its original
    execution with a near-empty ledger; the attribution history must
    keep the executed snapshot (review fix, round 15)."""
    srv = serve()
    try:
        executed = observability.RequestLedger("samecid01")
        executed.add("bridge_verbs_executed", 1)
        executed.add("h2d_bytes_staged", 4096)
        executed.finish()
        srv._record_attribution(executed)
        replay = observability.RequestLedger("samecid01")
        replay.add("bridge_idem_hits", 1)
        replay.finish()
        srv._record_attribution(replay)
        snap = srv.attribution_snapshot("samecid01")["ledger"]
        assert snap["counters"]["h2d_bytes_staged"] == 4096
        assert snap["counters"]["bridge_verbs_executed"] == 1
        # a SECOND execution under a reused cid still updates normally
        executed2 = observability.RequestLedger("samecid01")
        executed2.add("bridge_verbs_executed", 1)
        executed2.add("h2d_bytes_staged", 8192)
        executed2.finish()
        srv._record_attribution(executed2)
        snap = srv.attribution_snapshot("samecid01")["ledger"]
        assert snap["counters"]["h2d_bytes_staged"] == 8192
    finally:
        srv.close(drain_s=0.2)


def test_request_metrics_fold_usage(monkeypatch):
    observability.reset_request_metrics()
    with observability.request_ledger(tenant="uses"):
        tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(32, 2))
    agg = observability.request_metrics()["uses"]
    assert agg["requests"] == 1
    assert agg["h2d_bytes"] > 0
    assert agg["wall_seconds"] > 0


# ---------------------------------------------------------------------------
# bridge: correlation + attribution RPC (the acceptance scenario)
# ---------------------------------------------------------------------------


def test_bridge_request_attribution_with_deadline_and_faults(monkeypatch):
    """The acceptance criterion end to end: a deadline-carrying bridge
    verb under injected transient faults produces a ledger matching the
    process counters-delta bit for bit, with ONE correlation id across
    its bridge, engine, and fault trace events."""
    monkeypatch.setenv("TFS_BLOCK_RETRIES", "2")
    monkeypatch.setenv(
        "TFS_FAULT_INJECT", "transient:block=1:attempt=0"
    )
    observability.enable_trace()
    srv = serve()
    try:
        with BridgeClient(*srv.address, tenant="acme") as client:
            rf = client.create_frame(
                {"x": np.arange(24.0)}, num_blocks=3
            ).analyze()
            before = observability.counters()
            out = rf.map_blocks(
                _add3_graph(), fetches=["z"], deadline_ms=60000
            )
            delta = observability.counters_delta(before)
            cid = client.last_correlation_id
            att = client.attribution(cid)
            assert att["found"], att
            led = att["ledger"]
            assert led["correlation_id"] == cid
            assert led["tenant"] == "acme"
            assert led["method"] == "bridge:map_blocks"
            # bit-for-bit: the request's ledger IS the counters delta
            for key in (
                "h2d_bytes_staged",
                "block_retries",
                "pool_blocks",
                "faults_injected",
                "program_traces",
            ):
                assert led["counters"].get(key, 0) == delta[key], key
            assert led["counters"]["block_retries"] == 1  # injected
            assert led["counters"]["faults_injected"] == 1
            assert sum(led["blocks_per_device"].values()) == 3
            # one correlation id across the whole request's events
            evs = [
                e
                for e in observability.trace_events()
                if e.get("args", {}).get("cid") == cid
            ]
            tracks = {e["track"] for e in evs}
            names = {e["name"] for e in evs}
            assert any(t.startswith("bridge/") for t in tracks)  # bridge
            assert "serial" in tracks or any(
                t.startswith("device/") for t in tracks
            )  # engine
            assert "faults" in tracks and "engine.retry" in names  # faults
            # the verb still computed correctly through the retry
            np.testing.assert_allclose(
                out.collect()["z"], np.arange(24.0) + 3.0
            )
    finally:
        srv.close(drain_s=0.5)


def test_bridge_attribution_unknown_cid_and_recent():
    srv = serve()
    try:
        with BridgeClient(*srv.address) as client:
            rf = client.create_frame({"x": np.arange(8.0)}, num_blocks=2)
            att = client.attribution("no-such-cid")
            assert att["found"] is False and att["ledger"] is None
            recent = client.attribution()["recent"]
            assert recent, "create_frame should have been attributed"
            assert recent[-1]["method"] == "bridge:create_frame"
            assert all("correlation_id" in r for r in recent)
            rf.release()
    finally:
        srv.close(drain_s=0.5)


def test_last_correlation_id_survives_safe_calls():
    """Safe/ungated methods (attribution itself, ping, metrics) must
    not clobber last_correlation_id — the documented lookup pattern is
    verb -> attribution(last_correlation_id), repeatably (review fix,
    round 15)."""
    srv = serve()
    try:
        with BridgeClient(*srv.address) as client:
            client.create_frame({"x": np.arange(8.0)}, num_blocks=2)
            cid = client.last_correlation_id
            assert cid is not None
            assert client.attribution(cid)["found"]
            client.ping()
            client.metrics()
            # still the verb's cid, still found — polling works
            assert client.last_correlation_id == cid
            assert client.attribution(client.last_correlation_id)["found"]
    finally:
        srv.close(drain_s=0.5)


def test_bridge_server_mints_cid_for_legacy_clients():
    """An envelope without a cid (a pre-round-15 client) still gets
    attributed — under a server-minted correlation id."""
    import socket

    from tensorframes_tpu.bridge.protocol import (
        encode_value,
        read_message,
        write_message,
    )

    srv = serve()
    try:
        sock = socket.create_connection(srv.address)
        rf, wf = sock.makefile("rb"), sock.makefile("wb")
        bins = []
        write_message(
            wf,
            {
                "id": 1,
                "method": "create_frame",
                "params": encode_value(
                    {"columns": {"x": np.arange(4.0)}, "num_blocks": 1},
                    bins,
                ),
                # no "cid", no "tenant": the legacy envelope
            },
            bins,
        )
        resp, _ = read_message(rf)
        assert "result" in resp, resp
        sock.close()
        with BridgeClient(*srv.address) as client:
            recent = client.attribution()["recent"]
        legacy = [
            r for r in recent if r["method"] == "bridge:create_frame"
        ]
        assert legacy and legacy[-1]["correlation_id"]
        assert legacy[-1]["tenant"] is None
    finally:
        srv.close(drain_s=0.5)


# ---------------------------------------------------------------------------
# explain(analyze=True)
# ---------------------------------------------------------------------------


def _lazy_chain(n=64, blocks=4):
    import jax.numpy as jnp

    frame = tfs.TensorFrame.from_arrays(
        {
            "x": np.arange(float(n * 2)).reshape(n, 2),
            "dead": np.ones(n),
        },
        num_blocks=blocks,
    )
    lz = frame.lazy()
    a = tfs.map_blocks(
        tfs.Program.wrap(lambda x: {"y": jnp.tanh(x)}, fetches=["y"]), lz
    )
    b = tfs.map_blocks(
        tfs.Program.wrap(lambda y: {"z": y + 1.0}, fetches=["z"]), a
    )
    return frame, b


def test_explain_analyze_reports_measured_wall_and_bytes():
    _, b = _lazy_chain()
    txt = tfs.explain(b, analyze=True)
    assert "== analyze (measured) ==" in txt
    # every fused group line carries measured wall time and bytes
    assert "wall=" in txt and "h2d_bytes=" in txt
    assert "dispatch=" in txt and "reason=" in txt
    # the request totals line carries the ledger's cid
    assert "request: cid=" in txt
    # the records themselves carry the measured fields
    recs = b._last_records
    assert recs
    for r in recs:
        assert r["wall_s"] > 0
        assert "h2d_bytes" in r and "traces" in r
    # the chain fused: exactly one group, h2d excludes the dead column
    fused = [r for r in recs if r.get("fused", 1) >= 2]
    assert len(fused) == 1
    assert fused[0]["h2d_bytes"] == 64 * 2 * 8  # x only, f64


def test_explain_analyze_is_consistent_with_plain_explain():
    _, b = _lazy_chain()
    analyzed = tfs.explain(b, analyze=True)
    plain = tfs.explain(b)
    # the logical-plan half renders identically after execution
    assert plain.splitlines()[0] == analyzed.splitlines()[0]
    assert "== logical plan (lazy) ==" in analyzed
    # re-analyzing an already-materialized plan keeps the last
    # execution's measurements and says so
    again = tfs.explain(b, analyze=True)
    assert "already materialized" in again
    assert "wall=" in again


def test_explain_analyze_requires_planned_frame():
    frame = _frame(16, 2)
    with pytest.raises(ValueError, match="lazy"):
        tfs.explain(frame, analyze=True)
    # plain explain still renders the schema for eager frames
    assert "x" in tfs.explain(frame)


def test_explain_analyze_executes_exactly_once():
    frame, b = _lazy_chain()
    tfs.explain(b, analyze=True)
    mat = b.frame()
    np.testing.assert_allclose(
        np.asarray(mat.column("z").data),
        np.tanh(np.arange(128.0).reshape(64, 2)) + 1.0,
    )


# ---------------------------------------------------------------------------
# tfs.doctor()
# ---------------------------------------------------------------------------


def _healthy_counters():
    c = {k: 0 for k in observability.counters() if k != "by_verb"}
    c["by_verb"] = {}
    return c


def test_doctor_healthy_process_is_quiet():
    diags = tfs.doctor(
        counters=_healthy_counters(), latency={}, spans=[]
    )
    assert diags == []
    assert "no anti-patterns" in doctor_render(diags)


def test_doctor_retrace_storm():
    c = _healthy_counters()
    c["by_verb"] = {"map_blocks": {"program_traces": 40, "backend_compiles": 40}}
    lat = {"verb:map_blocks": {"count": 50, "p50_s": 0.01, "p99_s": 0.02}}
    diags = tfs.doctor(counters=c, latency=lat, spans=[])
    codes = {d["code"] for d in diags}
    assert "retrace_storm" in codes
    d = next(d for d in diags if d["code"] == "retrace_storm")
    assert d["knob"] == "TFS_BLOCK_BUCKETS"
    assert d["evidence"]["verb"] == "map_blocks"


def test_doctor_bucket_miss_churn_and_no_cache():
    c = _healthy_counters()
    c["backend_compiles"] = 30
    diags = tfs.doctor(counters=c, latency={}, spans=[])
    d = next(d for d in diags if d["code"] == "bucket_miss_churn")
    assert d["knob"] == "TFS_COMPILE_CACHE"
    c["persistent_cache_misses"] = 25
    c["persistent_cache_hits"] = 2
    diags = tfs.doctor(counters=c, latency={}, spans=[])
    d = next(d for d in diags if d["code"] == "bucket_miss_churn")
    assert "misses" in d["summary"]


def test_doctor_cache_thrash():
    c = _healthy_counters()
    c["cache_evictions"] = 20
    c["cache_shard_hits"] = 10
    diags = tfs.doctor(counters=c, latency={}, spans=[])
    d = next(d for d in diags if d["code"] == "cache_thrash")
    assert d["knob"] == "TFS_HBM_BUDGET"
    # a healthy cache (many hits, few evictions) stays quiet
    c["cache_shard_hits"] = 1000
    assert not [
        d
        for d in tfs.doctor(counters=c, latency={}, spans=[])
        if d["code"] == "cache_thrash"
    ]


def test_doctor_low_pool_occupancy_from_spans():
    c = _healthy_counters()
    c["pool_blocks"] = 32
    spans = [
        {
            "verb": "map_blocks",
            "device_pool": {
                "devices": 4,
                "occupancy": [0.9, 0.1, 0.1, 0.1],
                "blocks_per_device": [8, 8, 8, 8],
            },
        }
    ]
    diags = tfs.doctor(counters=c, latency={}, spans=spans)
    d = next(d for d in diags if d["code"] == "low_pool_occupancy")
    assert d["knob"] == "TFS_PREFETCH_BLOCKS"


def test_doctor_low_pool_occupancy_from_ledger_skew():
    c = _healthy_counters()
    c["pool_blocks"] = 32
    ledger = {"blocks_per_device": {"0": 30, "1": 2}}
    diags = tfs.doctor(counters=c, latency={}, ledger=ledger, spans=[])
    assert any(d["code"] == "low_pool_occupancy" for d in diags)


def test_doctor_shed_burn_severity():
    c = _healthy_counters()
    c["bridge_shed"] = 80
    c["bridge_verbs_executed"] = 20
    diags = tfs.doctor(counters=c, latency={}, spans=[])
    d = next(d for d in diags if d["code"] == "shed_burn")
    assert d["severity"] == "critical"
    assert d["knob"] == "TFS_BRIDGE_MAX_INFLIGHT"
    assert diags[0]["code"] == "shed_burn"  # worst first


def test_doctor_retry_burn_and_slow_tail():
    c = _healthy_counters()
    c["block_retries"] = 50
    c["devices_quarantined"] = 1
    lat = {
        "bridge:map_blocks": {
            "count": 100, "p50_s": 0.001, "p99_s": 0.5,
        }
    }
    diags = tfs.doctor(counters=c, latency=lat, spans=[])
    codes = {d["code"] for d in diags}
    assert "retry_burn" in codes and "slow_tail" in codes
    tail = next(d for d in diags if d["code"] == "slow_tail")
    assert tail["evidence"]["series"] == "bridge:map_blocks"


def test_doctor_reads_live_state():
    # no args: reads the live process — must not raise, returns a list
    assert isinstance(tfs.doctor(), list)


# ---------------------------------------------------------------------------
# satellites: streaming window bytes, latency reset atomicity
# ---------------------------------------------------------------------------


def test_stream_window_events_carry_bytes():
    pa = pytest.importorskip("pyarrow")
    from tensorframes_tpu import streaming

    observability.enable_trace()
    n = 256
    batch = pa.record_batch({"x": pa.array(np.arange(float(n)))})
    stream = streaming.from_batches(
        lambda: iter([batch]), window_rows=64
    )
    streaming.reduce_blocks(
        lambda x_input: {"x": x_input.sum(0)}, stream, fetches=["x"]
    )
    win_evs = [
        e for e in observability.trace_events() if e["track"] == "stream"
    ]
    assert win_evs, "expected per-window stream events"
    for e in win_evs:
        assert e["args"]["bytes"] == 64 * 8  # 64 f64 rows per window
        assert e["args"]["rows"] == 64


def test_stream_sink_drain_events_carry_bytes(tmp_path):
    pytest.importorskip("pyarrow")
    from tensorframes_tpu import streaming

    observability.enable_trace()
    src = tmp_path / "in.parquet"
    tfs_frame = tfs.TensorFrame.from_arrays(
        {"x": np.arange(512.0)}, num_blocks=1
    )
    from tensorframes_tpu import io as tfs_io

    tfs_io.write_parquet(tfs_frame, str(src))
    stream = streaming.scan_parquet(str(src), window_rows=128)
    streaming.map_blocks(
        lambda x: {"z": x * 2.0},
        stream,
        sink=str(tmp_path / "out.parquet"),
    )
    win_evs = [
        e for e in observability.trace_events() if e["track"] == "stream"
    ]
    assert win_evs
    assert all(e["args"]["bytes"] > 0 for e in win_evs)


def test_reset_latency_atomic_with_concurrent_scrapes():
    """Scrapes racing reset_latency and record_latency must always see
    a consistent snapshot: parseable text, unique families, histogram
    bucket counts monotonic."""
    stop = threading.Event()
    errors = []

    def hammer_records():
        i = 0
        while not stop.is_set():
            observability.record_latency("verb", f"v{i % 4}", 0.001 * (i % 7 + 1))
            i += 1

    def hammer_resets():
        while not stop.is_set():
            observability.reset_latency()

    def hammer_scrapes():
        try:
            for _ in range(200):
                text = observability.metrics_text()
                fams = [
                    ln.split()[2]
                    for ln in text.splitlines()
                    if ln.startswith("# TYPE")
                ]
                assert len(fams) == len(set(fams)), "duplicate family"
                observability.latency_snapshot()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=hammer_records),
        threading.Thread(target=hammer_resets),
    ]
    scraper = threading.Thread(target=hammer_scrapes)
    for t in threads:
        t.start()
    scraper.start()
    scraper.join(60)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, errors
    observability.reset_latency()


def test_latency_histo_snapshot_consistent_under_recording():
    h = observability._LatencyHisto()
    stop = threading.Event()

    def rec():
        while not stop.is_set():
            h.record(0.001)

    t = threading.Thread(target=rec)
    t.start()
    try:
        for _ in range(500):
            counts, count, sum_, max_ = h.snapshot_state()
            # the four fields must be mutually consistent: bucket total
            # equals the count, and the sum implies the count
            assert sum(counts) == count
            assert (count == 0) == (sum_ == 0.0)
    finally:
        stop.set()
        t.join(10)
