"""Flight-recorder tracing, latency histograms, and metrics exposition
(round 13, docs/OBSERVABILITY.md).

The main suite runs these with ``TFS_TRACE`` pinned off (conftest);
tests drive the recorder through the API (``enable_trace`` overrides the
env).  run_tests.sh's observability tier re-runs the file with
``TFS_TRACE=1`` exported, proving the env wiring end to end.  The pooled
ordering test (``test_pooled_*``) self-isolates into a fresh
8-device interpreter via conftest.
"""

import json
import re

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import observability


@pytest.fixture(autouse=True)
def _recorder_reset():
    """Every test starts and ends with an empty ring and env-following
    enablement (the observability tier exports TFS_TRACE=1; tests that
    need a specific state pin it via enable_trace/disable_trace)."""
    _follow_env()
    yield
    _follow_env()
    observability.disable()


def _follow_env():
    observability.clear_trace()
    observability._trace_state["override"] = None
    observability._trace_state["capacity"] = None
    observability.trace_enabled()  # re-resolve: spans read the kept answer


def _frame(n=64, blocks=4):
    return tfs.analyze(
        tfs.TensorFrame.from_arrays(
            {"x": np.arange(float(n))}, num_blocks=blocks
        )
    )


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_disabled_mode_emits_zero_events():
    observability.disable_trace()
    tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame())
    assert observability.trace_depth() == 0
    assert observability.trace_drops() == 0
    assert observability.trace_events() == []


def test_trace_env_knob(monkeypatch):
    monkeypatch.setenv("TFS_TRACE", "1")
    assert observability.trace_enabled()
    monkeypatch.setenv("TFS_TRACE", "0")
    assert not observability.trace_enabled()
    # the API override wins over the env in both directions
    observability.enable_trace()
    assert observability.trace_enabled()
    observability.disable_trace()
    monkeypatch.setenv("TFS_TRACE", "1")
    assert not observability.trace_enabled()


def test_engine_events_and_verb_event():
    observability.enable_trace()
    tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(64, 4))
    evs = observability.trace_events()
    blocks = [e for e in evs if e["track"] == "serial"]
    assert [e["args"]["block"] for e in blocks] == [0, 1, 2, 3]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in blocks)
    verb_evs = [e for e in evs if e["track"] == "verbs"]
    assert verb_evs and verb_evs[-1]["name"] == "engine.map"
    assert verb_evs[-1]["args"]["verb"] == "map_blocks"
    # stable names: what varies is an argument, never part of the name
    assert {e["name"] for e in blocks} == {"engine.block"}
    # staging-lane events from the prefetch worker
    assert any(e["track"].startswith("lane/") for e in evs)


def test_ring_capacity_drop_accounting(monkeypatch):
    monkeypatch.setenv("TFS_TRACE_EVENTS", "8")
    observability.enable_trace()
    for i in range(20):
        observability.instant(f"e{i}", "t")
    assert observability.trace_depth() == 8
    assert observability.trace_drops() == 12
    # ring semantics: the SURVIVORS are the newest 8, oldest first
    names = [e["name"] for e in observability.trace_events()]
    assert names == [f"e{i}" for i in range(12, 20)]


def test_dump_trace_chrome_format(tmp_path):
    observability.enable_trace()
    tfs.map_blocks(lambda x: {"z": x * 2.0}, _frame())
    observability.instant("marker", "faults", block=3)
    path = observability.dump_trace(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    evs = data["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert "ts" in ev and "dur" in ev
    # one named pseudo-thread per track (Perfetto swim lanes)
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    names = {e["args"]["name"] for e in meta}
    assert "serial" in names and "faults" in names
    assert data["otherData"]["dropped_events"] == 0


def test_trace_events_returns_deep_copies():
    observability.enable_trace()
    observability.instant("a", "t", k=1)
    got = observability.trace_events()[0]
    got["name"] = "mutated"
    got["args"]["k"] = 999  # nested args must not alias the live ring
    fresh = observability.trace_events()[0]
    assert fresh["name"] == "a" and fresh["args"]["k"] == 1


def test_pooled_trace_event_ordering_and_drops(monkeypatch):
    """Forced-8-device pooled run (process-isolated via conftest's
    ``test_pooled_*`` rule): one dispatch track per pool device, block
    ids ascending within every track (events are emitted in global
    block order), staging events on multiple lanes, readback events on
    the device tracks — then a tiny ring proves drop accounting under
    the same run."""
    import jax

    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PREFETCH_BLOCKS", "2")
    n_dev = len(jax.local_devices())
    assert n_dev >= 2, "isolated child must see the forced 8-device mesh"
    observability.enable_trace()
    frame = _frame(256, 16)
    tfs.map_blocks(lambda x: {"z": x + 1.0}, frame)
    evs = observability.trace_events()
    dispatch = {}
    for e in evs:
        if e["track"].startswith("device/") and e["name"] == "engine.block":
            dispatch.setdefault(e["track"], []).append(e["args"]["block"])
    assert len(dispatch) == n_dev, dispatch.keys()
    for track, blocks in dispatch.items():
        assert blocks == sorted(blocks), (track, blocks)
    assert sorted(b for bs in dispatch.values() for b in bs) == list(
        range(16)
    )
    lanes = {e["track"] for e in evs if e["track"].startswith("lane/")}
    assert len(lanes) >= 2, lanes
    assert any(
        e["name"] == "pool.readback"
        for e in evs
        if e["track"].startswith("device/")
    )
    # capacity-drop accounting under the same pooled run
    observability.clear_trace()
    observability.enable_trace(capacity=4)
    tfs.map_blocks(lambda x: {"z": x + 2.0}, frame)
    assert observability.trace_depth() == 4
    assert observability.trace_drops() > 0


# ---------------------------------------------------------------------------
# latency histograms
# ---------------------------------------------------------------------------


def test_histogram_bucket_math():
    h = observability._LatencyHisto()
    bounds = observability._LATENCY_BOUNDS
    # inclusive upper bounds (Prometheus ``le`` semantics): a value
    # exactly at a bound lands in THAT bucket, not the next
    h.record(bounds[10])
    assert h.counts[10] == 1
    h.record(bounds[10] * 1.0001)
    assert h.counts[11] == 1
    # under the lowest bound -> bucket 0; over the highest -> overflow
    h.record(bounds[0] / 4)
    assert h.counts[0] == 1
    h.record(bounds[-1] * 10)
    assert h.counts[-1] == 1
    assert h.count == 4
    assert h.max == bounds[-1] * 10
    assert h.sum == pytest.approx(
        bounds[10] * 2.0001 + bounds[0] / 4 + bounds[-1] * 10
    )


def test_histogram_quantiles_vs_exact_percentiles():
    observability.reset_latency()
    samples = [i / 1000.0 for i in range(1, 1001)]  # 1ms .. 1000ms
    for s in samples:
        observability.record_latency("verb", "_qtest", s)
    snap = observability.latency_snapshot()["verb:_qtest"]
    assert snap["count"] == 1000
    for key, q in (("p50_s", 0.50), ("p95_s", 0.95), ("p99_s", 0.99)):
        exact = float(np.percentile(samples, q * 100))
        est = snap[key]
        # log2 buckets + in-bucket linear interpolation: uniform data
        # interpolates near-exactly; 10% headroom covers edge ranks
        assert abs(est - exact) / exact < 0.10, (key, est, exact)
    observability.reset_latency()


def test_verb_latency_recorded_always_on():
    observability.reset_latency()
    tfs.map_blocks(lambda x: {"z": x - 1.0}, _frame())  # spans DISABLED
    snap = observability.latency_snapshot()
    assert snap["verb:map_blocks"]["count"] >= 1
    assert snap["verb:map_blocks"]["p99_s"] > 0


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$"
)


def test_metrics_text_parses_and_no_duplicate_families():
    observability.reset_latency()
    tfs.map_blocks(lambda x: {"z": x + 3.0}, _frame())
    # a registered gauge colliding with a counter family must NOT emit a
    # duplicate TYPE line (the counter wins) — the live-server scenario:
    # an open BridgeServer's providers coexist with the bridge counters
    collide = lambda: 1  # noqa: E731
    observability.register_gauge("tfs_bridge_shed_total", collide)
    try:
        text = observability.metrics_text()
    finally:
        observability.unregister_gauge("tfs_bridge_shed_total", collide)
    families = []
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            name, mtype = line[len("# TYPE "):].rsplit(" ", 1)
            assert mtype in ("counter", "gauge", "histogram"), line
            families.append(name)
            continue
        assert not line.startswith("#"), line
        assert _METRIC_LINE.match(line), line
        float(line.rsplit(" ", 1)[1])  # value parses
    assert len(families) == len(set(families)), "duplicate TYPE family"
    # the named gauges of the issue contract
    assert "tfs_peak_host_bytes" in families
    assert "tfs_hbm_budget_bytes" in families
    # histogram family with buckets, sum, count, and quantile gauges
    assert "tfs_verb_latency_seconds" in families
    assert 'tfs_verb_latency_seconds_bucket{verb="map_blocks",le="+Inf"}' in text
    assert 'tfs_verb_latency_seconds_count{verb="map_blocks"}' in text
    for q in ("p50", "p95", "p99"):
        assert f'q="{q}"' in text
    # every metric line's family is declared: strip _bucket/_sum/_count
    declared = set(families)
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        base = line.split("{", 1)[0].split(" ", 1)[0]
        stripped = re.sub(r"_(bucket|sum|count)$", "", base)
        assert base in declared or stripped in declared, line


def test_metrics_http_endpoint():
    import urllib.request

    httpd = observability.start_metrics_server(0)
    try:
        host, port = httpd.server_address[:2]
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ).read().decode()
        assert "tfs_program_traces_total" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://{host}:{port}/other", timeout=5
            )
    finally:
        observability.stop_metrics_server()


def test_metrics_http_endpoint_concurrent_scrapes():
    """Round-15 satellite: the TFS_METRICS_PORT endpoint under
    concurrent scrapers racing verb execution, latency recording, and
    reset_latency — every response must be 200 with a consistently
    parseable body (no duplicate TYPE families, no torn histograms),
    and no handler thread may raise."""
    import threading
    import urllib.request

    httpd = observability.start_metrics_server(0)
    errors: list = []
    stop = threading.Event()
    try:
        host, port = httpd.server_address[:2]
        url = f"http://{host}:{port}/metrics"

        def scrape(n):
            try:
                for _ in range(n):
                    body = urllib.request.urlopen(url, timeout=10).read()
                    text = body.decode()
                    fams = [
                        ln.split()[2]
                        for ln in text.splitlines()
                        if ln.startswith("# TYPE")
                    ]
                    assert len(fams) == len(set(fams)), "dup family"
                    assert "tfs_program_traces_total" in text
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        def churn():
            i = 0
            while not stop.is_set():
                observability.record_latency(
                    "verb", f"scrape_churn{i % 3}", 0.001
                )
                if i % 50 == 0:
                    observability.reset_latency()
                i += 1

        churner = threading.Thread(target=churn, daemon=True)
        churner.start()
        scrapers = [
            threading.Thread(target=scrape, args=(10,)) for _ in range(6)
        ]
        for t in scrapers:
            t.start()
        # scrape-during-verb-execution: real dispatches while scraping
        for _ in range(3):
            tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame(64, 4))
        for t in scrapers:
            t.join(60)
        stop.set()
        churner.join(10)
        assert not any(t.is_alive() for t in scrapers), "scraper hung"
        assert not errors, errors
    finally:
        stop.set()
        observability.stop_metrics_server()
        observability.reset_latency()


def test_bridge_metrics_rpc_and_health_gauges():
    from tensorframes_tpu.bridge import BridgeClient, serve

    server = serve()
    try:
        host, port = server.address[:2]
        with BridgeClient(host, port) as c:
            rf = c.create_frame({"x": np.arange(16.0)}, num_blocks=2)
            rf.collect()
            health = c.health()
            gauges = health["gauges"]
            assert {
                "live_host_bytes",
                "peak_host_bytes",
                "trace_events",
                "trace_drops",
            } <= set(gauges)
            text = c.metrics()
            assert 'tfs_bridge_latency_seconds_bucket{method="collect"' in text
            assert "tfs_bridge_inflight" in text
            # e2e method latency recorded for gated AND ungated methods
            assert 'method="metrics"' not in text  # recorded after reply
            snap = observability.latency_snapshot()
            assert snap["bridge:collect"]["count"] >= 1
            assert snap["bridge:health"]["count"] >= 1
    finally:
        server.close()


def test_bridge_unknown_methods_share_one_latency_label():
    """Client-supplied garbage method names must not mint unbounded
    histogram series — everything unknown lands under ``unknown``."""
    from tensorframes_tpu.bridge import BridgeClient, serve
    from tensorframes_tpu.bridge.client import BridgeError

    observability.reset_latency()
    server = serve()
    try:
        host, port = server.address[:2]
        with BridgeClient(host, port) as c:
            for i in range(3):
                with pytest.raises(BridgeError):
                    c.call(f"no_such_method_{i}")
        snap = observability.latency_snapshot()
        assert snap["bridge:unknown"]["count"] == 3
        assert not any(
            k.startswith("bridge:no_such_method") for k in snap
        )
    finally:
        server.close()
        observability.reset_latency()


def test_metrics_grouped_gauge_provider():
    """A provider returning a Mapping contributes one gauge per item
    (the bridge's single-snapshot admission gauges)."""
    fn = lambda: {"tfs_test_gauge_a": 1, "tfs_test_gauge_b": 2}  # noqa: E731
    observability.register_gauge("tfs_test_group", fn)
    try:
        text = observability.metrics_text()
        assert "tfs_test_gauge_a 1" in text
        assert "tfs_test_gauge_b 2" in text
        assert "tfs_test_group" not in text  # the key is a registry name
    finally:
        observability.unregister_gauge("tfs_test_group", fn)


def test_bridge_request_trace_events():
    from tensorframes_tpu.bridge import BridgeClient, serve

    observability.enable_trace()
    server = serve()
    try:
        host, port = server.address[:2]
        with BridgeClient(host, port) as c:
            rf = c.create_frame({"x": np.arange(8.0)})
            rf.collect()
        evs = observability.trace_events()
        bridge = [e for e in evs if e["track"].startswith("bridge/")]
        names = {e["name"] for e in bridge}
        assert {
            "bridge.request", "bridge.admit", "bridge.execute"
        } <= names, names
        assert {"create_frame", "collect"} <= {
            e["args"]["method"] for e in bridge
        }
    finally:
        server.close()


# ---------------------------------------------------------------------------
# satellites: span snapshot safety
# ---------------------------------------------------------------------------


def test_last_spans_deep_copies_nested_dicts():
    observability.enable()
    try:
        tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame())
        span = observability.last_spans()[-1]
        span["retrace"]["program_traces"] = 10**9
        span["phases_s"]["validate"] = -1.0
        live = observability._state["spans"][-1]
        assert live["retrace"]["program_traces"] != 10**9
        assert live["phases_s"]["validate"] != -1.0
    finally:
        observability.disable()
