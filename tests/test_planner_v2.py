"""Planner v2 (``ops/planner.py``, round 19): whole-query optimization
across plans, epochs, and concurrent requests.

The contracts under test:

* **fused terminal reduce** — a plan ending in ``reduce_rows``/
  ``reduce_blocks`` folds per-block partials inside the pooled chain
  dispatch (no materialized intermediate: zero D2H assembly, zero H2D
  re-staging) and stays BIT-IDENTICAL to eager materialize-then-reduce,
  chaos leg included;
* **terminal-pruned aggregate** — ``lazy.group_by(...)`` defers the one
  materialisation to ``aggregate``, which fetches only keys + reduced
  columns; the grouping itself runs the unchanged eager engine;
* **cross-plan CSE** — identical subplans execute once; concurrent
  requests rendezvous in the registry and their per-request ledgers sum
  to the global counters delta bit-for-bit; a params update or
  ``TFS_PLAN_CSE=0`` re-executes;
* **streaming window plans** — stacked per-window map stages (the
  ``StreamFrame.map_blocks`` chain and the relational pipeline's map
  stages) fuse per window under ``TFS_PLAN``, bit-identical to eager;
* **planner-aware ``iterate_epochs``** — entry cache on the FIRST
  consumption, 0 steady-state H2D bytes, 0 re-run traces;
* **plan warmup** — ``LazyFrame.warmup()`` primes the fused-chain
  bucket grid so the first planned run traces and compiles nothing;
* **per-tenant HBM budgets** — an over-budget tenant evicts its OWN
  shards first (``TFS_CACHE_TENANT_BUDGET``), other tenants' stay.

``test_pooled_*`` tests run process-isolated on the forced 8-device CPU
mesh (tests/conftest.py); the rest run in-process against the pinned
single-device baseline.
"""

import socket
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import tensorframes_tpu as tfs
from tensorframes_tpu import observability as obs
from tensorframes_tpu.doctor import doctor
from tensorframes_tpu.ops import frame_cache, planner

_EAGER = tfs.Executor()


def _frame(n=130, nb=6, seed=0, d=4):
    rng = np.random.RandomState(seed)
    return tfs.TensorFrame.from_arrays(
        {
            "x": rng.rand(n, d).astype(np.float32),
            "dead": rng.rand(n, d).astype(np.float32),
            "k": (np.arange(n) % 5).astype(np.int32),
        },
        num_blocks=nb,
    )


def _chain_programs():
    m1 = tfs.Program.wrap(
        lambda x: {"y": jnp.tanh(x) * 2.0 + x}, fetches=["y"]
    )
    m2 = tfs.Program.wrap(lambda y: {"z": y * 0.5 + 1.25}, fetches=["z"])
    return m1, m2


def _terminals(frame_fn, m1, m2, engine=None):
    """Every terminal verb over a FRESH (never-materialized) chain —
    the planned legs must take the fused-terminal paths."""
    out = {}
    pair = tfs.Program.wrap(
        lambda z_1, z_2: {"z": z_1 + 3.0 * z_2}, fetches=["z"]
    )
    red = tfs.Program.wrap(
        lambda z_input: {"z": (z_input * 1.3).sum(0)}, fetches=["z"]
    )
    agg = tfs.Program.wrap(
        lambda z_input: {"z": z_input.sum(0)}, fetches=["z"]
    )

    def chain():
        a = tfs.map_blocks(m1, frame_fn(), engine=engine)
        return tfs.map_blocks(m2, a, engine=engine)

    out["reduce_rows_tree"] = tfs.reduce_rows(
        pair, chain(), mode="tree", engine=engine
    )["z"]
    out["reduce_rows_seq"] = tfs.reduce_rows(
        pair, chain(), mode="sequential", engine=engine
    )["z"]
    out["reduce_blocks"] = tfs.reduce_blocks(red, chain(), engine=engine)[
        "z"
    ]
    g = tfs.aggregate(agg, tfs.group_by(chain(), "k"), engine=engine)
    out["aggregate_k"] = np.asarray(g.column("k").data)
    out["aggregate_z"] = np.asarray(g.column("z").data)
    return out


# ---------------------------------------------------------------------------
# fused terminal reduce/aggregate: bit-identity matrix
# ---------------------------------------------------------------------------


def test_terminal_reduce_bit_identity_serial_baseline():
    """On the pinned single-device baseline the fused terminal falls
    back to materialize-then-reduce — planned must still equal eager."""
    frame = _frame()
    m1, m2 = _chain_programs()
    eager = _terminals(lambda: frame, m1, m2, engine=_EAGER)
    planned = _terminals(lambda: frame.lazy(), m1, m2)
    assert set(eager) == set(planned)
    for k in eager:
        np.testing.assert_array_equal(eager[k], planned[k])


def test_pooled_fused_terminal_reduce_bit_identity(monkeypatch):
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    frame = _frame(n=256, nb=8)
    m1, m2 = _chain_programs()
    eager = _terminals(lambda: frame, m1, m2, engine=_EAGER)
    c0 = obs.counters()
    planned = _terminals(lambda: frame.lazy(), m1, m2)
    d = obs.counters_delta(c0)
    for k in eager:
        np.testing.assert_array_equal(eager[k], planned[k])
    # three reduce terminals folded in-dispatch + one pruned aggregate
    assert d["plan_fused_reduces"] >= 3, d


def test_pooled_fused_terminal_reduce_eliminates_round_trip(monkeypatch):
    """The headline evidence: the fused fold assembles NO intermediate
    (0 D2H bytes) and re-stages nothing, where the eager leg pays the
    full assemble-then-restage round trip."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    frame = _frame(n=256, nb=8)
    m1, m2 = _chain_programs()
    red = tfs.Program.wrap(
        lambda z_input: {"z": (z_input * 1.3).sum(0)}, fetches=["z"]
    )

    c0 = obs.counters()
    b = tfs.map_blocks(m2, tfs.map_blocks(m1, frame, engine=_EAGER),
                       engine=_EAGER)
    e_r = tfs.reduce_blocks(red, b, engine=_EAGER)["z"]
    d_eager = obs.counters_delta(c0)

    c0 = obs.counters()
    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    p_r = tfs.reduce_blocks(red, lz)["z"]
    d_planned = obs.counters_delta(c0)

    np.testing.assert_array_equal(e_r, p_r)
    # eager: pooled maps assemble y then z to host (D2H), reduce
    # re-stages z (H2D).  fused: nothing is ever assembled.
    assert d_eager["d2h_bytes_assembled"] > 0, d_eager
    assert d_planned["d2h_bytes_assembled"] == 0, d_planned
    assert (
        d_planned["h2d_bytes_staged"] < d_eager["h2d_bytes_staged"]
    ), (d_planned, d_eager)
    assert d_planned["plan_fused_reduces"] == 1, d_planned


def test_pooled_fused_terminal_reduce_chaos(monkeypatch):
    """Chaos leg: fused terminal folds stay bit-identical under
    injected transient block faults (retries re-stage + re-run the
    whole chain+fold)."""
    frame = _frame(n=160, nb=8)
    m1, m2 = _chain_programs()
    eager = _terminals(lambda: frame, m1, m2, engine=_EAGER)
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    monkeypatch.setenv("TFS_BLOCK_RETRIES", "6")
    monkeypatch.setenv("TFS_BLOCK_BACKOFF_S", "0.001")
    monkeypatch.setenv("TFS_FAULT_INJECT", "transient:rate=0.3:seed=7")
    c0 = obs.counters()
    chaotic = _terminals(lambda: frame.lazy(), m1, m2)
    d = obs.counters_delta(c0)
    for k in eager:
        np.testing.assert_array_equal(eager[k], chaotic[k])
    assert d["faults_injected"] > 0, d  # chaos actually engaged
    assert d["block_retries"] > 0, d


# ---------------------------------------------------------------------------
# terminal-pruned aggregate
# ---------------------------------------------------------------------------


def test_lazy_grouped_aggregate_is_deferred_and_identical():
    frame = _frame()
    m1, m2 = _chain_programs()
    agg = tfs.Program.wrap(
        lambda z_input: {"z": z_input.sum(0)}, fetches=["z"]
    )
    b_e = tfs.map_blocks(m2, tfs.map_blocks(m1, frame, engine=_EAGER),
                         engine=_EAGER)
    g_e = tfs.aggregate(agg, tfs.group_by(b_e, "k"), engine=_EAGER)

    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    grouped = tfs.group_by(lz, "k")
    # grouping an unmaterialized plan defers: nothing has executed yet
    assert isinstance(grouped, planner.LazyGroupedFrame)
    assert not lz.is_materialized
    g_p = tfs.aggregate(agg, grouped)
    np.testing.assert_array_equal(
        np.asarray(g_e.column("k").data), np.asarray(g_p.column("k").data)
    )
    np.testing.assert_array_equal(
        np.asarray(g_e.column("z").data), np.asarray(g_p.column("z").data)
    )


def test_lazy_grouped_repeat_aggregates_materialize_once():
    """Repeat aggregates over one grouped handle must not re-execute
    the chain per program: same read set = memoized pruned frame; a
    second DISTINCT read set flips to one full (node-memoized)
    materialisation that serves everything after."""
    frame = _frame(n=96, nb=4, seed=21)
    m1, m2 = _chain_programs()
    agg_z = tfs.Program.wrap(
        lambda z_input: {"z": z_input.sum(0)}, fetches=["z"]
    )
    agg_y = tfs.Program.wrap(
        lambda y_input: {"y": y_input.sum(0)}, fetches=["y"]
    )
    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    g = tfs.group_by(lz, "k")
    r1 = tfs.aggregate(agg_z, g)  # pruned chain execution
    c0 = obs.counters()
    r2 = tfs.aggregate(agg_z, g)  # same read set: memoized
    d = obs.counters_delta(c0)
    assert d["plan_fused_dispatches"] == 0, d
    assert d["h2d_bytes_staged"] == 0, d
    np.testing.assert_array_equal(
        np.asarray(r1.column("z").data), np.asarray(r2.column("z").data)
    )
    r3 = tfs.aggregate(agg_y, g)  # new read set: ONE full materialize
    assert lz.is_materialized  # ...memoized on the node
    c0 = obs.counters()
    tfs.aggregate(agg_y, g)  # served from the memoized frame
    d = obs.counters_delta(c0)
    assert d["plan_fused_dispatches"] == 0, d
    eager_b = tfs.map_blocks(
        m2, tfs.map_blocks(m1, frame, engine=_EAGER), engine=_EAGER
    )
    eager_y = tfs.aggregate(
        agg_y, tfs.group_by(eager_b, "k"), engine=_EAGER
    )
    np.testing.assert_array_equal(
        np.asarray(eager_y.column("y").data),
        np.asarray(r3.column("y").data),
    )


def test_lazy_grouped_frame_property_materializes():
    frame = _frame()
    m1, m2 = _chain_programs()
    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    grouped = tfs.group_by(lz, "k")
    mat = grouped.frame  # the eager escape hatch
    assert isinstance(mat, tfs.TensorFrame)
    assert "z" in mat.column_names


def test_group_by_empty_keys_raises_lazily_too():
    frame = _frame()
    m1, _ = _chain_programs()
    lz = tfs.map_blocks(m1, frame.lazy())
    with pytest.raises(tfs.ValidationError):
        tfs.group_by(lz)


def test_lazy_group_by_validates_keys_at_call_site():
    """Deferral must not move the eager call-site errors to aggregate
    time: a bad key name or a non-scalar key raises from group_by()
    whenever the chain's schema is statically known."""
    frame = _frame()
    m1, m2 = _chain_programs()
    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    with pytest.raises(tfs.SchemaError):
        tfs.group_by(lz, "typo")
    with pytest.raises(tfs.ValidationError, match="must be scalar"):
        tfs.group_by(lz, "z")  # vector-valued chain output
    assert not lz.is_materialized  # the checks executed nothing


# ---------------------------------------------------------------------------
# cross-plan CSE
# ---------------------------------------------------------------------------


def test_cse_identical_chain_executes_once():
    frame = _frame(n=96, nb=4, seed=3)
    m1, m2 = _chain_programs()
    lz1 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    z1 = np.asarray(lz1.column("z").data)
    c0 = obs.counters()
    lz2 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    z2 = np.asarray(lz2.column("z").data)
    d = obs.counters_delta(c0)
    np.testing.assert_array_equal(z1, z2)
    assert d["plan_cse_hits"] == 1, d
    assert d["program_traces"] == 0, d
    assert d["h2d_bytes_staged"] == 0, d
    # the reused segment is recorded as a CSE dispatch
    assert any(
        r.get("dispatch") == "cse" for r in lz2._last_records
    ), lz2._last_records


def test_cse_concurrent_requests_share_and_ledgers_sum_exactly():
    """Two concurrent requests build the identical subplan: it executes
    ONCE, and the per-request ledger shares sum to the global counters
    delta bit-for-bit (the coalescer's attribution contract)."""
    frame = _frame(n=192, nb=4, seed=5)
    m1, m2 = _chain_programs()
    snaps = [None, None]
    zs = [None, None]
    barrier = threading.Barrier(2)
    errs = []

    def worker(i):
        try:
            with obs.request_ledger(
                tenant=f"t{i}", method="verb"
            ) as led:
                barrier.wait()
                lz = tfs.map_blocks(
                    m2, tfs.map_blocks(m1, frame.lazy())
                )
                zs[i] = np.asarray(lz.column("z").data)
            snaps[i] = led.snapshot()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    c0 = obs.counters()
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    d = obs.counters_delta(c0)
    np.testing.assert_array_equal(zs[0], zs[1])
    assert d["plan_cse_hits"] == 1, d
    sums = {}
    for s in snaps:
        for k, v in s["counters"].items():
            sums[k] = sums.get(k, 0) + v
    for k, v in d.items():
        if k == "plan_cse_hits" or k.startswith(("span_n.", "span_ns.")):
            # the hit is noted by the consumer outside absorb; the span
            # table feeds no request ledger
            continue
        assert sums.get(k, 0) == v, (
            f"ledger shares sum {sums.get(k, 0)} != global delta {v} "
            f"for {k}"
        )


def test_reduce_terminal_cse_concurrent_requests_execute_once(monkeypatch):
    """Round 22, the round-19 residual closed: two concurrent requests
    ending in the SAME fused terminal reduce rendezvous through the CSE
    registry — ONE fused execution, exact absorbed ledger shares, like
    map-terminal plans."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    m1, m2 = _chain_programs()
    red = tfs.Program.wrap(
        lambda z_input: {"z": (z_input * 1.3).sum(0)}, fetches=["z"]
    )
    # warm the fused executable on a throwaway frame so the concurrent
    # race below measures the rendezvous, not first-compile skew
    warm_frame = _frame(n=192, nb=4, seed=9)
    tfs.reduce_blocks(
        red, tfs.map_blocks(m2, tfs.map_blocks(m1, warm_frame.lazy()))
    )

    frame = _frame(n=192, nb=4, seed=10)
    b = tfs.map_blocks(m2, tfs.map_blocks(m1, frame, engine=_EAGER),
                       engine=_EAGER)
    ref = tfs.reduce_blocks(red, b, engine=_EAGER)["z"]

    snaps = [None, None]
    zs = [None, None]
    barrier = threading.Barrier(2)
    errs = []

    def worker(i):
        try:
            with obs.request_ledger(tenant=f"t{i}", method="verb") as led:
                barrier.wait()
                lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
                zs[i] = tfs.reduce_blocks(red, lz)["z"]
            snaps[i] = led.snapshot()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    c0 = obs.counters()
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    d = obs.counters_delta(c0)
    np.testing.assert_array_equal(np.asarray(zs[0]), np.asarray(zs[1]))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(zs[0]))
    assert d["plan_cse_hits"] == 1, d
    assert d["plan_fused_reduces"] == 1, d  # the fold ran ONCE
    sums = {}
    for s in snaps:
        for k, v in s["counters"].items():
            sums[k] = sums.get(k, 0) + v
    for k, v in d.items():
        if k == "plan_cse_hits" or k.startswith(("span_n.", "span_ns.")):
            # the hit is noted by the consumer outside absorb; the span
            # table feeds no request ledger
            continue
        assert sums.get(k, 0) == v, (
            f"ledger shares sum {sums.get(k, 0)} != global delta {v} "
            f"for {k}"
        )


def test_reduce_terminal_cse_registry_hit_when_result_held(monkeypatch):
    """A later identical reduce whose earlier result is still alive is
    served from the registry: same object back, zero traces, zero
    staging — and the reuse is visible in the plan records."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    frame = _frame(n=96, nb=4, seed=11)
    m1, m2 = _chain_programs()
    red = tfs.Program.wrap(
        lambda z_input: {"z": (z_input * 1.3).sum(0)}, fetches=["z"]
    )
    lz1 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    r1 = tfs.reduce_blocks(red, lz1)  # HOLD the result dict
    c0 = obs.counters()
    lz2 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    r2 = tfs.reduce_blocks(red, lz2)
    d = obs.counters_delta(c0)
    assert r2 is r1, "registry hit must return the cached result"
    np.testing.assert_array_equal(np.asarray(r1["z"]), np.asarray(r2["z"]))
    assert d["plan_cse_hits"] == 1, d
    assert d["program_traces"] == 0, d
    assert d["h2d_bytes_staged"] == 0, d
    assert any(
        r.get("dispatch") == "cse" and r.get("terminal") == "reduce_blocks"
        for r in lz2._last_records
    ), lz2._last_records


def test_bridge_concurrent_requests_cse_execute_once(monkeypatch):
    """Acceptance (b), real bridge path: two concurrent verb RPCs on
    the SAME registered frame with the warm-pool-shared program execute
    the subplan once under ``TFS_PLAN=1`` — ``plan_cse_hits`` moves and
    the two requests' attribution ledgers sum to the global counters
    delta bit-for-bit."""
    from tensorframes_tpu.bridge import BridgeClient, serve
    from tensorframes_tpu.bridge.client import RemoteFrame
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.const("three", np.float64(3.0))
    g.op("Add", "z", ["x", "three"])
    graph = g.to_bytes()

    monkeypatch.setenv("TFS_PLAN", "1")
    srv = serve(max_inflight=0, coalesce_us=0, warm_spec="8")
    xs = np.arange(48.0)
    try:
        with BridgeClient(*srv.address, tenant="seed") as c0:
            f = c0.create_frame({"x": xs}, num_blocks=2).analyze()
            token, fid, schema = c0.session_token, f.frame_id, f.schema

            # reattach two more clients to the seed client's session
            # BEFORE the measured window: the hello handshake binds the
            # session at connect time, so adopt the token and force a
            # reconnect (shutdown, not close — makefile refs keep a
            # closed socket's fd usable, which would let the next call
            # ride the OLD connection and its old session), then ping
            # so the reconnect's retry noise stays out of the window
            clients = []
            for i in range(2):
                c = BridgeClient(*srv.address, tenant=f"t{i}")
                c.session_token = token
                with c._lock:
                    c._sock.shutdown(socket.SHUT_RDWR)
                c.call("ping")
                clients.append(c)

            setup = threading.Barrier(3)
            go = threading.Barrier(3)
            fired = threading.Barrier(3)
            cids = [None, None]
            atts = [None, None]
            outs = [None, None]
            errs = []

            def worker(i):
                try:
                    c = clients[i]
                    rf = RemoteFrame(c, fid, schema)
                    setup.wait()
                    go.wait()  # main snapshots between these
                    # ONLY the maps run inside the measured window; the
                    # collect/attribution reads land after `fired`
                    out = rf.map_blocks(graph, fetches=["z"])
                    cids[i] = c.last_correlation_id
                    fired.wait()
                    outs[i] = out.collect()["z"]
                    atts[i] = c.attribution(cids[i])["ledger"]
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)
                    for b in (setup, go, fired):
                        b.abort()

            ts = [
                threading.Thread(target=worker, args=(i,))
                for i in range(2)
            ]
            for t in ts:
                t.start()
            setup.wait()
            before = obs.counters()
            go.wait()
            fired.wait()
            after = obs.counters()
            for t in ts:
                t.join()
            delta = obs.counters_delta(before, after)
            for c in clients:
                c.close()
            if errs:
                raise errs[0]
        np.testing.assert_array_equal(outs[0], xs + 3.0)
        np.testing.assert_array_equal(outs[1], xs + 3.0)
        assert delta["plan_cse_hits"] >= 1, delta
        summed = {}
        for led in atts:
            assert led is not None
            for k, v in led["counters"].items():
                summed[k] = summed.get(k, 0) + v
        for k, v in delta.items():
            if k in ("plan_cse_hits", "bridge_verbs_executed") or (
                k.startswith(("span_n.", "span_ns."))
            ):
                # noted by the server/consumer outside the absorbed
                # dispatch delta; the span table feeds no request ledger
                continue
            assert summed.get(k, 0) == v, (
                f"ledger shares sum {summed.get(k, 0)} != global "
                f"delta {v} for {k}"
            )
    finally:
        srv.close(drain_s=1.0)


def test_cse_params_update_invalidates_signature():
    frame = _frame(n=64, nb=2, seed=7)
    m = tfs.Program.wrap(
        lambda x, w: {"z": x * w}, fetches=["z"],
        params={"w": np.float32(2.0)},
    )
    lz1 = tfs.map_blocks(m, frame.lazy())
    z1 = np.asarray(lz1.column("z").data)
    m.update_params(w=np.float32(3.0))
    c0 = obs.counters()
    lz2 = tfs.map_blocks(m, frame.lazy())
    z2 = np.asarray(lz2.column("z").data)
    d = obs.counters_delta(c0)
    assert d["plan_cse_hits"] == 0, d  # live params changed: no reuse
    np.testing.assert_array_equal(z2, z1 * 1.5)


def test_cse_disabled_by_knob(monkeypatch):
    monkeypatch.setenv("TFS_PLAN_CSE", "0")
    frame = _frame(n=64, nb=2, seed=11)
    m1, m2 = _chain_programs()
    lz1 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    z1 = np.asarray(lz1.column("z").data)
    c0 = obs.counters()
    lz2 = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    z2 = np.asarray(lz2.column("z").data)
    d = obs.counters_delta(c0)
    np.testing.assert_array_equal(z1, z2)
    assert d["plan_cse_hits"] == 0, d


def test_doctor_cse_miss_rule():
    diags = doctor(
        counters={"plan_cse_hits": 0},
        latency={},
        spans=[],
        tenants={},
        shuffles=[],
        plans=[{"executions": 9, "hits": 0, "stages": 2}],
    )
    codes = [d["code"] for d in diags]
    assert "cse_miss" in codes, diags
    d = next(d for d in diags if d["code"] == "cse_miss")
    assert d["knob"] == "TFS_PLAN_CSE"
    assert d["evidence"]["executions"] == 9
    # a shared signature (hits > 0) is healthy: no diagnostic
    healthy = doctor(
        counters={"plan_cse_hits": 5},
        latency={},
        spans=[],
        tenants={},
        shuffles=[],
        plans=[{"executions": 9, "hits": 5, "stages": 2}],
    )
    assert "cse_miss" not in [d["code"] for d in healthy], healthy


# ---------------------------------------------------------------------------
# streaming window plans
# ---------------------------------------------------------------------------


def _window_stream(n=1000, window=250, seed=0):
    import pyarrow as pa

    from tensorframes_tpu.streaming import from_batches

    rng = np.random.RandomState(seed)
    x = rng.rand(n).astype(np.float64)
    tbl = pa.table({"x": x, "dead": x * 2.0})
    return from_batches(
        lambda: iter(tbl.to_batches(max_chunksize=100)),
        window_rows=window,
        label="t",
    )


def test_stream_map_chain_planned_bit_identical(monkeypatch):
    m1 = tfs.Program.wrap(lambda x: {"y": x + 3.0}, fetches=["y"])
    m2 = tfs.Program.wrap(lambda y: {"z": y * 0.5}, fetches=["z"])
    monkeypatch.setenv("TFS_PLAN", "0")
    eager = [
        np.asarray(wf.column("z").data)
        for wf in _window_stream().map_blocks(m1).map_blocks(m2).windows()
    ]
    monkeypatch.setenv("TFS_PLAN", "1")
    c0 = obs.counters()
    planned = [
        np.asarray(wf.column("z").data)
        for wf in _window_stream().map_blocks(m1).map_blocks(m2).windows()
    ]
    d = obs.counters_delta(c0)
    assert len(eager) == len(planned) == 4
    for a, b in zip(eager, planned):
        np.testing.assert_array_equal(a, b)
    assert d["plan_stream_windows"] == 4, d
    assert d["plan_fused_dispatches"] == 4, d


def test_stream_single_stage_stays_eager(monkeypatch):
    """A one-stage chain has nothing to fuse: no per-window plan
    overhead, same results."""
    m1 = tfs.Program.wrap(lambda x: {"y": x + 3.0}, fetches=["y"])
    monkeypatch.setenv("TFS_PLAN", "1")
    c0 = obs.counters()
    outs = [
        np.asarray(wf.column("y").data)
        for wf in _window_stream().map_blocks(m1).windows()
    ]
    d = obs.counters_delta(c0)
    assert len(outs) == 4
    assert d["plan_stream_windows"] == 0, d


def test_relational_pipeline_map_stages_planned(monkeypatch, tmp_path):
    """The bridge pipeline's stacked map stages route through per-window
    plans under TFS_PLAN — results identical to the eager run."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from tensorframes_tpu.relational.pipeline import run_stream_pipeline
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    rng = np.random.RandomState(0)
    x = rng.rand(600).astype(np.float64)
    pq.write_table(pa.table({"x": x}), tmp_path / "in.parquet")

    def graph(op, out, const):
        g = GraphBuilder()
        g.placeholder("x" if out == "y" else "y", "float64", [-1])
        g.const("c", np.float64(const))
        g.op(op, out, [("x" if out == "y" else "y"), "c"])
        return g.to_bytes()

    stages = [
        {"op": "map_blocks", "graph": graph("Add", "y", 3.0),
         "fetches": ["y"]},
        {"op": "map_blocks", "graph": graph("Mul", "z", 0.5),
         "fetches": ["z"]},
    ]
    src = {"parquet": str(tmp_path / "in.parquet"), "window_rows": 200}
    monkeypatch.setenv("TFS_PLAN", "0")
    eager = run_stream_pipeline(src, stages, {"kind": "frame"})
    monkeypatch.setenv("TFS_PLAN", "1")
    c0 = obs.counters()
    planned = run_stream_pipeline(src, stages, {"kind": "frame"})
    d = obs.counters_delta(c0)
    np.testing.assert_array_equal(
        np.asarray(eager["frame"].column("z").data),
        np.asarray(planned["frame"].column("z").data),
    )
    assert d["plan_stream_windows"] >= 3, d
    # per-window ledgers still sum exactly (nested attribution intact)
    assert planned["rows"] == eager["rows"] == 600


# ---------------------------------------------------------------------------
# planner-aware multi-epoch iterate
# ---------------------------------------------------------------------------


def test_pooled_iterate_epochs_steady_state_fences(monkeypatch):
    """Acceptance (c): planned multi-epoch iterate — entry cache on the
    FIRST consumption, 0 steady-state H2D bytes, 0 re-run traces,
    bit-stable results."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    frame = _frame(n=256, nb=8)
    m1, m2 = _chain_programs()
    red = tfs.Program.wrap(
        lambda z_input: {"z": (z_input * 1.3).sum(0)}, fetches=["z"]
    )
    eager_b = tfs.map_blocks(
        m2, tfs.map_blocks(m1, frame, engine=_EAGER), engine=_EAGER
    )
    eager_r = tfs.reduce_blocks(red, eager_b, engine=_EAGER)["z"]

    deltas = []

    def step(root, e):
        c0 = obs.counters()
        b = tfs.map_blocks(m2, tfs.map_blocks(m1, root))
        r = tfs.reduce_blocks(red, b)["z"]
        deltas.append(obs.counters_delta(c0))
        return r

    f2 = _frame(n=256, nb=8)
    rs = tfs.iterate_epochs(f2, step, 4)
    for r in rs:
        np.testing.assert_array_equal(r, eager_r)
    # epoch 0: the loop pre-declares >= 2 consumptions, so the entry
    # cache inserts immediately and even the FIRST fold reads shards
    assert deltas[0]["cache_shard_hits"] >= 1, deltas[0]
    assert deltas[0]["plan_cache_inserts"] == 1, deltas[0]
    for d in deltas[1:]:
        assert d["h2d_bytes_staged"] == 0, deltas
        assert d["program_traces"] == 0, deltas
        assert d["cache_shard_hits"] >= 1, deltas


def test_iterate_epochs_param_updates_flow_through():
    """Params updated between epochs change results (no stale CSE/memo
    reuse) while the executables stay warm."""
    frame = _frame(n=64, nb=2, seed=13)
    m = tfs.Program.wrap(
        lambda x, w: {"z": x * w}, fetches=["z"],
        params={"w": np.float32(1.0)},
    )
    red = tfs.Program.wrap(
        lambda z_input: {"z": z_input.sum(0)}, fetches=["z"]
    )

    def step(root, e):
        b = tfs.map_blocks(m, root)
        r = tfs.reduce_blocks(red, b)["z"]
        m.update_params(w=np.float32(float(e) + 2.0))
        return r

    rs = tfs.iterate_epochs(frame, step, 3)
    np.testing.assert_allclose(rs[1], rs[0] * 2.0, rtol=1e-6)
    np.testing.assert_allclose(rs[2], rs[0] * 3.0, rtol=1e-6)


def test_iterate_epochs_validates_inputs():
    with pytest.raises(tfs.ValidationError):
        tfs.iterate_epochs(_frame(), lambda root, e: None, 0)
    with pytest.raises(tfs.ValidationError):
        tfs.iterate_epochs("nope", lambda root, e: None, 2)


# ---------------------------------------------------------------------------
# plan warmup: the fused-chain bucket grid
# ---------------------------------------------------------------------------


def test_pooled_warm_plan_first_run_compiles_nothing(monkeypatch):
    """The round-19 warmup fix: after ``LazyFrame.warmup()`` the first
    planned dispatch is a pure cache hit — zero program traces, zero
    backend compiles — where per-stage warmup alone still compiled the
    chain's donating bucketed per-device entries."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_POOL_MIN_INTENSITY", "0")
    frame = _frame(n=250, nb=8)  # uneven tail: bucket pads engage
    m1, m2 = _chain_programs()
    lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
    primed = lz.warmup()
    assert primed, "warm_plan primed nothing"
    c0 = obs.counters()
    z = np.asarray(lz.column("z").data)
    d = obs.counters_delta(c0)
    assert d["program_traces"] == 0, d
    assert d["backend_compiles"] == 0, d
    eager = tfs.map_blocks(
        m2, tfs.map_blocks(m1, frame, engine=_EAGER), engine=_EAGER
    )
    np.testing.assert_array_equal(np.asarray(eager.column("z").data), z)


def test_warm_plan_single_stage_delegates_to_engine_warmup():
    frame = _frame(n=64, nb=2, seed=17)
    m1, _ = _chain_programs()
    lz = tfs.map_blocks(m1, frame.lazy())
    fps = planner.warm_plan(lz)
    assert isinstance(fps, list)


# ---------------------------------------------------------------------------
# per-tenant HBM cache budgets
# ---------------------------------------------------------------------------


def test_pooled_tenant_budget_evicts_own_shards_first(monkeypatch):
    """TFS_CACHE_TENANT_BUDGET: tenant A exceeding its cap evicts A's
    own least-recently-used shards; tenant B's resident shards are
    untouched.  Billing keys off the request ledger's tenant."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_HBM_BUDGET", "64M")
    n, nb, d = 256, 4, 64
    col_bytes = n * d * 4
    # cap: fits ONE frame's shards per tenant, not two
    monkeypatch.setenv("TFS_CACHE_TENANT_BUDGET", str(int(col_bytes * 1.5)))

    def cached_frame(seed, tenant):
        rng = np.random.RandomState(seed)
        f = tfs.TensorFrame.from_arrays(
            {"x": rng.rand(n, d).astype(np.float32)}, num_blocks=nb
        )
        with obs.request_ledger(tenant=tenant, method="cache"):
            return f.cache(sharded=True)

    fa1 = cached_frame(1, "tenant-a")
    fb1 = cached_frame(2, "tenant-b")
    by_tenant = frame_cache.budget_bytes_by_tenant()
    assert by_tenant.get("tenant-a", 0) == col_bytes, by_tenant
    assert by_tenant.get("tenant-b", 0) == col_bytes, by_tenant

    c0 = obs.counters()
    fa2 = cached_frame(3, "tenant-a")  # A over budget: evicts A's own
    d_ = obs.counters_delta(c0)
    by_tenant = frame_cache.budget_bytes_by_tenant()
    assert d_["cache_evictions"] >= 1, d_
    # A stays within its cap; B's shards were never touched
    assert by_tenant.get("tenant-a", 0) <= int(col_bytes * 1.5), by_tenant
    assert by_tenant.get("tenant-b", 0) == col_bytes, by_tenant
    cb = frame_cache.active_cache(fb1)
    assert cb is not None and cb.resident_blocks() == nb
    # keep the cached frames alive through the assertions
    assert fa1 is not None and fa2 is not None


def test_tenant_budget_malformed_is_uncapped(monkeypatch):
    monkeypatch.setenv("TFS_CACHE_TENANT_BUDGET", "banana")
    assert frame_cache.tenant_budget() == 0
    monkeypatch.setenv("TFS_CACHE_TENANT_BUDGET", "2M")
    assert frame_cache.tenant_budget() == 2 * 1024 * 1024


# ---------------------------------------------------------------------------
# calibration feedback
# ---------------------------------------------------------------------------


def test_pooled_calibration_feedback_overrides_static_model(monkeypatch):
    """TFS_PLAN_CALIBRATE: once both dispatch kinds have measured
    rows/s for a chain signature, the observed winner overrides the
    static intensity threshold (the recorded reason names it)."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PLAN_CALIBRATE", "1")
    monkeypatch.setenv("TFS_PLAN_CSE", "0")  # re-runs must re-execute
    monkeypatch.delenv("TFS_PLAN_POOL_MIN_INTENSITY", raising=False)
    # elementwise: cold decision is serial (transfer-bound), warm is
    # pool — after one of each, calibration has both measurements
    m1 = tfs.Program.wrap(lambda x: {"y": x + 1.0}, fetches=["y"])
    m2 = tfs.Program.wrap(lambda y: {"z": y * 2.0}, fetches=["z"])

    def run():
        # a FRESH frame per run: same chain signature (shape-keyed),
        # but no auto-cache promotion shadowing the decision layer
        frame = _frame(n=256, nb=8, d=8)
        lz = tfs.map_blocks(m2, tfs.map_blocks(m1, frame.lazy()))
        z = np.asarray(lz.column("z").data)
        rec = [r for r in lz._last_records if r["fused"] >= 2]
        return z, rec[0]

    z1, r1 = run()  # cold: serial (measured)
    z2, r2 = run()  # warm: pool (measured)
    z3, r3 = run()  # both measured: calibrated decision
    np.testing.assert_array_equal(z1, z2)
    np.testing.assert_array_equal(z1, z3)
    assert r1["dispatch"] == "serial", r1
    assert r3["reason"] in ("calibrated_pool", "calibrated_serial"), r3
    assert "calibration_rows_s" in r3, r3
    snap = planner.calibration_snapshot()
    assert any("pool" in s and "serial" in s for s in snap), snap
