"""Elastic bridge fleet (round 21): replicated servers, journal-backed
job migration, zero-downtime rolling restarts.

Four layers of evidence:

* router mechanics — rendezvous hashing's minimal-disruption property,
  flap counting + quarantine (injected fetch/clock), epoch-change
  restart detection, draining/pick/failover-budget semantics, fleet
  gauges;
* client failover — ``Draining`` replies, severed connections, and
  ``SessionLost`` each reroute a routed :class:`BridgeClient` to a
  healthy peer inside its own retry loop (thread-mode servers, fast);
* registry + janitor interplay — heartbeat files as cross-process
  liveness: an artifact owned by a pid with a fresh heartbeat is never
  reclaimed, a stale heartbeat ages out; the server writes/removes its
  own heartbeat;
* the chaos acceptance (slow-marked, run in the ``fleet`` CI tier) —
  a 3-replica process fleet survives one replica SIGKILLed mid-durable-
  job (``replica_kill`` fault) with zero failed requests, the migrated
  job's resume bit-identical to an uninterrupted run and exactly-once
  by counters; a rolling restart sheds nothing and rejoins warm (zero
  recompiles via the shared ``TFS_COMPILE_CACHE``); two live processes
  racing one ``job_id`` resolve to exactly one fence winner.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu import observability as obs
from tensorframes_tpu import recovery, relational, streaming
from tensorframes_tpu.bridge import (
    BridgeClient,
    BridgeFleet,
    FleetClient,
    FleetRouter,
    serve,
)
from tensorframes_tpu.bridge import fleet as fleet_mod
from tensorframes_tpu.bridge.client import busy_backoff_s
from tensorframes_tpu.doctor import doctor
from tensorframes_tpu.recovery import janitor

RACER = os.path.join(os.path.dirname(__file__), "_fence_racer.py")
DRIVER = os.path.join(os.path.dirname(__file__), "_recovery_driver.py")
ROWS, WINDOW, N_WINDOWS = 800, 100, 8

ADD = lambda x_1, x_2: {"x": x_1 + x_2}  # noqa: E731


# ---------------------------------------------------------------------------
# fixtures + helpers
# ---------------------------------------------------------------------------


@pytest.fixture()
def jroot(tmp_path, monkeypatch):
    root = tmp_path / "journal"
    monkeypatch.setenv("TFS_JOURNAL_DIR", str(root))
    return str(root)


@pytest.fixture()
def src_parquet(tmp_path):
    sys.path.insert(0, os.path.dirname(DRIVER))
    try:
        import _recovery_driver as drv
    finally:
        sys.path.pop(0)
    return drv.make_fixture(str(tmp_path))


def _scan(src):
    return streaming.scan_parquet(src, window_rows=WINDOW)


def _map_graph():
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.const("two", np.float64(2.0))
    g.op("Mul", "y", ["x", "two"])
    return g.to_bytes()


def _agg_graph():
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("y_input", "float64", [-1])
    g.const("axis", np.int32(0))
    g.op("Sum", "y", ["y_input", "axis"])
    return g.to_bytes()


def _pipeline_spec(src):
    return dict(
        source={"parquet": src, "window_rows": WINDOW},
        stages=[
            {"op": "map_rows", "graph": _map_graph(), "fetches": ["y"]},
            {"op": "aggregate", "keys": ["k"], "graph": _agg_graph(),
             "fetches": ["y"]},
        ],
    )


def _stub_fetch(host, port):
    return {"status": "ok", "sessions": 0,
            "replica": {"epoch": "e1", "pid": 1, "uptime_s": 1.0}}


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Rng:
    def __init__(self, v):
        self.v = v

    def random(self):
        return self.v


def _dead_pid() -> int:
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    deadline = time.monotonic() + 5
    while janitor.pid_alive(proc.pid) and time.monotonic() < deadline:
        time.sleep(0.05)
    return proc.pid


def _key_routing_to(names, target, prefix="key"):
    """A routing key whose rendezvous owner (over ``names``, all
    eligible) is ``target`` — computable without any server running."""
    for i in range(10000):
        k = f"{prefix}{i}"
        owner = max(
            names, key=lambda n: fleet_mod._rendezvous_score(n, k)
        )
        if owner == target:
            return k
    raise AssertionError(f"no key routes to {target}")


def _fleet_env(tmp_path):
    """base_env for a process fleet: the SHARED durable state, plus the
    determinism pins the recovery driver uses (cpu + x64 so children's
    f64 results are byte-comparable with the parent's references)."""
    return {
        "TFS_JOURNAL_DIR": str(tmp_path / "journal"),
        "TFS_COMPILE_CACHE": str(tmp_path / "cache"),
        "TFS_FLEET_REGISTRY": str(tmp_path / "fleet-registry"),
        "TFS_BRIDGE_PIPELINE_PATHS": str(tmp_path),
        "JAX_PLATFORMS": "cpu",
        "JAX_ENABLE_X64": "1",
        "TFS_DEVICE_POOL": "0",
        "TFS_BLOCK_RETRIES": "0",
        # children must not inherit fault leftovers from the tier env;
        # per-replica chaos rides fault_env on top of this
        "TFS_FAULT_INJECT": "",
    }


# ---------------------------------------------------------------------------
# router mechanics
# ---------------------------------------------------------------------------


def test_rendezvous_minimal_remap():
    names = [f"r{i}" for i in range(5)]
    router = FleetRouter(
        [(n, "127.0.0.1", 9000 + i) for i, n in enumerate(names)],
        health_s=60.0, fetch=_stub_fetch,
    )
    try:
        router.poll_once()
        keys = [f"key-{i}" for i in range(200)]
        owner1 = {k: router.route(k).name for k in keys}
        assert len(set(owner1.values())) == 5  # every replica owns some
        router.remove("r2")
        owner2 = {k: router.route(k).name for k in keys}
        moved = [k for k in keys if owner1[k] != owner2[k]]
        # minimal disruption: ONLY the removed replica's keys remapped
        assert moved
        assert all(owner1[k] == "r2" for k in moved)
        assert all(owner2[k] != "r2" for k in keys)
    finally:
        router.close()


def test_route_is_stable_and_degrades():
    router = FleetRouter(
        [("a", "h", 1), ("b", "h", 2)], health_s=60.0, fetch=_stub_fetch
    )
    try:
        # unpolled (nothing known-healthy) the router still routes —
        # degraded beats refusing
        first = router.route("k").name
        assert all(router.route("k").name == first for _ in range(5))
        router.remove("a")
        router.remove("b")
        with pytest.raises(RuntimeError):
            router.route("k")
    finally:
        router.close()


def test_quarantine_after_flaps_and_recovery():
    clock = _FakeClock()
    failing = set()

    def fetch(host, port):
        if port in failing:
            raise ConnectionError("down")
        return _stub_fetch(host, port)

    router = FleetRouter(
        [("a", "h", 1), ("b", "h", 2)],
        health_s=60.0, quarantine_after=2, quarantine_s=30.0,
        fetch=fetch, clock=clock,
    )
    try:
        c0 = obs.counters()
        router.poll_once()  # both healthy
        for _ in range(2):  # two down/up cycles inside the flap window
            failing.add(1)
            clock.t += 1
            router.poll_once()
            failing.discard(1)
            clock.t += 1
            router.poll_once()
        snap = router.snapshot()["replicas"]["a"]
        assert snap["flaps_recent"] >= 2
        assert snap["quarantined"] is True
        assert obs.counters_delta(c0)["fleet_quarantines"] >= 1
        # quarantined replicas own no keys...
        assert all(router.route(f"k{i}").name == "b" for i in range(20))
        # ...until the hold expires
        clock.t += 31.0
        router.poll_once()
        assert any(router.route(f"k{i}").name == "a" for i in range(20))
    finally:
        router.close()


def test_epoch_change_counts_as_flap():
    clock = _FakeClock()
    epoch = {"v": "e1"}

    def fetch(host, port):
        return {"status": "ok", "sessions": 0,
                "replica": {"epoch": epoch["v"], "pid": 1,
                            "uptime_s": 0.1}}

    router = FleetRouter(
        [("a", "h", 1)], health_s=60.0, quarantine_after=1,
        quarantine_s=5.0, fetch=fetch, clock=clock,
    )
    try:
        router.poll_once()
        assert router.snapshot()["replicas"]["a"]["flaps_recent"] == 0
        epoch["v"] = "e2"  # a restart the poller never saw go down
        clock.t += 1.0
        router.poll_once()
        snap = router.snapshot()["replicas"]["a"]
        assert snap["flaps_recent"] == 1
        assert snap["quarantined"] is True
        assert snap["epoch"] == "e2"
    finally:
        router.close()


def test_pick_budget_and_draining():
    router = FleetRouter(
        [("a", "h", 1)], health_s=60.0, fetch=_stub_fetch
    )
    try:
        router.poll_once()
        assert router.failover_budget() == 1
        assert router.pick(exclude=("h", 1)) is None
        router.add("b", "h", 2)
        router.poll_once()
        assert router.failover_budget() == 2
        assert router.pick(exclude=("h", 1)) == ("h", 2)
        # operator draining moves routed keys off the replica
        keys = [f"k{i}" for i in range(30)]
        assert any(router.route(k).name == "a" for k in keys)
        router.mark_draining("a")
        assert all(router.route(k).name == "b" for k in keys)
        router.mark_draining("a", False)
        assert any(router.route(k).name == "a" for k in keys)
        # client feedback: note_draining by address
        router.note_draining(("h", 2))
        assert router.snapshot()["replicas"]["b"]["draining"] is True
    finally:
        router.close()


def test_fleet_gauges_registered():
    router = FleetRouter(
        [("a", "h", 1), ("b", "h", 2)], health_s=60.0, fetch=_stub_fetch
    )
    try:
        router.poll_once()
        g = router._gauges()
        assert g["tfs_fleet_replicas"] == 2
        assert g["tfs_fleet_healthy"] == 2
        assert "tfs_fleet_replicas" in obs.metrics_text()
    finally:
        router.close()
    # closing unregisters the provider
    assert "tfs_fleet_replicas" not in obs.metrics_text()


# ---------------------------------------------------------------------------
# busy backoff (satellite: capped decorrelated jitter)
# ---------------------------------------------------------------------------


def test_busy_backoff_bounds():
    # the server hint is honored, jittered within [target/2, target]
    assert busy_backoff_s(200, cap_ms=1000, attempt=0, rng=_Rng(0.0)) == (
        pytest.approx(0.1)
    )
    assert busy_backoff_s(200, cap_ms=1000, attempt=0, rng=_Rng(1.0)) == (
        pytest.approx(0.2)
    )
    # attempts double the target...
    assert busy_backoff_s(200, cap_ms=1000, attempt=1, rng=_Rng(1.0)) == (
        pytest.approx(0.4)
    )
    # ...up to the cap, which also clamps a hostile server hint: a
    # malicious/buggy retry_after_ms cannot park the client for minutes
    assert busy_backoff_s(200, cap_ms=1000, attempt=9, rng=_Rng(1.0)) == (
        pytest.approx(1.0)
    )
    assert busy_backoff_s(60000, cap_ms=1000, attempt=0, rng=_Rng(1.0)) == (
        pytest.approx(1.0)
    )
    # a zero/negative hint still waits at least half a millisecond
    assert busy_backoff_s(0, cap_ms=1000, attempt=0, rng=_Rng(0.0)) > 0


# ---------------------------------------------------------------------------
# replica identity (satellite: pid + epoch + uptime in hello/health)
# ---------------------------------------------------------------------------


def test_replica_identity_in_hello_and_health(monkeypatch):
    monkeypatch.setenv("TFS_FLEET_REPLICA", "ident0")
    s = serve()
    c = BridgeClient(*s.address)
    try:
        rep = c.server_replica  # stamped from the hello reply
        assert rep["name"] == "ident0"
        assert rep["pid"] == os.getpid()
        assert rep["epoch"]
        h = c.health()["replica"]
        assert h["epoch"] == rep["epoch"]
        assert h["uptime_s"] >= 0.0
        epoch1 = rep["epoch"]
    finally:
        c.close()
        s.close(drain_s=0.2)
    # a "restarted" server = same name, NEW epoch token
    s2 = serve()
    c2 = BridgeClient(*s2.address)
    try:
        assert c2.server_replica["name"] == "ident0"
        assert c2.server_replica["epoch"] != epoch1
    finally:
        c2.close()
        s2.close(drain_s=0.2)


def test_scheduler_snapshot_carries_p99():
    s = serve()
    c = BridgeClient(*s.address)
    try:
        c.ping()
        sched = c.health()["scheduler"]
        assert "p99_ms" in sched  # None until bridge latency accrues
    finally:
        c.close()
        s.close(drain_s=0.2)


# ---------------------------------------------------------------------------
# client failover (thread-mode servers)
# ---------------------------------------------------------------------------


def _pair_with_router():
    a = serve()
    b = serve()
    router = FleetRouter(
        [("a", *a.address), ("b", *b.address)], health_s=60.0
    )
    router.poll_once()
    return a, b, router


def test_client_failover_on_dead_replica():
    a, b, router = _pair_with_router()
    c = BridgeClient(*a.address, router=router)
    try:
        assert c.ping()
        c0 = obs.counters()
        a.close(drain_s=0.1)
        # a thread server's live connections survive close(); a real
        # death severs them — do that explicitly
        with c._lock:
            c._teardown_locked()
        f = c.create_frame({"x": np.arange(4.0)})
        assert np.asarray(f.collect()["x"]).tolist() == [0, 1, 2, 3]
        assert (c._host, c._port) == b.address
        assert c.failovers == 1
        assert c.server_replica is not None
        assert obs.counters_delta(c0)["fleet_failovers"] >= 1
        # the router learned from client feedback, not a poll
        assert router.snapshot()["replicas"]["a"]["healthy"] is False
    finally:
        c.close()
        router.close()
        b.close(drain_s=0.2)


def test_client_failover_on_draining():
    a, b, router = _pair_with_router()
    c = BridgeClient(*a.address, router=router)
    try:
        assert c.ping()
        a.gate.start_draining()
        f = c.create_frame({"x": np.arange(3.0)})  # gated -> Draining
        assert np.asarray(f.collect()["x"]).tolist() == [0, 1, 2]
        assert (c._host, c._port) == b.address
        assert c.failovers == 1
        assert router.snapshot()["replicas"]["a"]["draining"] is True
    finally:
        c.close()
        router.close()
        a.close(drain_s=0.2)
        b.close(drain_s=0.2)


def test_client_failover_on_session_lost():
    a, b, router = _pair_with_router()
    c = BridgeClient(*a.address, router=router)
    try:
        assert c.ping()
        # simulate the replica restarting under the client: stale token
        # + dropped connection -> reconnect -> hello(session=stale)
        with c._lock:
            c._teardown_locked()
        c.session_token = "stale-token-from-a-previous-life"
        assert c.ping()
        assert (c._host, c._port) == b.address
        assert c.failovers == 1
        assert c.session_token  # fresh session on the peer
        # SessionLost means "alive but restarted": not marked down
        assert router.snapshot()["replicas"]["a"]["healthy"] is True
    finally:
        c.close()
        router.close()
        a.close(drain_s=0.2)
        b.close(drain_s=0.2)


def test_client_without_router_unchanged():
    s = serve()
    c = BridgeClient(*s.address)
    try:
        assert c.router is None
        assert c.failovers == 0
        assert c.ping()
    finally:
        c.close()
        s.close(drain_s=0.2)


# ---------------------------------------------------------------------------
# thread-mode fleet end to end
# ---------------------------------------------------------------------------


def test_thread_fleet_router_and_client():
    with BridgeFleet(size=2, mode="thread") as fl:
        router = fl.router(health_s=30.0)
        try:
            snap = router.snapshot()["replicas"]
            assert len(snap) == 2
            assert all(r["healthy"] for r in snap.values())
            assert all(
                r["pid"] == os.getpid() for r in snap.values()
            )
            with FleetClient(router, key="k1") as fc:
                assert fc.ping()
                f = fc.create_frame({"x": np.arange(5.0)})
                assert float(np.asarray(f.collect()["x"]).sum()) == 10.0
                assert "replica" in fc.health()
        finally:
            router.close()


def test_fleet_validation(monkeypatch):
    with pytest.raises(ValueError):
        BridgeFleet(0, mode="thread")
    with pytest.raises(ValueError):
        BridgeFleet(2, mode="carrier-pigeon")
    monkeypatch.setenv("TFS_FLEET_SIZE", "3")
    assert BridgeFleet(mode="thread").size == 3
    # thread replicas share this process's env: per-replica env is a lie
    with pytest.raises(ValueError):
        BridgeFleet(1, mode="thread", base_env={"X": "1"}).start()


def test_spawn_refuses_replicas_that_would_contend_for_the_chip(monkeypatch):
    """One process owns a chip.  When this process holds the TPU, process
    replicas whose environment names no platform — or names the TPU —
    are refused with the reason instead of racing for the device and
    timing out; replicas pinned to another platform still spawn."""
    spawned = []

    class _Proc:
        def __init__(self, argv, env=None, **kw):
            spawned.append(env["JAX_PLATFORMS"])

    monkeypatch.setattr(fleet_mod.subprocess, "Popen", _Proc)
    monkeypatch.setattr(fleet_mod, "_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="refusing to spawn.*same chip"):
        BridgeFleet(1)._spawn(fleet_mod._Replica("r0", "127.0.0.1", 0))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # what a chip host exports
    with pytest.raises(RuntimeError, match="'tpu,cpu'"):
        BridgeFleet(1)._spawn(fleet_mod._Replica("r0", "127.0.0.1", 0))
    assert spawned == []
    BridgeFleet(1, base_env={"JAX_PLATFORMS": "cpu"})._spawn(
        fleet_mod._Replica("r0", "127.0.0.1", 0)
    )
    # a launcher that holds no chip keeps today's behaviour
    monkeypatch.setattr(fleet_mod, "_backend", lambda: "")
    BridgeFleet(1)._spawn(fleet_mod._Replica("r1", "127.0.0.1", 0))
    assert spawned == ["cpu", "tpu,cpu"]


# ---------------------------------------------------------------------------
# registry + janitor interplay (satellite: fleet-liveness veto)
# ---------------------------------------------------------------------------


def test_registry_roundtrip_and_ttl(tmp_path):
    root = str(tmp_path / "reg")
    fleet_mod.registry_write(
        "a", "127.0.0.1", 7001, pid=os.getpid(), epoch="e1", root=root
    )
    assert os.getpid() in fleet_mod.registry_live_pids(root=root)
    dead = _dead_pid()
    fleet_mod.registry_write(
        "b", "127.0.0.1", 7002, pid=dead, epoch="e2", root=root
    )
    # a fresh heartbeat counts even when the local pid probe says dead
    # (the writer may live in another container/pid namespace)
    assert dead in fleet_mod.registry_live_pids(root=root)
    # ...but it ages out past the TTL
    p = os.path.join(root, "replica-b.json")
    old = time.time() - 2 * fleet_mod.REGISTRY_TTL_S
    os.utime(p, (old, old))
    assert dead not in fleet_mod.registry_live_pids(root=root)
    fleet_mod.registry_remove("a", root=root)
    assert os.getpid() not in fleet_mod.registry_live_pids(root=root)
    # garbage files are skipped, not fatal
    with open(os.path.join(root, "replica-x.json"), "w") as f:
        f.write("not json")
    assert fleet_mod.registry_live_pids(root=root) == frozenset()


def test_server_heartbeats_registry(tmp_path, monkeypatch):
    reg = tmp_path / "reg"
    monkeypatch.setenv("TFS_FLEET_REGISTRY", str(reg))
    monkeypatch.setenv("TFS_FLEET_REPLICA", "hb0")
    s = serve()
    path = reg / "replica-hb0.json"
    try:
        assert path.exists()
        doc = json.loads(path.read_text())
        assert doc["pid"] == os.getpid()
        assert doc["port"] == s.address[1]
        assert doc["epoch"]
        assert os.getpid() in fleet_mod.registry_live_pids(root=str(reg))
    finally:
        s.close(drain_s=0.2)
    # clean shutdown removes the heartbeat
    assert not path.exists()


def test_janitor_respects_fleet_registry(tmp_path, monkeypatch):
    reg = tmp_path / "reg"
    spill = tmp_path / "spill"
    spill.mkdir()
    monkeypatch.setenv("TFS_FLEET_REGISTRY", str(reg))
    dead = _dead_pid()
    (spill / f"shard-{dead}-00000.npz").write_bytes(b"x" * 64)
    # a fresh heartbeat for the locally-dead pid vetoes the reclaim:
    # the owner may be a replica in another pid namespace, mid-job
    fleet_mod.registry_write(
        "ghost", "127.0.0.1", 7009, pid=dead, epoch="e", root=str(reg)
    )
    arts = janitor.scan(spill_root=str(spill), journal_root="")
    assert arts == []
    # once the heartbeat goes stale the artifact is reclaimable again
    p = reg / "replica-ghost.json"
    old = time.time() - 2 * fleet_mod.REGISTRY_TTL_S
    os.utime(p, (old, old))
    arts = janitor.scan(spill_root=str(spill), journal_root="")
    assert [a for a in arts if a["reclaimable"]]
    got = janitor.reclaim(
        spill_root=str(spill), journal_root="", artifacts=arts
    )
    assert got["count"] == 1


# ---------------------------------------------------------------------------
# doctor rules (satellite: replica-flap + fleet-imbalance)
# ---------------------------------------------------------------------------


def _fleet_snap(replicas):
    return {
        "replicas": replicas,
        "quarantine_after": 3,
        "quarantine_s": 30.0,
        "flap_window_s": 60.0,
    }


def _rep(**kw):
    base = dict(
        host="h", port=1, healthy=True, draining=False,
        quarantined=False, pid=1, epoch="e", uptime_s=100.0,
        p99_ms=None, sessions=0, flaps_recent=0, failures=0,
    )
    base.update(kw)
    return base


def test_doctor_replica_flap_rule():
    snap = _fleet_snap(
        {"r0": _rep(flaps_recent=4, quarantined=True, healthy=False),
         "r1": _rep()}
    )
    diags = doctor(counters={}, latency={}, fleet=snap)
    flap = [d for d in diags if d["code"] == "replica_flap"]
    assert flap
    assert flap[0]["evidence"]["replica"] == "r0"
    assert flap[0]["knob"] == "TFS_FLEET_QUARANTINE_AFTER"
    # a healthy fleet fires nothing
    healthy = _fleet_snap({"r0": _rep(), "r1": _rep()})
    assert not [
        d for d in doctor(counters={}, latency={}, fleet=healthy)
        if d["code"] in ("replica_flap", "fleet_imbalance")
    ]


def test_doctor_fleet_imbalance_rule():
    snap = _fleet_snap(
        {
            "r0": _rep(sessions=24),
            "r1": _rep(sessions=0),
            "r2": _rep(sessions=0, draining=True),
            "r3": _rep(sessions=0),
            "r4": _rep(sessions=0, healthy=False),
        }
    )
    diags = doctor(counters={}, latency={}, fleet=snap)
    imb = [d for d in diags if d["code"] == "fleet_imbalance"]
    assert imb
    assert imb[0]["evidence"]["sessions"]["r0"] == 24
    assert set(imb[0]["evidence"]["ineligible"]) == {"r2", "r4"}
    assert imb[0]["knob"] == "TFS_FLEET_SIZE"


# ---------------------------------------------------------------------------
# cross-process fence race (satellite: exactly one adopter wins)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cross_process_fence_race(tmp_path, src_parquet, monkeypatch):
    """Two LIVE processes adopt the same job_id against a shared
    journal: the later adopter owns the fence; the earlier one's next
    append raises FenceLost and it stops writing; the winner's resume
    is bit-identical to an uninterrupted run."""
    monkeypatch.setenv("TFS_JOURNAL_DIR", str(tmp_path / "journal"))
    env = {**os.environ, "TFS_TEST_ISOLATED": "1"}

    def launch(delay_s):
        return subprocess.Popen(
            [sys.executable, RACER, src_parquet, "race", str(delay_s)],
            env=env, stdout=subprocess.PIPE, text=True,
        )

    a = launch(1.5)  # ~12s of windows: ample adoption window for B
    try:
        # wait until A owns the fence and journaled >= 1 boundary
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            st = recovery.job_status("race")
            if st.get("present") and st.get("boundary", 0) >= 1:
                break
            assert a.poll() is None, "racer A exited prematurely"
            time.sleep(0.1)
        else:
            raise AssertionError("racer A never journaled a boundary")
        b = launch(0.05)
        out_b, _ = b.communicate(timeout=300)
        out_a, _ = a.communicate(timeout=300)
    finally:
        if a.poll() is None:
            a.kill()
    assert a.returncode == 0 and b.returncode == 0
    ra = json.loads(out_a.strip().splitlines()[-1])
    rb = json.loads(out_b.strip().splitlines()[-1])
    # B adopted after A: B owns the fence, A is the zombie
    assert rb["outcome"] == "complete"
    assert ra["outcome"] == "fence_lost"
    assert ra["counters"]["journal_fence_rejections"] >= 1
    # the winner resumed A's journal mid-job and skipped, never
    # re-ingested, every boundary A completed — exactly-once
    assert rb["counters"]["journal_resumes"] == 1
    assert rb["counters"]["journal_windows_skipped"] >= 1
    assert (
        rb["counters"]["journal_windows_skipped"]
        + rb["counters"]["stream_windows"]
        == N_WINDOWS
    )
    # bit-identical to an uninterrupted in-process run
    ref = streaming.reduce_rows(ADD, _scan(src_parquet), fetches=["x"])
    arr = np.ascontiguousarray(np.asarray(ref["x"]))
    assert rb["sha"] == hashlib.sha256(arr.tobytes()).hexdigest()
    assert recovery.job_status("race")["status"] == "complete"


# ---------------------------------------------------------------------------
# chaos acceptance: replica SIGKILL mid-durable-job, zero failed requests
# ---------------------------------------------------------------------------


def _start_traffic(router, n):
    """Background ping traffic through failover-aware clients; returns
    (stop_event, errors_list, threads)."""
    stop, errors, threads = threading.Event(), [], []

    def unit(i):
        try:
            with FleetClient(router, key=f"traffic-{i}") as tc:
                while not stop.is_set():
                    tc.ping()
                    time.sleep(0.02)
        except Exception as exc:  # noqa: BLE001 — the assert reports it
            errors.append(exc)

    for i in range(n):
        t = threading.Thread(target=unit, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    return stop, errors, threads


@pytest.mark.slow
def test_fleet_chaos_replica_kill_migrates_durable_job(
    tmp_path, src_parquet
):
    names = ["r0", "r1", "r2"]
    key = "chaos-durable"
    victim = max(
        names, key=lambda n: fleet_mod._rendezvous_score(n, key)
    )
    # engine `delay` paces the victim's windows so the SIGKILL lands
    # mid-job with boundaries already journaled; `call=1` targets the
    # session's SECOND pipeline (the durable one — call 0 is warmup)
    fault_env = {
        victim: "replica_kill:method=pipeline:call=1:ms=900;delay:ms=150"
    }
    spec = _pipeline_spec(src_parquet)
    # uninterrupted single-process reference, same GraphDef spec
    ref = relational.run_stream_pipeline(**spec)

    fl = BridgeFleet(
        3, base_env=_fleet_env(tmp_path), fault_env=fault_env,
        log_dir=str(tmp_path / "logs"),
    )
    with fl:
        router = fl.router(health_s=0.2)
        try:
            assert router.route(key).name == victim
            stop, errors, threads = _start_traffic(router, 4)
            c0 = obs.counters()
            fc = FleetClient(router, key=key)
            try:
                # warmup (pipeline call 0): jits the graphs on the
                # victim so the durable run's windows are delay-paced
                warm = fc.run_pipeline(spec["source"], spec["stages"])
                assert warm["rows"] == ROWS
                # durable job (pipeline call 1): the victim SIGKILLs
                # itself 900ms in, mid-append — the client reroutes and
                # the survivor adopts the journal fence
                r = fc.run_pipeline(
                    spec["source"], spec["stages"], job_id="chaos-mig"
                )
                stop.set()
                for t in threads:
                    t.join(timeout=10)
                assert not errors  # zero failed requests
                assert fc.client.failovers >= 1
                assert r.get("resumed") is True
                got = r["frame"].collect()
                for n in ref["frame"].column_names:
                    assert (
                        np.asarray(got[n]).tobytes()
                        == np.asarray(ref["frame"].column(n).data).tobytes()
                    )
                delta = obs.counters_delta(c0)
                assert delta["fleet_failovers"] >= 1
                assert delta["fleet_jobs_migrated"] == 1
                # the victim really died by SIGKILL
                assert fl._replicas[victim].proc.poll() == -signal.SIGKILL
                # exactly-once on the adopter: every boundary the victim
                # journaled was SKIPPED, and skipped + executed covers
                # the stream exactly (the adopter ran nothing else)
                h = fc.health()["counters"]
                assert h["journal_resumes"] >= 1
                assert h["journal_windows_skipped"] >= 1
                assert (
                    h["journal_windows_skipped"] + h["stream_windows"]
                    == N_WINDOWS
                )
                # a completed job replays without executing anything
                assert fc.job_status("chaos-mig")["status"] == "complete"
            finally:
                stop.set()
                fc.close()
        finally:
            router.close()


# ---------------------------------------------------------------------------
# rolling restart: zero shed, zero recompiles on rejoin
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_rolling_restart_zero_shed_zero_recompile(
    tmp_path, src_parquet
):
    spec = _pipeline_spec(src_parquet)
    fl = BridgeFleet(
        2, base_env=_fleet_env(tmp_path), log_dir=str(tmp_path / "logs")
    )
    with fl:
        router = fl.router(health_s=0.2)
        try:
            names = [n for n, _, _ in fl.replicas()]
            # prime the SHARED compile cache: one replica compiles the
            # spec's executables once; every later process deserializes
            with FleetClient(
                router, key=_key_routing_to(names, names[0])
            ) as pc:
                assert pc.run_pipeline(
                    spec["source"], spec["stages"]
                )["rows"] == ROWS
            stop, errors, threads = _start_traffic(router, 2)
            c0 = obs.counters()
            fl.rolling_restart(router)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            # zero shed requests attributable to the restart
            assert not errors
            assert obs.counters_delta(c0)["fleet_replica_restarts"] == 2
            snap = router.snapshot()["replicas"]
            assert all(
                r["healthy"] and not r["draining"]
                for r in snap.values()
            )
            # every restarted replica serves the primed pipeline with
            # ZERO recompiles: warm rejoin via the shared cache
            for name in names:
                with FleetClient(
                    router, key=_key_routing_to(names, name)
                ) as c:
                    assert router.route(c.key).name == name
                    assert c.run_pipeline(
                        spec["source"], spec["stages"]
                    )["rows"] == ROWS
                    h = c.health()
                    assert h["replica"]["name"] == name
                    assert h["counters"]["persistent_cache_hits"] > 0
                    assert h["counters"]["persistent_cache_misses"] == 0
        finally:
            router.close()
