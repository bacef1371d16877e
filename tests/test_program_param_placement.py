"""A program's params are resident on every device its blocks run on
(``program._Residency``).

jax moves every *uncommitted* argument of a call to the device its
committed arguments live on, at every call, and keeps nothing: a program
with a many-leaf params tree mapped over the device pool would begin
every block on a non-default device with one copy a leaf.  The binding
(``Program._bind_live_params``) therefore hands a block the params that
live on the block's device — the originals at home, one committed
replica placed on first use elsewhere.  Under test: the replica is made
once a device and params state (never once a block), results are
bit-identical to the serial placement, no write to the params can leave
a stale replica, a redirected block gets the other device's replica,
and the replicas go with the program.

Tests named ``test_pooled_*`` run process-isolated on the forced
8-device mesh (tests/conftest.py).
"""

import gc
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from tensorframes_tpu import observability as obs
from tensorframes_tpu import program as program_mod
from tensorframes_tpu.ops import block_loop, engine, frame_cache, planner

LAYERS = 24  # 48 leaves: enough that a copy a leaf a block would show
WIDTH = 16
ROWS, BLOCKS = 128, 16
POOL = 4


def _tree(seed, scale=0.3):
    rng = np.random.default_rng(seed)
    return {
        f"layer{i}": {
            "w": (rng.standard_normal((WIDTH, WIDTH)) * scale).astype(
                np.float32
            ),
            "b": rng.standard_normal(WIDTH).astype(np.float32),
        }
        for i in range(LAYERS)
    }


def _net(x, model):
    for i in range(LAYERS):
        layer = model[f"layer{i}"]
        x = jnp.tanh(x @ layer["w"] + layer["b"])
    return {"y": x}


def _program(seed=0):
    return tfs.Program.wrap(_net, fetches=["y"], params={"model": _tree(seed)})


def _frame(rows=ROWS, blocks=BLOCKS):
    x = np.random.default_rng(99).standard_normal((rows, WIDTH))
    return tfs.TensorFrame.from_arrays(
        {"x": x.astype(np.float32)}, num_blocks=blocks
    )


def _tree_bytes(prog):
    return sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(prog.params)
    )


def _map(prog, frame):
    return np.asarray(tfs.map_blocks(prog, frame).column("y").data)


def _serial(monkeypatch, prog, frame):
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    try:
        return _map(prog, frame)
    finally:
        monkeypatch.setenv("TFS_DEVICE_POOL", str(POOL))


def _watch(keys=("dispatch_blocks", "param_replica_hits",
                 "param_bytes_placed")):
    before = obs.counters()
    return lambda: {
        k: v for k, v in obs.counters_delta(before).items() if k in keys
    }


def test_pooled_params_placed_once_a_device_over_epochs(monkeypatch):
    """(a) + (b): three epochs over a sharded cached frame on four
    devices place the tree three times in all (once a non-default
    device), every block finds the params on its device, and the bytes
    are the serial placement's."""
    prog, frame = _program(), _frame()
    base = _serial(monkeypatch, prog, frame)
    cached = frame.cache(sharded=True)
    cache = frame_cache.active_cache(cached)
    assert sorted(set(cache.assignment)) == list(range(POOL))
    assert block_loop.place(
        engine._DEFAULT, cached, range(BLOCKS)
    ).kind == "affinity"
    delta = _watch()
    for _ in range(3):
        np.testing.assert_array_equal(_map(prog, cached), base)
    assert delta() == {
        "dispatch_blocks": 3 * BLOCKS,
        "param_replica_hits": 3 * BLOCKS,
        "param_bytes_placed": (POOL - 1) * _tree_bytes(prog),
    }
    home = jax.devices()[0]
    res = prog._residency
    assert res.home == home
    assert set(res.replicas) == set(cache.devices) - {home}
    for dev, rep in res.replicas.items():
        for leaf in jax.tree_util.tree_leaves(rep):
            assert leaf.committed and leaf.sharding.device_set == {dev}


def test_pooled_update_params_drops_replicas_and_places_again(monkeypatch):
    """(c): ``update_params`` between two epochs — the second epoch's
    outputs are the new values' on every device, and each device's
    replica was placed again, once."""
    prog, frame = _program(0), _frame()
    fresh = _program(1)
    want_old = _serial(monkeypatch, prog, frame)
    want_new = _serial(monkeypatch, fresh, frame)
    assert not np.array_equal(want_old, want_new)
    cached = frame.cache(sharded=True)
    np.testing.assert_array_equal(_map(prog, cached), want_old)
    old = prog._residency
    assert len(old.replicas) == POOL - 1
    prog.update_params(model=_tree(1))
    assert prog._residency is None  # dropped with the version bump
    delta = _watch()
    for _ in range(2):
        np.testing.assert_array_equal(_map(prog, cached), want_new)
    assert delta() == {
        "dispatch_blocks": 2 * BLOCKS,
        "param_replica_hits": 2 * BLOCKS,
        "param_bytes_placed": (POOL - 1) * _tree_bytes(prog),
    }
    assert prog._residency is not old


def test_pooled_direct_write_cannot_leave_a_stale_replica(monkeypatch):
    """(d): a write to ``_params[p]`` past ``update_params`` — what the
    planner's ``_sync_probe_params`` does to its probe program — is seen
    by the next block on every device: a replica is keyed on the
    identity of the values it was made from."""
    owner, probe = _program(0), _program(0)
    frame = _frame()
    want_new = _serial(monkeypatch, _program(1), frame)
    cached = frame.cache(sharded=True)
    _map(probe, cached)
    stale = probe._residency
    assert len(stale.replicas) == POOL - 1
    owner.update_params(model=_tree(1))
    meta = planner._FusedMeta.__new__(planner._FusedMeta)
    meta.program, meta.param_slots = probe, [("model", owner)]
    planner._sync_probe_params(meta)
    assert probe._params["model"] is owner._params["model"]
    assert not stale.current(probe._params)
    delta = _watch()
    np.testing.assert_array_equal(_map(probe, cached), want_new)
    assert probe._residency is not stale
    assert delta()["param_bytes_placed"] == (POOL - 1) * _tree_bytes(probe)
    assert delta()["param_replica_hits"] == BLOCKS


def test_pooled_quarantine_redirect_uses_the_other_devices_replica(
    monkeypatch,
):
    """(e): a device that keeps failing is quarantined; its blocks run
    on another device, with THAT device's replica (no device holds two,
    none is copied to twice), bit-identically."""
    prog, frame = _program(), _frame()
    base = _serial(monkeypatch, prog, frame)
    cached = frame.cache(sharded=True)
    monkeypatch.setenv("TFS_QUARANTINE_AFTER", "2")
    monkeypatch.setenv("TFS_BLOCK_RETRIES", "3")
    monkeypatch.setenv("TFS_RETRY_BACKOFF_MS", "0")
    monkeypatch.setenv("TFS_FAULT_INJECT", "transient:device=2")
    obs.enable()
    try:
        delta = _watch(("dispatch_blocks", "param_replica_hits",
                        "param_bytes_placed", "devices_quarantined"))
        got = _map(prog, cached)
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    np.testing.assert_array_equal(got, base)
    assert span["fault_tolerance"]["quarantined_devices"] == [2]
    ran_on = span["device_pool"]["blocks_per_device"]
    assert ran_on[2] == 0 and sum(ran_on) == BLOCKS
    d = delta()
    assert d["devices_quarantined"] == 1
    assert d["param_replica_hits"] == d["dispatch_blocks"] == BLOCKS
    # devices 1 and 3 hold a replica; device 2 never completed a block
    # (a fault is raised before the executable is entered) and got none
    devices = frame_cache.active_cache(cached).devices
    assert set(prog._residency.replicas) == {devices[1], devices[3]}
    assert d["param_bytes_placed"] == 2 * _tree_bytes(prog)


def test_pooled_host_fresh_frame_places_once_a_device(monkeypatch):
    """(f): the pooled placement of a host-fresh frame (inputs staged by
    the lanes onto device d) is cured by the same line."""
    prog, frame = _program(), _frame()
    base = _serial(monkeypatch, prog, frame)
    assert block_loop.place(
        engine._DEFAULT, frame, range(BLOCKS)
    ).kind == "pool"
    delta = _watch()
    for _ in range(2):
        np.testing.assert_array_equal(_map(prog, frame), base)
    assert delta() == {
        "dispatch_blocks": 2 * BLOCKS,
        "param_replica_hits": 2 * BLOCKS,
        "param_bytes_placed": (POOL - 1) * _tree_bytes(prog),
    }


def test_pooled_reduce_and_rows_entries_share_the_replicas(monkeypatch):
    """Every entry that binds live params (``jitted``, ``vmapped``,
    ``cached_jit``) picks the replica by the same rule: a ``map_rows``
    and a ``reduce_blocks`` of programs with params over the pool place
    once a device and agree with the serial result."""
    w = np.random.default_rng(5).standard_normal(WIDTH).astype(np.float32)
    rows = tfs.Program.wrap(
        lambda x, w: {"r": jnp.tanh(x * w).sum()}, fetches=["r"],
        params={"w": w},
    )
    red = tfs.Program.wrap(
        lambda x_input, w: {"x": (x_input * w).sum(0)}, fetches=["x"],
        params={"w": w},
    )
    frame = _frame()
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    want_rows = np.asarray(tfs.map_rows(rows, frame).column("r").data)
    want_red = tfs.reduce_blocks(red, frame)["x"]
    monkeypatch.setenv("TFS_DEVICE_POOL", str(POOL))
    cached = frame.cache(sharded=True)
    delta = _watch()
    for _ in range(2):
        got_rows = np.asarray(tfs.map_rows(rows, cached).column("r").data)
        got_red = tfs.reduce_blocks(red, cached)["x"]
    np.testing.assert_array_equal(got_rows, want_rows)
    np.testing.assert_array_equal(got_red, want_red)
    assert delta()["param_bytes_placed"] == 2 * (POOL - 1) * w.nbytes
    assert len(rows._residency.replicas) == POOL - 1
    assert len(red._residency.replicas) == POOL - 1


def test_one_device_places_nothing():
    """(g): on one device the originals are already where the blocks
    run: no replica, no byte placed, every block a hit."""
    prog, frame = _program(), _frame()  # the suite pins TFS_DEVICE_POOL=0
    cached = frame.cache()
    assert block_loop.place(
        engine._DEFAULT, cached, range(BLOCKS)
    ).kind == "serial"
    delta = _watch()
    for fr in (frame, cached):
        _map(prog, fr)
    assert delta() == {
        "dispatch_blocks": 2 * BLOCKS,
        "param_replica_hits": 2 * BLOCKS,
        "param_bytes_placed": 0,
    }
    assert prog._residency is None or not prog._residency.replicas


def test_uncommitted_and_mesh_inputs_keep_the_plain_call():
    """Inputs that are not committed, or committed to a sharding over
    several devices, get the originals — today's call exactly."""
    prog = _program()
    x = jnp.ones((8, WIDTH), jnp.float32)
    assert program_mod._committed_device(({"x": x},)) is None
    assert program_mod._committed_device(({"x": np.ones(3)},)) is None
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("d",))
    spread = jax.device_put(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("d"))
    )
    assert program_mod._committed_device(({"x": spread},)) is None
    assert prog._params_at(None) is prog._params
    there = jax.device_put(x, jax.devices()[1])
    assert program_mod._committed_device(({"x": there},)) == jax.devices()[1]
    delta = _watch()
    want = np.asarray(prog.jitted()({"x": x})["y"])
    assert prog._residency is None  # nothing committed: nobody asked
    np.testing.assert_array_equal(
        np.asarray(prog.jitted()({"x": spread})["y"]), want
    )
    assert delta()["param_bytes_placed"] == 0
    np.testing.assert_array_equal(
        np.asarray(prog.jitted()({"x": there})["y"]), want
    )
    assert delta()["param_bytes_placed"] == _tree_bytes(prog)
    assert prog._params_at(jax.devices()[0]) is prog._params  # home


def test_dropping_the_program_frees_the_replicas():
    """(h): a replica is the params' bytes once a device, held by the
    program alone: it goes with the program, and with the params state
    at ``update_params``."""
    prog = _program()
    x = jax.device_put(jnp.ones((8, WIDTH), jnp.float32), jax.devices()[1])
    prog.jitted()({"x": x})["y"].block_until_ready()
    (rep,) = prog._residency.replicas.values()
    first = weakref.ref(jax.tree_util.tree_leaves(rep)[0])
    del rep
    prog.update_params(model=_tree(3))
    gc.collect()
    assert first() is None  # the old state's replica went at the update
    prog.jitted()({"x": x})["y"].block_until_ready()
    (rep,) = prog._residency.replicas.values()
    second = weakref.ref(jax.tree_util.tree_leaves(rep)[0])
    del rep, prog
    gc.collect()
    assert second() is None
