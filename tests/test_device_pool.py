"""Block-parallel device-pool scheduler (``ops/device_pool.py``).

The reference's native scaling mode is data parallelism over partitions —
one tensor program per Spark partition, in parallel across executors
(SURVEY §2.7 P1/P4).  The pool reproduces it at single-host scale: blocks
dispatch across the forced 8-device CPU mesh with per-device staging
lanes and overlapped readback.  The contract under test is strict
**bit-identity**: whatever the pool schedules, every verb must return
exactly the single-device bytes, assembled in block order.

Tests named ``test_pooled_*`` run process-isolated (tests/conftest.py):
each gets a fresh interpreter on the forced 8-device mesh, so per-device
jit caches and env-knob flips never leak into the single-device-pinned
main suite.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from tensorframes_tpu import cancellation
from tensorframes_tpu import observability as obs
from tensorframes_tpu.ops import block_loop, device_pool, engine, frame_cache
from tensorframes_tpu.ops.pipeline import pipeline


# ---------------------------------------------------------------------------
# knob / scheduling logic (no dispatch: safe in-process)
# ---------------------------------------------------------------------------


def test_pool_devices_knob(monkeypatch):
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    assert device_pool.pool_devices() == []
    assert not device_pool.enabled()
    monkeypatch.setenv("TFS_DEVICE_POOL", "off")
    assert device_pool.pool_devices() == []
    monkeypatch.setenv("TFS_DEVICE_POOL", "1")  # a 1-pool is the serial path
    assert device_pool.pool_devices() == []
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    assert len(device_pool.pool_devices()) == len(jax.local_devices())
    monkeypatch.setenv("TFS_DEVICE_POOL", "3")
    assert len(device_pool.pool_devices()) == 3
    monkeypatch.setenv("TFS_DEVICE_POOL", "64")  # capped at local devices
    assert len(device_pool.pool_devices()) == len(jax.local_devices())
    monkeypatch.setenv("TFS_DEVICE_POOL", "banana")  # malformed -> auto
    assert len(device_pool.pool_devices()) == len(jax.local_devices())


def test_assign_least_loaded_deterministic():
    # equal blocks -> round robin
    assert device_pool.assign([10, 10, 10, 10], 2) == [0, 1, 0, 1]
    # skewed blocks -> row-balanced, ties to the lowest device index
    assert device_pool.assign([100, 1, 1, 1], 2) == [0, 1, 1, 1]
    # deterministic: same sizes, same plan
    sizes = [7, 3, 9, 9, 2, 5, 1, 8]
    assert device_pool.assign(sizes, 3) == device_pool.assign(sizes, 3)
    # empty blocks still cost a dispatch slot (never all pile on device 0)
    assert device_pool.assign([0, 0, 0, 0], 2) == [0, 1, 0, 1]


def test_executor_opt_in_flags():
    assert engine.Executor.supports_device_pool is True
    from tensorframes_tpu.parallel import dist

    assert dist.MeshExecutor.supports_device_pool is False


# ---------------------------------------------------------------------------
# pooled dispatch (process-isolated: test_pooled_*)
# ---------------------------------------------------------------------------


def _frame(n=120, nb=6, seed=0, d=4):
    rng = np.random.RandomState(seed)
    return tfs.analyze(
        tfs.TensorFrame.from_arrays(
            {
                "x": rng.rand(n, d).astype(np.float32),
                "k": (np.arange(n) % 5).astype(np.int32),
            },
            num_blocks=nb,
        )
    )


def test_pooled_six_verbs_bit_identical(monkeypatch):
    """All six verbs under the pool return EXACTLY the single-device
    bytes — same values, same block-order assembly."""
    frame = _frame()
    mapb = tfs.Program.wrap(
        lambda x: {"y": jnp.tanh(x) * 2.0 + x}, fetches=["y"]
    )
    mapr = tfs.Program.wrap(lambda x: {"r": x.sum() + x[0]}, fetches=["r"])
    trimmed = tfs.Program.wrap(
        lambda x: {"s": x.sum(0, keepdims=True)}, fetches=["s"]
    )
    pair = tfs.Program.wrap(
        lambda x_1, x_2: {"x": x_1 + 3.0 * x_2}, fetches=["x"]
    )
    blockred = tfs.Program.wrap(
        lambda x_input: {"x": (x_input * 1.3).sum(0)}, fetches=["x"]
    )
    agg = tfs.Program.wrap(
        lambda x_input: {"x": x_input.sum(0)}, fetches=["x"]
    )

    def run_all():
        out = {}
        out["map_blocks"] = np.asarray(
            tfs.map_blocks(mapb, frame).column("y").data
        )
        out["map_rows"] = np.asarray(
            tfs.map_rows(mapr, frame).column("r").data
        )
        out["trimmed"] = np.asarray(
            tfs.map_blocks(trimmed, frame, trim=True).column("s").data
        )
        out["reduce_rows_tree"] = tfs.reduce_rows(pair, frame, mode="tree")[
            "x"
        ]
        out["reduce_rows_seq"] = tfs.reduce_rows(
            pair, frame, mode="sequential"
        )["x"]
        out["reduce_blocks"] = tfs.reduce_blocks(blockred, frame)["x"]
        a = tfs.aggregate(agg, frame.group_by("k"))
        out["aggregate_k"] = np.asarray(a.column("k").data)
        out["aggregate_x"] = np.asarray(a.column("x").data)
        return out

    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    base = run_all()
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    pooled = run_all()
    for name in base:
        np.testing.assert_array_equal(
            base[name], pooled[name], err_msg=name
        )


def test_pooled_map_blocks_actually_pools(monkeypatch):
    """The pool genuinely engages: pool_blocks counts every block and the
    span's per-device block counts cover > 1 device."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    frame = _frame(n=160, nb=8)
    prog = tfs.Program.wrap(lambda x: {"y": x * 2.0}, fetches=["y"])
    obs.enable()
    try:
        c0 = obs.counters()
        out = tfs.map_blocks(prog, frame)
        np.asarray(out.column("y").data)
        d = obs.counters_delta(c0)
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    assert d["pool_blocks"] == frame.num_blocks, d
    pool = span["device_pool"]
    assert pool["devices"] == len(jax.local_devices())
    assert sum(pool["blocks_per_device"]) == frame.num_blocks
    assert sum(pool["rows_per_device"]) == frame.num_rows
    assert sum(1 for b in pool["blocks_per_device"] if b) > 1
    assert len(pool["occupancy"]) == pool["devices"]
    assert len(pool["idle_s"]) == pool["devices"]
    # the span also carries the standard prefetch stats (lane totals)
    assert span["prefetch"]["items"] == frame.num_blocks


def test_pooled_bucketed_and_streamed_bit_identical(monkeypatch):
    """Pool x shape-canonical bucketing (uneven blocks pad + slice) and
    pool x chunked h2d streaming both keep bit-identity."""
    # uneven frame: 1030 rows over 4 blocks -> 258/258/257/257, bucketed
    rng = np.random.RandomState(1)
    arrs = {"x": rng.rand(1030, 8).astype(np.float32)}
    prog = tfs.Program.wrap(lambda x: {"y": x * 2.0 + 1.0}, fetches=["y"])

    def run():
        frame = tfs.analyze(
            tfs.TensorFrame.from_arrays(arrs, num_blocks=4)
        )
        return np.asarray(tfs.map_blocks(prog, frame).column("y").data)

    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    base = run()
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    np.testing.assert_array_equal(base, run())

    # streamed chunks: force tiny chunk bytes so every block streams
    monkeypatch.setenv("TFS_PREFETCH_BLOCKS", "2")
    monkeypatch.setattr(engine.Executor, "stream_chunk_bytes", 4096)
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    base = run()
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    obs.enable()
    try:
        got = run()
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    np.testing.assert_array_equal(base, got)
    assert span["device_pool"]["devices"] >= 2


def test_pooled_block_order_stable_under_adversarial_delays(monkeypatch):
    """Per-block host_stage delays scramble completion order; assembly
    must stay strictly by block index (row i of the output is row i of
    the input, transformed)."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_PREFETCH_BLOCKS", "2")
    n, nb = 64, 8
    vals = np.arange(n, dtype=np.float32).reshape(n, 1)
    frame = tfs.analyze(
        tfs.TensorFrame.from_arrays({"x": vals}, num_blocks=nb)
    )

    def adversarial_stage(cells):
        arr = np.asarray(cells, np.float32)
        # early blocks sleep LONGEST: later devices finish first, so a
        # completion-order bug would reorder the output blocks
        time.sleep(0.002 * max(0.0, float(n - arr[0, 0])) / 8.0)
        return arr

    prog = tfs.Program.wrap(lambda x: {"y": x + 100.0}, fetches=["y"])
    out = tfs.map_blocks(prog, frame, host_stage={"x": adversarial_stage})
    np.testing.assert_array_equal(
        np.asarray(out.column("y").data), vals + 100.0
    )
    # and passthrough columns still align row-for-row
    np.testing.assert_array_equal(
        np.asarray(out.column("x").data), vals
    )


def test_pooled_donation_safety(monkeypatch):
    """Forced donation (TFS_DONATE=1) under the pool: staged copies are
    donated, the source frame's host columns stay intact, and repeated
    verbs over the same frame keep producing identical results.  A
    device-cached frame must bypass the pool entirely (residency is
    shared state)."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_DONATE", "1")
    monkeypatch.setenv("TFS_PREFETCH_BLOCKS", "2")
    frame = _frame(n=96, nb=6)
    before = np.asarray(frame.column("x").data).copy()
    prog = tfs.Program.wrap(lambda x: {"y": x * 4.0}, fetches=["y"])
    first = np.asarray(tfs.map_blocks(prog, frame).column("y").data)
    second = np.asarray(tfs.map_blocks(prog, frame).column("y").data)
    np.testing.assert_array_equal(first, second)
    np.testing.assert_array_equal(
        np.asarray(frame.column("x").data), before
    )
    # single-device cached frame (sharded=False): the pool must not
    # engage — its columns are shared state on ONE device, and donating
    # or splitting them would corrupt/shuffle HBM
    cached = frame.cache(sharded=False)
    obs.enable()
    try:
        c0 = obs.counters()
        out = np.asarray(tfs.map_blocks(prog, cached).column("y").data)
        d = obs.counters_delta(c0)
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    np.testing.assert_array_equal(out, first)
    assert d["pool_blocks"] == 0, d
    assert "device_pool" not in span
    assert span["prefetch"]["donate"] is False
    # DEFAULT cache() while the pool is active shards (round 10,
    # ops/frame_cache.py): the affinity dispatch pools every block on
    # its resident device — zero H2D, never donating, same bytes
    sharded = frame.cache()
    obs.enable()
    try:
        c0 = obs.counters()
        out = np.asarray(tfs.map_blocks(prog, sharded).column("y").data)
        d = obs.counters_delta(c0)
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    np.testing.assert_array_equal(out, first)
    assert d["pool_blocks"] == frame.num_blocks, d
    assert d["h2d_bytes_staged"] == 0, d
    assert span["device_pool"]["affinity"] is True


def test_pooled_warmup_primes_every_device(monkeypatch):
    """After ``warmup`` on a pool-eligible frame, the first real pooled
    dispatch compiles NOTHING — every (bucket size, device) executable
    is already seeded."""
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    monkeypatch.setenv("TFS_BLOCK_BUCKETS", "0")  # exact shapes: one size
    frame = _frame(n=96, nb=6)  # 16 rows per block, even
    program = tfs.Program.wrap(lambda x: {"y": x * 5.0}, fetches=["y"])
    fps = tfs.warmup(program, frame)
    assert fps  # the AOT fingerprints still come back
    c0 = obs.counters()
    out = tfs.map_blocks(program, frame)
    np.asarray(out.column("y").data)
    d = obs.counters_delta(c0)
    assert d["backend_compiles"] == 0, d
    assert d["pool_blocks"] == frame.num_blocks, d


def test_pooled_reduce_partials_fold_shape(monkeypatch):
    """The reduce combine keeps the exact single-device fold shape: a
    NON-associative pairwise program (order-sensitive) still matches the
    serial result bit for bit, in both fold modes."""
    rng = np.random.RandomState(3)
    vals = rng.rand(100).astype(np.float32)
    frame = tfs.analyze(
        tfs.TensorFrame.from_arrays({"v": vals}, num_blocks=5)
    )
    # deliberately non-associative: (a, b) -> a * 0.9 + b * b
    pair = tfs.Program.wrap(
        lambda v_1, v_2: {"v": v_1 * 0.9 + v_2 * v_2}, fetches=["v"]
    )
    blockred = tfs.Program.wrap(
        lambda v_input: {"v": jnp.cumsum(v_input)[-1] * 1.0000001},
        fetches=["v"],
    )
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    base = {
        "tree": tfs.reduce_rows(pair, frame, mode="tree")["v"],
        "seq": tfs.reduce_rows(pair, frame, mode="sequential")["v"],
        "blocks": tfs.reduce_blocks(blockred, frame)["v"],
    }
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    obs.enable()
    try:
        got = {
            "tree": tfs.reduce_rows(pair, frame, mode="tree")["v"],
            "seq": tfs.reduce_rows(pair, frame, mode="sequential")["v"],
            "blocks": tfs.reduce_blocks(blockred, frame)["v"],
        }
        span = obs.last_spans(1)[0]
    finally:
        obs.disable()
    for k in base:
        np.testing.assert_array_equal(base[k], got[k], err_msg=k)
    assert span["device_pool"]["devices"] >= 2
    assert sum(span["device_pool"]["blocks_per_device"]) == 5


class _CancelAtCheckpoint(cancellation.CancelScope):
    """Cancels itself at its ``n``-th checkpoint (0-based).  With block
    retries off — the suite's pin — the block loop's per-block
    checkpoint is the only one a verb makes, so that is the boundary
    before block ``n``."""

    __slots__ = ("n", "seen")

    def __init__(self, n):
        super().__init__(label="test")
        self.n = n
        self.seen = 0

    def check(self):
        if self.seen == self.n:
            self.cancel("cut before block %d" % self.n)
        self.seen += 1
        super().check()


_LOOP_VERBS = {
    # verb -> (program, run -> numpy result, the loop's span)
    "map_blocks": (
        lambda: tfs.Program.wrap(
            lambda x: {"y": jnp.tanh(x) * 2.0 + x}, fetches=["y"]
        ),
        lambda prog, f: np.asarray(tfs.map_blocks(prog, f).column("y").data),
        "engine.block",
    ),
    "map_rows": (
        lambda: tfs.Program.wrap(
            lambda x: {"r": x.sum() + x[0]}, fetches=["r"]
        ),
        lambda prog, f: np.asarray(tfs.map_rows(prog, f).column("r").data),
        "engine.block",
    ),
    "reduce_blocks": (
        lambda: tfs.Program.wrap(
            lambda x_input: {"x": (x_input * 1.3).sum(0)}, fetches=["x"]
        ),
        lambda prog, f: tfs.reduce_blocks(prog, f)["x"],
        "engine.reduce_block",
    ),
}


@pytest.mark.parametrize("verb", sorted(_LOOP_VERBS))
@pytest.mark.parametrize("placement", ["serial", "pool", "affinity"])
def test_pooled_one_block_loop_equivalence(monkeypatch, placement, verb):
    """The engine has ONE block loop (``ops/block_loop.py``) behind the
    map and reduce verbs, whatever the placement: the bytes equal the
    serial result, every block's span is there exactly once on the
    track of the device it ran on, and a scope cancelled before block 2
    stops the verb at block 2."""
    make, run, span_name = _LOOP_VERBS[verb]
    prog = make()
    frame = _frame()  # 6 blocks of 20 rows
    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    base = run(prog, frame)
    if placement == "serial":
        want_tracks = ["serial"] * frame.num_blocks
    else:
        monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
        if placement == "affinity":
            frame = frame.cache(sharded=True)
            assignment = frame_cache.active_cache(frame).assignment
        else:
            assignment = device_pool.assign(
                frame.block_sizes, len(device_pool.pool_devices())
            )
        assert len(set(assignment)) >= 2
        want_tracks = [f"device/{di}" for di in assignment]
    placed = block_loop.place(engine._DEFAULT, frame, range(6))
    assert placed.kind == placement

    def block_spans():
        return [
            (e["args"]["block"], e["args"]["rows"], e["track"])
            for e in obs.trace_events()
            if e["name"] == span_name
        ]

    obs.enable_trace()
    try:
        obs.clear_trace()
        got = run(prog, frame)
        spans = block_spans()
        obs.clear_trace()
        with cancellation.activate(_CancelAtCheckpoint(2)):
            with pytest.raises(cancellation.Cancelled):
                run(prog, frame)
        cut = block_spans()
    finally:
        obs.disable_trace()
    np.testing.assert_array_equal(base, got)
    assert spans == [(bi, 20, want_tracks[bi]) for bi in range(6)]
    assert cut == spans[:2]  # blocks 0 and 1 ran, block 2 never started


def test_pooled_pipeline_map_chain(monkeypatch):
    """A map-terminal pipeline pools per block and matches both the fused
    single-dispatch result and the eager verbs; a row-terminal chain
    keeps the fused dispatch (no pool span).  The frame is deliberately
    UNEVEN (31/31/30/30) so the pooled chain exercises the bucket-padded
    path (one chain signature per device instead of one per block size)."""
    frame = _frame(n=122, nb=4)

    def chain():
        return (
            pipeline(frame)
            .map_rows(lambda x: {"z": x * 2.0})
            .map_blocks(lambda z: {"w": z + 1.0})
        )

    monkeypatch.setenv("TFS_DEVICE_POOL", "0")
    fused = chain().run()
    monkeypatch.setenv("TFS_DEVICE_POOL", "auto")
    obs.enable()
    try:
        pooled = chain().run()
        span_map = obs.last_spans(1)[0]
        row = (
            pipeline(frame)
            .map_blocks_trimmed(lambda x: {"s": x.sum(0, keepdims=True)})
            .reduce_blocks(lambda s_input: {"s": s_input.sum(0)})
            .run()
        )
        span_row = obs.last_spans(1)[0]
    finally:
        obs.disable()
    for col in ("w", "z", "x", "k"):
        np.testing.assert_array_equal(
            np.asarray(fused.column(col).data),
            np.asarray(pooled.column(col).data),
            err_msg=col,
        )
    assert pooled.offsets == fused.offsets
    assert span_map["device_pool"]["devices"] >= 2
    assert "device_pool" not in span_row  # row-terminal: one fused dispatch
    # and the fused reduce still agrees with the eager verb
    eager = tfs.reduce_blocks(
        lambda s_input: {"s": s_input.sum(0)},
        tfs.map_blocks(
            lambda x: {"s": x.sum(0, keepdims=True)}, frame, trim=True
        ),
    )
    np.testing.assert_allclose(
        np.asarray(jax.device_get(row["s"])), eager["s"], rtol=1e-6
    )
