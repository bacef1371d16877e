"""The frame engine's one block loop.

Every eager verb that walks a frame block by block — ``map_blocks``,
``map_rows`` and the per-block partials of ``reduce_rows`` /
``reduce_blocks`` — dispatches through :func:`run_blocks`.  The loop owns
what used to be written out once per verb and placement:

* **placement** (:func:`place`), decided once from what the code can
  observe: a sharded frame cache -> each block on the device holding its
  shard (affinity); a host-fresh frame of more than one block on an
  executor that pools, with >= 2 pool devices -> ``device_pool.assign``
  over the pool with per-device staging lanes; anything else -> serial
  on the default device;
* **how a block's inputs arrive and how a retry gets them again**: a
  buffer staged ahead by a lane, a resident shard with the missing
  columns filled in from the host copy, or an inline stage — and the ONE
  attempt closure of the retry session (attempt 0 may consume what was
  staged for the effective device; every later attempt re-stages from
  the host on the current effective device; a buffer handed to a
  dispatch is never used twice, donated or not);
* **how outputs are collected**: kept on the device in block order with
  no host sync (serial), assembled on the host by index through
  ``PoolRun.submit`` / ``finish`` (pooled maps), or moved — one cell per
  base column — to the one combine device in block order (pooled reduce
  partials, so the final fold keeps its serial shape);
* the per-block ``cancellation.checkpoint()``, the ``tfs:engine.block``
  / ``tfs:engine.reduce_block`` span, the dispatch counters and the
  ``device_pool`` / ``frame_cache`` / ``fault_tolerance`` span records.

The verb hands the loop a :class:`Work`: how to stage one block to a
device and how to run staged inputs.  Results are bit-identical across
placements by construction — the same executables see the same values,
and assembly is by block index, never by completion order.
"""

from __future__ import annotations

import functools
import logging
from typing import (
    Any, Dict, FrozenSet, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax

from .. import cancellation, observability
from . import device_pool, fault_tolerance, frame_cache, prefetch

_log = logging.getLogger("tensorframes_tpu.engine")


class Work:
    """What only the verb knows about its blocks.  The defaults are the
    reduce verbs' (nothing streams, nothing to check, no OOM split)."""

    name = ""  # the verb, as the retry session and the spans name it
    span = "engine.block"
    # pooled outputs: host-assembled by block index (maps), else moved to
    # the one combine device in block order (reduce partials)
    to_host = True
    # frame columns ``stage`` takes from a resident shard when it has them
    reads: FrozenSet[str] = frozenset()
    # blocks that bring their own inputs, chunk by chunk (run_streamed)
    streams: FrozenSet[int] = frozenset()
    # serial placement: stage blocks ahead on a Prefetcher thread
    ahead = False
    # pooled placement: ONE lane stages every block in block order (the
    # host_stage contract: stage fns may be stateful or non-reentrant)
    in_order = False

    def stage(self, bi: int, block, device) -> Any:
        """Block ``bi``'s inputs (``block``: its column slices, resident
        shard columns already substituted) onto ``device`` (None: the
        default device).  Runs on staging threads: no jit entry points."""
        raise NotImplementedError

    def run(self, bi: int, inputs) -> Dict[str, Any]:
        """Dispatch the program over staged ``inputs``."""
        raise NotImplementedError

    def run_streamed(self, bi: int, device, session, resolver, stats):
        """Dispatch a block of ``streams``, staging it chunk by chunk."""
        raise NotImplementedError

    def check(self, bi: int, outs) -> None:
        """Validate one block's outputs before they are collected."""

    def params_resident(self, outs) -> bool:
        """Whether the program's live params are on the device that
        produced ``outs`` (the ``param_replica_hits`` counter)."""
        return False

    def oom_split(self, bi: int, session, devices, pool, di):
        """The block's OOM-degradation closure for ``session.run``."""
        return None


class Placement(NamedTuple):
    """Which blocks a verb dispatches and where each runs.
    ``assignment`` is positional over ``blocks`` (``assignment[k]`` is
    the device index of ``blocks[k]``); ``fresh`` is the donation rule's
    freshness, False for a cached frame (shards are shared state and
    never donate)."""

    kind: str  # "serial" | "pool" | "affinity"
    blocks: Sequence[int]
    devices: Optional[Sequence[Any]] = None
    assignment: Optional[List[int]] = None
    cache: Any = None
    fresh: bool = False


def place(executor, frame, blocks: Sequence[int]) -> Placement:
    """THE serial / pool / affinity decision, for maps and reduces alike.

    A sharded-cached frame runs each block where its shard lives (the
    residency plan IS the schedule).  A host-fresh frame with more than
    one block to dispatch spreads over the device pool when the executor
    allows it and the pool resolves >= 2 devices.  Device-resident
    frames stay serial on their device (splitting a cached column across
    the pool would shuffle HBM), as does everything else."""
    cache = frame_cache.active_cache(frame)
    if cache is not None:
        return Placement(
            "affinity", blocks, cache.devices,
            [cache.assignment[bi] for bi in blocks], cache,
        )
    fresh = executor._frame_fresh(frame)
    if executor.supports_device_pool and fresh and len(blocks) > 1:
        devices = device_pool.pool_devices()
        if len(devices) >= 2:
            sizes = frame.block_sizes
            return Placement(
                "pool", blocks, devices,
                device_pool.assign(
                    [sizes[bi] for bi in blocks], len(devices)
                ),
                fresh=fresh,
            )
    return Placement("serial", blocks, fresh=fresh)


def lane_next(it, lane_dead, li: int, session, pool):
    """Pull the next staged value from a pool lane.  Without a retry
    session, staging failures propagate exactly as before.  With
    one, a failed lane is marked dead (its worker has exited; its
    Prefetcher raises once then StopIterations), the failure counts
    against the lane's device, and the consumer re-stages every
    later block of that lane itself — recovery trades the staging
    overlap for completing the frame."""
    if lane_dead[li]:
        return None
    try:
        return next(it)
    except StopIteration:
        raise
    except BaseException as exc:  # noqa: BLE001 - recovery below
        if session is None:
            raise
        lane_dead[li] = True
        if pool is not None and li < len(pool.devices):
            pool.note_block_failure(li)
        _log.warning(
            "staging lane %d failed (%r); re-staging its remaining "
            "blocks on the consumer thread",
            li,
            exc,
        )
        return None


def run_blocks(
    placement: Placement,
    frame,
    work: Work,
    span,
    times=None,
) -> Tuple[List[Dict[str, Any]], Tuple[int, float, float]]:
    """Dispatch ``work`` over the blocks of ``frame`` that ``placement``
    names, where it places them.

    Returns the per-block outputs in block order — device-resident under
    serial placement, host numpy when pooled with ``work.to_host``, on
    the combine device otherwise — and the staging totals ``(blocks
    staged ahead + streamed chunks, stage_s, wait_s)`` for the verb's
    ``prefetch`` record.  ``times``: the map verbs' head / tail marks.

    Per block the device sees, in order: stage, dispatch, start of the
    D2H copy, and a materialise at most ``depth`` blocks behind."""
    sizes = frame.block_sizes
    blocks, devices = placement.blocks, placement.devices
    assignment, cache = placement.assignment, placement.cache
    n = len(blocks)
    pool = None
    if placement.kind != "serial":
        pool = device_pool.PoolRun(
            devices, assignment, prefetch.prefetch_depth() or 1,
            affinity=cache is not None,
        )
    # block-level fault tolerance (ops/fault_tolerance.py): None when
    # TFS_BLOCK_RETRIES=0 and no fault injection — the default — so the
    # suite's trace/compile fences stay deterministic.  Quarantine state
    # lives on the PoolRun.
    session = fault_tolerance.frame_session(
        frame.num_blocks, verb=work.name, pool=pool
    )

    def stage_ahead(k, device):
        bi = blocks[k]
        if bi in work.streams:
            return None  # streamed inline, chunk-level staging
        return work.stage(bi, frame.block(bi), device)

    if placement.kind == "pool" and not work.in_order:
        lanes = device_pool.lanes(devices, assignment, stage_ahead)
    elif placement.kind == "pool":
        # compute dispatch and readback still parallelize across the pool
        lanes = [
            prefetch.Prefetcher(
                lambda k: stage_ahead(k, devices[assignment[k]]),
                n,
                name="tfs-pool-stage",
            )
        ]
    elif placement.kind == "serial" and work.ahead:
        lanes = [prefetch.Prefetcher(lambda k: stage_ahead(k, None), n)]
    else:
        # affinity: the bytes are already where they run; serial with
        # nothing to stage ahead keeps the plain consumer loop
        lanes = []
    lane_iters = [iter(ln) for ln in lanes]
    lane_dead = [False] * len(lanes)
    # chunk-prefetcher stats accumulate here, NOT into a lane's stats:
    # the staging threads write those concurrently with this loop, and
    # += on a shared dict entry would lose updates
    chunk_stats = {"items": 0, "stage_s": 0.0, "wait_s": 0.0}

    def resolve(di):
        e = pool.effective_device(di)
        return e, devices[e]

    def attempt(bi, di, st, a, dev_i):
        ins = st.pop("staged", None)  # at most once, ever
        home = a == 0 and (pool is None or dev_i == di)
        if ins is None or not home:
            # only attempt 0 on the home device may read the shard;
            # every retry / quarantine redirect builds fresh buffers
            # from the authoritative host copy on the CURRENT device
            resident = st["resident"] if home else None
            st["hit"] = bool(resident)
            block = frame.block(bi)
            if resident:
                block = {**block, **resident}
            ins = work.stage(
                bi, block, devices[dev_i] if pool is not None else None
            )
        return work.run(bi, ins)

    to_host = pool is not None and work.to_host
    out: List[Optional[Dict[str, Any]]] = [None] * n if to_host else []
    hits = restaged = 0
    if times is not None:
        times.first_block()
    for k, bi in enumerate(blocks):
        # cooperative cancellation (bridge deadlines / drain): the block
        # boundary is the check granularity — one contextvar read when
        # no scope is active
        cancellation.checkpoint()
        n_rows = sizes[bi]
        di = assignment[k] if pool is not None else None
        sp = observability.span(
            work.span, "serial" if di is None else f"device/{di}",
            verb=work.name, block=bi, rows=n_rows, device=di or 0,
        )
        staged = None
        if lane_iters:
            li = di if len(lane_iters) > 1 else 0
            # a lane shared by every device names none when it dies (no
            # healthy device gets charged a failure it didn't cause),
            # and the serial lane has no device to fall back to: its
            # failure surfaces as it always did
            staged = lane_next(
                lane_iters[li], lane_dead, li,
                session if pool is not None else None,
                pool if len(lane_iters) > 1 else None,
            )
        dev_i = pool.effective_device(di) if pool is not None else None
        st: Dict[str, Any] = {
            "staged": staged, "resident": None, "hit": False,
        }
        del staged  # drop staged refs (donation hygiene)
        if bi in work.streams:
            outs = work.run_streamed(
                bi,
                devices[dev_i] if pool is not None else None,
                session,
                functools.partial(resolve, di)
                if session is not None and pool is not None
                else None,
                chunk_stats,
            )
        else:
            if cache is not None:
                shard = cache.shard(bi)
                if shard is not None:
                    st["resident"] = {
                        c: shard[c] for c in work.reads if c in shard
                    }
            if session is None:
                outs = attempt(bi, di, st, 0, dev_i)
            else:
                outs = session.run(
                    bi,
                    n_rows,
                    functools.partial(attempt, bi, di, st),
                    device=functools.partial(pool.effective_device, di)
                    if pool is not None
                    else 0,  # serial dispatch = device 0
                    oom_split=work.oom_split(
                        bi, session, devices, pool, di
                    ),
                )
        work.check(bi, outs)
        if pool is None:
            # request attribution (round 15): one contextvar read per
            # block when no ledger is active — the documented hot-path
            # cost of the attribution layer on the serial loop
            observability.note_request_block(0, n_rows)
            ns = sp.end()
            out.append(outs)
        else:
            if session is not None:
                dev_i = pool.effective_device(di)  # a quarantine may have moved it
            late = {"device": dev_i}
            if cache is not None:
                # whether the attempt that SUCCEEDED read the shard — a
                # retried block re-stages from host, and the hit counter
                # must not claim otherwise
                late["shard_hit"] = hit = st["hit"]
                if hit:
                    hits += 1
                    observability.note_cache_shard_hit()
                else:
                    restaged += 1
                    if session is not None and st["resident"]:
                        session.note_cache_restage()
            sp.track = f"device/{dev_i}"
            ns = sp.end(**late)
            if to_host:
                pool.submit(k, dev_i, n_rows, outs, out)
            else:
                pool.note_dispatch(dev_i, n_rows)
                # async hop to the combine device: one reduced cell per
                # base column
                out.append(
                    {
                        c: jax.device_put(v, devices[0])
                        for c, v in outs.items()
                    }
                )
        if times is not None:
            observability.note_dispatch_block(ns, work.params_resident(outs))
    if times is not None:
        times.last_block()
    if to_host:
        pool.finish(out)
    # the loop consumed every item, so the staging threads have finished
    # (their last stats write happened-before the last queue get): the
    # lanes' stats are safe to read and merge with the chunk totals
    stage_s = sum(ln.stats["stage_s"] for ln in lanes) + chunk_stats["stage_s"]
    wait_s = sum(ln.stats["wait_s"] for ln in lanes) + chunk_stats["wait_s"]
    if pool is not None:
        span.annotate("device_pool", pool.record(stage_s, wait_s))
    if cache is not None:
        fc = cache.record()
        fc["shard_hits"] = hits
        fc["restaged_blocks"] = restaged
        span.annotate("frame_cache", fc)
    if session is not None and session.events():
        span.annotate("fault_tolerance", session.record())
    # ``items`` counts buffers actually staged ahead: whole blocks a lane
    # staged plus streamed chunks — never the trivial None passes for
    # streamed blocks
    staged_ahead = (n - len(work.streams)) if lanes else 0
    return out, (staged_ahead + chunk_stats["items"], stage_s, wait_s)
