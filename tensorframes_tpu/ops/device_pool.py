"""Block-parallel device-pool scheduler: independent blocks across chips.

The reference's core scaling story is data parallelism over partitions —
one tensor program per Spark partition, in parallel across executors, with
a driver-coordinated pairwise reduce (SURVEY §2.7 P1/P4).  The engine so
far had the two extremes: the single-device :class:`~tensorframes_tpu.ops.
engine.Executor` walks blocks serially on one chip, and the GSPMD
``MeshExecutor`` fuses the whole frame into one logical block.  This module
supplies the embarrassingly parallel middle — the paper's native mode — for
a multi-chip HOST: blocks of a frame are scheduled across
``jax.local_devices()`` with

* **deterministic least-loaded assignment** (:func:`assign`): blocks are
  assigned in block order to the device with the fewest assigned rows
  (ties -> lowest device index), so the plan depends only on the block
  sizes, never on runtime completion order;
* **per-device prefetch lanes** (:func:`lanes`): one
  :class:`~tensorframes_tpu.ops.prefetch.Prefetcher` per device stages
  that device's blocks in order — host cast + ``host_stage`` + async
  ``device_put`` *to its target device* — so block N+1's transfer for a
  device overlaps block N's compute on the same device, and the lanes of
  different devices stage concurrently.  The donation rules are inherited
  unchanged from the prefetch contract: freshly staged buffers donate,
  device-resident/cached columns never reach the pool (the engine only
  pools host-fresh frames);
* **bounded in-flight windows + overlapped D2H readback**
  (:class:`PoolRun`): a dispatched block's outputs start their async
  device->host copy immediately (``copy_to_host_async``), and at most
  ``depth`` blocks per device stay un-materialised — output assembly
  overlaps later blocks' compute instead of paying one serial readback at
  the end, and steady-state HBM per device stays bounded.

Order guarantees are bit-exact: outputs are reassembled by block index
(never completion order), and the reduce verbs compute per-block partials
on their assigned devices but bring ALL partials back to one device and
run the exact single-device combine — the fold shape is identical to the
serial path, so results match bit for bit regardless of which device
finished first.

Knobs:

* ``TFS_DEVICE_POOL`` — ``auto`` (default: all local devices; the pool
  only engages when there are >= 2), an integer N (first N local
  devices; ``0``/``1``/``off`` disable the pool), read per verb call so
  bench A/B legs and tests can toggle it mid-process.
* ``TFS_PREFETCH_BLOCKS`` — reused as both the per-lane staging depth and
  the per-device in-flight readback window (``0`` stages synchronously
  and keeps a window of 1 — the "overlap off" baseline).

Scope, by design: the pool engages for host-fresh multi-block frames on
the plain ``Executor`` only.  ``MeshExecutor`` keeps its GSPMD semantics
(``supports_device_pool = False``); device-resident (cached) frames stay
on their device — splitting a cached column across the pool would turn
every verb into a cross-device shuffle; ``aggregate`` keeps its
single-device paths (the segment fast path is already ONE fused dispatch,
and splitting its global key sort would change the reduction order);
fused row-terminal pipelines stay one dispatch (their combine shape IS
the executable).  Map-terminal pipelines pool per block
(``ops/pipeline.py``).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .. import observability, resilience
from .. import envutil
from . import fault_tolerance, prefetch

logger = logging.getLogger("tensorframes_tpu.device_pool")

ENV_VAR = "TFS_DEVICE_POOL"

# the exception classes a failed ``copy_to_host_async`` may legitimately
# raise (backend lacks the method's semantics, buffer already on host,
# runtime refused the async copy): jax runtime errors plus the plain
# RuntimeError/NotImplementedError some PJRT clients use.  Narrow by
# design — a TypeError here is a bug and must propagate.
_COPY_FALLBACK_TYPES = (
    RuntimeError,
    NotImplementedError,
) + resilience._RUNTIME_TYPES

_warned: set = set()


def _warn_once(raw: str) -> None:
    if raw not in _warned:
        _warned.add(raw)
        logger.warning(
            "%s=%r is malformed; use 'auto', an integer device count, or "
            "'0'/'off' to disable. Falling back to 'auto'.",
            ENV_VAR,
            raw,
        )


def pool_devices() -> List[Any]:
    """The resolved device pool, or ``[]`` when pooling is disabled.

    ``auto``/unset: all ``jax.local_devices()`` (empty unless >= 2 —
    a one-device pool is just the serial path).  An integer caps the
    pool at the first N local devices; ``0``/``1``/``off`` disable it.
    Read per call: the knob toggles mid-process (bench legs, tests)."""
    import jax

    raw = envutil.env_raw(ENV_VAR, "auto").lower()
    if raw in ("0", "1", "off", "none", "false"):
        return []
    if raw in ("", "auto", "all"):
        n = None
    else:
        try:
            n = int(raw)
        except ValueError:
            _warn_once(raw)
            n = None
        else:
            if n <= 1:
                return []
    devs = list(jax.local_devices())
    if n is not None:
        devs = devs[: min(n, len(devs))]
    return devs if len(devs) >= 2 else []


def enabled() -> bool:
    """Whether the pool would engage (>= 2 resolved devices)."""
    return len(pool_devices()) >= 2


# process-wide quarantine memory (round 11): quarantine decisions live on
# each PoolRun, but a serving front-end needs to report "this host has a
# sick chip" across requests — every quarantine event also lands here so
# the bridge's health RPC can expose it.  Advisory/observational only:
# scheduling always consults the CURRENT run's own failure counts.
_quarantine_history: set = set()
_quarantine_lock = threading.Lock()


def recently_quarantined() -> List[int]:
    """Device indices any PoolRun quarantined since process start (or
    the last :func:`reset_quarantine_history`) — the health-RPC view of
    chip sickness on this host."""
    with _quarantine_lock:
        return sorted(_quarantine_history)


def reset_quarantine_history() -> None:
    """Clear the advisory quarantine history (tests; an operator's
    "I swapped the chip" acknowledgement)."""
    with _quarantine_lock:
        _quarantine_history.clear()


def assign(block_sizes: Sequence[int], n_devices: int) -> List[int]:
    """Deterministic least-loaded assignment: block index -> device index.

    Blocks are placed in block order on the device with the fewest
    assigned ROWS so far (ties -> lowest device index) — round-robin for
    equal blocks, row-balanced for skewed ones.  Depends only on the
    sizes, so the same frame always produces the same plan (the order
    guarantee the readback assembly relies on)."""
    loads = [0] * n_devices
    out: List[int] = []
    for sz in block_sizes:
        di = min(range(n_devices), key=lambda k: (loads[k], k))
        out.append(di)
        loads[di] += max(int(sz), 1)  # empty blocks still cost a dispatch
    return out


def lanes(
    devices: Sequence[Any],
    assignment: Sequence[int],
    stage_block: Callable[[int, Any], Any],
    name: str = "tfs-pool",
) -> List[prefetch.Prefetcher]:
    """One staging-lane :class:`~tensorframes_tpu.ops.prefetch.Prefetcher`
    per device: lane ``di`` stages the blocks assigned to device ``di`` in
    block order, calling ``stage_block(bi, device)`` on its worker thread.

    The consumer must pull via ``iter(lane)`` in GLOBAL block order —
    each lane yields its own blocks in ascending block index, and the
    global order visits every device's blocks in that same relative
    order, so ``next(lane_iters[assignment[bi]])`` always returns block
    ``bi``'s staged value.  The Prefetcher contract carries over: no jit
    entry points in ``stage_block`` (``device_put``/numpy/host_stage
    only)."""
    per_dev = [
        [bi for bi, d in enumerate(assignment) if d == di]
        for di in range(len(devices))
    ]
    out = []
    for di, dev in enumerate(devices):
        blocks_di = per_dev[di]

        def _stage(k, _blocks=blocks_di, _dev=dev):
            return stage_block(_blocks[k], _dev)

        out.append(
            prefetch.Prefetcher(
                _stage, len(blocks_di), name=f"{name}-d{di}"
            )
        )
    return out


class PoolRun:
    """One verb invocation's pool bookkeeping: per-device in-flight
    readback windows plus the scheduler stats a verb span records.

    ``submit(bi, di, n_rows, outs, out_blocks)`` notes the dispatch,
    starts the outputs' async device->host copies, and — once device
    ``di`` has more than ``depth`` un-materialised blocks — materialises
    the oldest into ``out_blocks[bi]`` (host numpy).  ``finish`` drains
    the remaining windows.  Assembly is always by block index."""

    def __init__(
        self,
        devices: Sequence[Any],
        assignment: Sequence[int],
        depth: int,
        affinity: bool = False,
    ):
        self.devices = list(devices)
        self.assignment = list(assignment)
        self.depth = max(1, int(depth))
        # affinity runs (sharded frame cache, round 10) dispatch blocks
        # on the device already holding their data: no staging lanes, so
        # stage_s/overlap stats read 0 by design — the flag keeps span
        # consumers from mistaking that for a dead prefetcher
        self.affinity = bool(affinity)
        n = len(self.devices)
        self._window: List[List] = [[] for _ in range(n)]
        self.blocks = [0] * n
        self.rows = [0] * n
        self._first_dispatch: List[Optional[float]] = [None] * n
        self._last_done: List[Optional[float]] = [None] * n
        self.drain_s = 0.0
        self._t0 = time.perf_counter()
        # fault tolerance (round 9): per-device transient-failure counts
        # and the quarantine set the retry layer consults
        # (ops/fault_tolerance.py); the threshold is captured once so a
        # mid-run env flip cannot split one run's policy
        self.failures = [0] * n
        self.quarantined: set = set()
        self._quarantine_after = fault_tolerance.quarantine_after()
        self._copy_warned = False

    # -- fault tolerance -----------------------------------------------------

    def note_block_failure(self, di: int) -> bool:
        """Record one transient dispatch failure on device ``di``;
        returns True when this failure newly quarantines the device.
        A quarantined device receives no further blocks this run —
        :meth:`effective_device` redirects them to healthy devices
        (Spark's executor blacklisting, at pool scope)."""
        self.failures[di] += 1
        if di in self.quarantined:
            return False
        if self.failures[di] < self._quarantine_after:
            return False
        self.quarantined.add(di)
        with _quarantine_lock:
            _quarantine_history.add(di)
        observability.note_device_quarantined()
        observability.instant(
            "pool.quarantine", "faults", device=di,
            failures=self.failures[di],
        )
        healthy = len(self.devices) - len(self.quarantined)
        logger.warning(
            "device %d quarantined after %d transient failures; "
            "re-dispatching its blocks across %d healthy device(s)%s",
            di,
            self.failures[di],
            healthy,
            " (pool degraded to the serial path)" if healthy <= 1 else "",
        )
        return True

    def effective_device(self, di: int) -> int:
        """The device index block work assigned to ``di`` should actually
        dispatch to: ``di`` while healthy, else the least-loaded healthy
        device (deterministic: ties to the lowest index).  With one
        healthy device left this is, by construction, the serial path on
        that device; with none left the frame fails loudly."""
        if di not in self.quarantined:
            return di
        healthy = [
            k for k in range(len(self.devices)) if k not in self.quarantined
        ]
        if not healthy:
            raise fault_tolerance.BlockExecutionError(
                f"device pool: all {len(self.devices)} devices are "
                f"quarantined (failure counts: {self.failures}); no "
                f"healthy device remains to re-dispatch blocks"
            )
        return min(healthy, key=lambda k: (self.rows[k], k))

    # -- dispatch/readback ---------------------------------------------------

    def note_dispatch(self, di: int, n_rows: int) -> None:
        """Record one block dispatched to device ``di`` (used directly by
        the reduce verbs, whose partials stay on device instead of going
        through the readback window).  The device index and row count
        ride into the active request's ledger (round 15) so per-request
        attribution carries blocks-per-device."""
        observability.note_pool_dispatch(di, n_rows)
        if self._first_dispatch[di] is None:
            self._first_dispatch[di] = time.perf_counter()
        self.blocks[di] += 1
        self.rows[di] += int(n_rows)

    def submit(
        self,
        bi: int,
        di: int,
        n_rows: int,
        outs: Dict[str, Any],
        out_blocks: List[Optional[Dict[str, np.ndarray]]],
    ) -> None:
        self.note_dispatch(di, n_rows)
        for v in outs.values():
            # overlapped D2H: the copy rides the link while later blocks
            # compute; np.asarray below then mostly finds the bytes ready
            copy = getattr(v, "copy_to_host_async", None)
            if copy is not None:
                try:
                    copy()
                except _COPY_FALLBACK_TYPES as e:
                    # readback still happens synchronously below — but a
                    # swallowed failure is a lost overlap, so it is
                    # counted (pool_copy_fallbacks) and logged once per
                    # run; anything outside the expected runtime-error
                    # types propagates (a TypeError here is a bug, not a
                    # backend quirk)
                    observability.note_pool_copy_fallback()
                    if not self._copy_warned:
                        self._copy_warned = True
                        logger.warning(
                            "copy_to_host_async failed (%s: %s); falling "
                            "back to synchronous readback for this run "
                            "(counted in pool_copy_fallbacks)",
                            type(e).__name__,
                            e,
                        )
        self._window[di].append((bi, outs))
        while len(self._window[di]) > self.depth:
            self._materialize(di, out_blocks)

    def _materialize(self, di: int, out_blocks) -> None:
        bi, outs = self._window[di].pop(0)
        # the D2H materialisation is where a pooled block actually
        # syncs: the span shows per-device readback overlap, the counter
        # how long the dispatching thread stood blocked in it
        with observability.span(
            "pool.readback", f"device/{di}", block=bi, device=di
        ) as sp:
            out_blocks[bi] = {k: np.asarray(v) for k, v in outs.items()}
        observability.note_readback(
            sum(int(v.nbytes) for v in out_blocks[bi].values()), sp.ns
        )
        self.drain_s += sp.ns / 1e9
        self._last_done[di] = time.perf_counter()

    def finish(self, out_blocks) -> None:
        for di in range(len(self.devices)):
            while self._window[di]:
                self._materialize(di, out_blocks)

    # -- stats ---------------------------------------------------------------

    def record(self, stage_s: float = 0.0, wait_s: float = 0.0) -> dict:
        """Scheduler observability for the verb span (and, via the span,
        for bench records): per-device blocks/rows, wall-clock occupancy
        (fraction of the verb's pool wall time the device had dispatched
        work in flight — an estimate from dispatch/materialise
        timestamps, no extra device syncs) and idle time, plus the lane
        staging totals and the overlap ratio they imply."""
        wall = max(time.perf_counter() - self._t0, 1e-9)
        occupancy, idle_s = [], []
        for di in range(len(self.devices)):
            t_first = self._first_dispatch[di]
            if t_first is None:
                occupancy.append(0.0)
                idle_s.append(round(wall, 6))
                continue
            t_done = self._last_done[di] or time.perf_counter()
            busy = max(0.0, t_done - t_first)
            occupancy.append(round(min(1.0, busy / wall), 4))
            idle_s.append(round(max(0.0, wall - busy), 6))
        rec = {
            "devices": len(self.devices),
            "depth": self.depth,
            "blocks_per_device": list(self.blocks),
            "rows_per_device": list(self.rows),
            "occupancy": occupancy,
            "idle_s": idle_s,
            "drain_s": round(self.drain_s, 6),
            "stage_s": round(stage_s, 6),
            "wait_s": round(wait_s, 6),
            "overlap_ratio": round(
                prefetch.overlap_ratio(stage_s, wait_s), 4
            ),
            "wall_s": round(wall, 6),
        }
        if self.affinity:
            rec["affinity"] = True
        if any(self.failures):
            rec["failures_per_device"] = list(self.failures)
            rec["quarantined_devices"] = sorted(self.quarantined)
        return rec
