"""Async block ingestion: double-buffered host->device prefetch.

The eager verbs already dispatch asynchronously (``device_put`` and jitted
execution both return before the work finishes), but the HOST side of block
ingestion — the dtype cast, the ``host_stage`` preprocessing, the act of
*issuing* the next transfer — still ran serially with the verb loop: block
N+1's bytes only started moving once every host-side step of block N had
run.  On a transfer-bound link (BENCH_r05: h2d 16.37 s/block against
0.154 s of compute) any host gap between transfers is throughput lost.

:class:`Prefetcher` closes the gap with the standard TPU input-pipeline
discipline:

* a single worker thread stages up to ``depth`` blocks ahead of the
  consumer — host cast + ``host_stage`` + ``jax.device_put`` all happen on
  the worker, so transfers queue back-to-back on the link while the
  consumer's compute dispatches run;
* the window is bounded (default 2 = double buffering), so at most
  ``depth`` staged input blocks exist at once;
* with **donation** (``donate_argnums`` on the consuming executable, see
  :func:`donate_inputs`) XLA reuses each staged input buffer for the
  block's outputs, so steady-state HBM holds <= ``depth`` input blocks no
  matter how many blocks the frame has.

Donation safety contract (the "no use-after-donate" rule): a donated
executable invalidates its input buffers, so ONLY buffers the engine
itself freshly staged for exactly one program application may flow
through a donating entry.  Device-resident frame columns (``cache()``-d
frames, chained verb outputs) are shared state and must never be donated
— the engine checks residency per block and routes shared buffers through
the non-donating executable.  Staged buffers are handed to the donating
executable exactly once and the reference is dropped immediately after.

Shape-canonical staging (round 7, ``ops/bucketing.py``): when block
bucketing applies, the engine's stage functions pad the row axis ON THE
HOST before the ``device_put``, so the staged buffer already carries the
padded signature the (single, shared) executable expects — the transfer
moves the padded bytes and no device-side reshape sits between staging
and dispatch.  Padded staged buffers remain donation-eligible: they are
fresh per block by construction, pad rows included, and the donating
executable consumes exactly the padded shape it was compiled for.

Knobs:

* ``TFS_PREFETCH_BLOCKS`` — staging window depth (default 2; ``0``
  disables the worker thread and stages synchronously, the pre-round-6
  behavior).
* ``TFS_DONATE`` — ``auto`` (default: donate on backends that implement
  buffer donation, i.e. TPU/GPU), ``1`` (force, e.g. to exercise the
  donated code path on CPU where jax warns and ignores the donation), or
  ``0`` (never donate).

The per-verb stats (:attr:`Prefetcher.stats`) record how much of the
staging wall time was hidden behind compute; the engine attaches them to
the verb span (``observability``) and ``bench.py`` reports the overlap
ratio for the streaming-ingestion leg.

Device-pool composition (round 8, ``ops/device_pool.py``): the pool
scheduler runs ONE Prefetcher per local device — each lane stages its
device's blocks in block order with ``device_put`` pointed at that
device (``name="tfs-pool-d<k>"``), and the donation contract above
carries over unchanged because only host-fresh frames ever pool.
"""

from __future__ import annotations

import contextvars
import queue
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from .. import observability
from .. import envutil

DEFAULT_DEPTH = 2


class StagingError(RuntimeError):
    """A prefetch worker's staging callable failed.

    The message names the failing item index and the lane
    (``raise ... from e`` keeps the original as ``__cause__``), so a
    mid-stream staging failure points at a BLOCK instead of surfacing
    as a bare queue-crossed exception with no context.  Program-contract
    errors (``ValidationError``) pass through unwrapped — they already
    carry their own diagnosis and callers assert on their type.
    ``resilience.FailureDetector`` classifies a StagingError by walking
    its cause, so a transient transfer failure stays retryable."""

# backends whose PJRT client implements input-buffer donation; elsewhere
# jax warns ("Some donated buffers were not usable") and copies instead
_DONATING_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def prefetch_depth() -> int:
    """The staging window depth from ``TFS_PREFETCH_BLOCKS`` (>=0)."""
    raw = envutil.env_raw("TFS_PREFETCH_BLOCKS")
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_DEPTH


def overlap_ratio(stage_s: float, wait_s: float) -> float:
    """Fraction of staging wall time the consumer did NOT wait for —
    1.0 means every transfer was fully hidden behind the consumer's own
    work, 0.0 means fully serial (the synchronous baseline).  The one
    definition both :class:`Prefetcher` and the engine's merged
    block+chunk span stats report."""
    if stage_s <= 0.0:
        return 0.0
    return max(0.0, min(1.0, 1.0 - wait_s / stage_s))


def donate_inputs() -> bool:
    """Whether freshly staged input buffers should be donated to the
    consuming executable (``TFS_DONATE``; ``auto`` = backend supports
    donation)."""
    raw = envutil.env_raw("TFS_DONATE", "auto").lower()
    if raw in ("1", "true", "yes"):
        return True
    if raw in ("0", "false", "no"):
        return False
    return jax.default_backend() in _DONATING_BACKENDS


class Prefetcher:
    """Iterate staged values with up to ``depth`` items in flight.

    ``stage(i)`` runs on the worker thread and must return the staged
    (typically device-resident) value for item ``i`` — e.g. a dict of
    arrays created by ``jax.device_put`` (async: the call returns while
    the DMA is in flight).  ``stage`` must not trace/compile jax programs
    (keep all jit entry points on the consumer thread); ``device_put``,
    numpy work, and host_stage functions are safe and are exactly the
    work worth overlapping.

    Items are yielded strictly in order.  A ``stage`` exception is
    re-raised at the consumer's matching ``next()``.  ``stats`` holds
    ``{"items", "depth", "stage_s", "wait_s"}`` where ``stage_s`` is
    total worker staging wall time and ``wait_s`` is total consumer time
    blocked waiting for a staged item; :meth:`overlap_ratio` is the
    fraction of staging time hidden behind the consumer's own work.

    ``num_items=None`` (round 12, the streaming window reader): the item
    count is unknown upfront — ``stage(i)`` is called for ``i = 0, 1,
    ...`` until it raises ``StopIteration``, which ends the iteration
    cleanly (the windowed reader pulls from an unbounded Arrow batch
    source, so only the source knows when it is dry).  ``stats["items"]``
    then counts the items actually staged.
    """

    def __init__(
        self,
        stage: Callable[[int], Any],
        num_items: Optional[int],
        depth: Optional[int] = None,
        name: str = "tfs-prefetch",
    ):
        self._stage = stage
        self._n = None if num_items is None else int(num_items)
        self._depth = prefetch_depth() if depth is None else max(0, depth)
        # thread name: the device-pool scheduler runs one lane per device
        # ("tfs-pool-d<k>"), and distinguishable names matter in py-spy /
        # profiler dumps when several lanes stage concurrently
        self._name = name
        self.stats: Dict[str, Any] = {
            "items": 0 if self._n is None else self._n,
            "depth": self._depth,
            "stage_s": 0.0,
            "wait_s": 0.0,
        }

    def overlap_ratio(self) -> float:
        """:func:`overlap_ratio` over this prefetcher's own stats."""
        return overlap_ratio(self.stats["stage_s"], self.stats["wait_s"])

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        if self._depth <= 0 or (self._n is not None and self._n <= 1):
            # synchronous fallback: stage inline on the consumer thread
            i = 0
            while self._n is None or i < self._n:
                # one span per staged item on this lane's track
                # (synchronous path: staging == waiting)
                sp = observability.span(
                    "stage.block", f"lane/{self._name}",
                    block=i, lane=self._name,
                )
                try:
                    v = self._stage(i)
                except StopIteration as e:
                    if self._n is not None:
                        # a BOUNDED stage running dry early is a bug in
                        # the stage, not clean exhaustion — silently
                        # truncating would hand the consumer a short
                        # frame with no diagnosis
                        raise StagingError(
                            f"{self._name}: staging item {i} raised "
                            f"StopIteration before the declared "
                            f"{self._n} items"
                        ) from e
                    return  # unbounded source exhausted
                dt = sp.end() / 1e9
                self.stats["stage_s"] += dt
                self.stats["wait_s"] += dt
                if self._n is None:
                    self.stats["items"] += 1
                yield v
                i += 1
            return
        yield from self._iter_threaded()

    def _iter_threaded(self):
        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        stop = threading.Event()
        end = object()  # unbounded-mode exhaustion sentinel

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            i = 0
            try:
                while self._n is None or i < self._n:
                    if stop.is_set():
                        return
                    # the staging timeline per lane: the H2D/compute
                    # overlap half of the trace
                    sp = observability.span(
                        "stage.block", f"lane/{self._name}",
                        block=i, lane=self._name,
                    )
                    try:
                        v = self._stage(i)
                    except StopIteration:
                        if self._n is not None:
                            # bounded mode: early exhaustion is a stage
                            # bug — re-raise so the outer handler ships
                            # the error sentinel (the consumer would
                            # otherwise block on the queue forever)
                            raise
                        break  # unbounded source exhausted
                    self.stats["stage_s"] += sp.end() / 1e9
                    if self._n is None:
                        self.stats["items"] += 1
                    if not put((v, None)):
                        return
                    i += 1
                if self._n is None:
                    put((end, None))
            except BaseException as e:  # propagate to the consumer,
                # tagged with the failing item so the consumer can
                # re-raise with block context (StagingError)
                put((None, (i, e)))

        # request-scoped telemetry (round 15): the worker runs under a
        # COPY of the consumer thread's context, so counter bumps made
        # while staging (``note_h2d_bytes`` inside ``device_put`` paths)
        # and the lane's trace events are attributed to the request that
        # staged them — without this, a ledger's h2d accounting would
        # miss exactly the bytes the staging lanes move.  Cancellation
        # semantics are unchanged: staging code never calls
        # ``cancellation.checkpoint()``, so the copied scope is inert.
        ctx = contextvars.copy_context()
        t = threading.Thread(
            target=lambda: ctx.run(worker), name=self._name, daemon=True
        )
        t.start()
        try:
            produced = 0
            while self._n is None or produced < self._n:
                t0 = time.perf_counter()
                v, err = q.get()
                self.stats["wait_s"] += time.perf_counter() - t0
                if err is not None:
                    i, e = err
                    from .validation import ValidationError

                    if isinstance(e, ValidationError):
                        # program-contract errors keep their type (the
                        # verb API's documented error surface)
                        raise e
                    raise StagingError(
                        f"{self._name}: staging block {i} failed: "
                        f"{type(e).__name__}: {e}"
                    ) from e
                if v is end:
                    return
                yield v
                produced += 1
        finally:
            stop.set()
            # unblock a worker stuck on a full queue, then reap it
            while t.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)


def stage_columns(
    cols: Dict[str, Any], device=None
) -> Dict[str, jax.Array]:
    """Issue one async ``device_put`` per host column, back to back, so
    the per-column transfers of a multi-column frame queue on the link
    together instead of being issued lazily by the consuming jit call.
    Device-resident values pass through untouched."""
    staged = {}
    for name, arr in cols.items():
        if isinstance(arr, jax.Array):
            staged[name] = arr
        else:
            host = np.asarray(arr)
            observability.note_h2d_bytes(host.nbytes)
            staged[name] = jax.device_put(host, device)
    return staged
