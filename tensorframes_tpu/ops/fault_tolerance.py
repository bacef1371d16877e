"""Block-level fault tolerance: per-block retry, quarantine, OOM policy.

The reference recovers at the *partition*: a failed Spark task replays
its partition from RDD lineage (SURVEY.md §5) and a flaky executor gets
blacklisted by the scheduler.  Our data plane's unit of work is the
block, and there is no lineage — the source block is still on the host,
so recovery is re-dispatch.  This module is the policy layer the
execution stack (``engine.py``, ``device_pool.py``, ``pipeline.py``)
threads through every block dispatch:

* **per-block retry** (:class:`FrameRetrySession`): a transient failure
  (classified by the SAME ``resilience.FailureDetector`` the step driver
  uses — one classifier, no drift) re-stages and re-dispatches the block
  with exponential backoff.  Two budgets bound it: ``TFS_BLOCK_RETRIES``
  retries per block, and a per-frame total (retries x blocks) metered by
  the shared detector, so a frame-wide brownout cannot retry forever.
  Exhaustion raises ``RestartBudgetExceeded`` carrying the LAST real
  error (``from exc``), never a bare budget message.
* **device quarantine**: pooled dispatches report transient failures to
  their :class:`~tensorframes_tpu.ops.device_pool.PoolRun`; after
  ``TFS_QUARANTINE_AFTER`` failures a device is drained — its remaining
  blocks re-dispatch to the least-loaded healthy device.  Reassembly is
  by block index, so redirection cannot change results; a pool degraded
  to one healthy device is, by construction, the serial path on that
  device.
* **OOM degradation**: a ``RESOURCE_EXHAUSTED`` on a map-verb block
  whose program passes the jaxpr row-independence proof splits the block
  in half recursively (floor ``TFS_MIN_SPLIT_ROWS``) and re-dispatches
  the halves — row independence makes the concatenated halves
  bit-identical to the whole-block dispatch.  Cross-row programs (and
  trimmed / host-staged blocks) surface a
  :class:`BlockExecutionError` naming the block and row range instead.

The retry contract: **retries never change results.**  Every re-dispatch
re-stages fresh buffers from the host frame (a donated-then-failed
buffer is never re-used — the no-use-after-donate rule survives
failures), runs the same executable, and lands in the same block slot.
Tests pin ``TFS_BLOCK_RETRIES=0`` (conftest) so trace-count fences stay
deterministic; the chaos tier turns the knobs on.

Streaming composition (round 12, ``tensorframes_tpu/streaming/``): the
out-of-core verbs run each window through the engine unchanged, so
every window's verb call builds its OWN :class:`FrameRetrySession` via
:func:`frame_session`.  That per-window scoping is deliberate: the
``retries x blocks`` frame budget bounds recovery *per window* — the
unit whose source bytes are still at hand — rather than amortising one
budget over an unbounded stream (where any fixed budget would either
exhaust arbitrarily early or never bind).  It is the same shape as
Spark's per-task retry budgets over a long job, and it keeps a
mid-stream brownout from poisoning windows that have not arrived yet.
Cancellation still preempts everything: a deadline that fires during a
window's retries surfaces at the next attempt checkpoint and the sink
stays at a window boundary (docs/RESILIENCE.md).

Knobs:

* ``TFS_BLOCK_RETRIES`` — retries per block (default 2; 0 disables the
  whole layer unless fault injection is active).
* ``TFS_BLOCK_BACKOFF_S`` — base backoff between block retries
  (default 0.05; block retries are cheap re-dispatches, not process
  restarts, so the base is far below ``FailureDetector``'s 1 s default).
* ``TFS_MIN_SPLIT_ROWS`` — OOM split floor (default 16): a range
  smaller than twice the floor never splits further.
* ``TFS_QUARANTINE_AFTER`` — transient failures before a pool device is
  drained (default 3).
* ``TFS_FAULT_INJECT`` — the deterministic fault-injection plan
  (``tensorframes_tpu/faults.py``).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Optional, Tuple

from .. import cancellation, faults, observability, resilience
from ..envutil import env_float as _env_float, env_int as _env_int

logger = logging.getLogger("tensorframes_tpu.fault_tolerance")

ENV_RETRIES = "TFS_BLOCK_RETRIES"
ENV_BACKOFF = "TFS_BLOCK_BACKOFF_S"
ENV_MIN_SPLIT = "TFS_MIN_SPLIT_ROWS"
ENV_QUARANTINE = "TFS_QUARANTINE_AFTER"

DEFAULT_RETRIES = 2
DEFAULT_BACKOFF_S = 0.05
DEFAULT_MIN_SPLIT_ROWS = 16
DEFAULT_QUARANTINE_AFTER = 3


def block_retries() -> int:
    """Retries per block dispatch (``TFS_BLOCK_RETRIES``, >= 0)."""
    return _env_int(ENV_RETRIES, DEFAULT_RETRIES)


def block_backoff_s() -> float:
    """Base backoff between block retries (``TFS_BLOCK_BACKOFF_S``)."""
    return _env_float(ENV_BACKOFF, DEFAULT_BACKOFF_S)


def min_split_rows() -> int:
    """OOM-degradation split floor (``TFS_MIN_SPLIT_ROWS``, >= 1)."""
    return _env_int(ENV_MIN_SPLIT, DEFAULT_MIN_SPLIT_ROWS, floor=1)


def quarantine_after() -> int:
    """Transient failures before a pool device drains
    (``TFS_QUARANTINE_AFTER``, >= 1)."""
    return _env_int(ENV_QUARANTINE, DEFAULT_QUARANTINE_AFTER, floor=1)


class BlockExecutionError(RuntimeError):
    """A block's dispatch failed irrecoverably; the message names the
    block index and row range so a frame-scale failure points at data."""


def frame_session(
    num_blocks: int, verb: str = "", pool=None
) -> Optional["FrameRetrySession"]:
    """A :class:`FrameRetrySession` for one verb invocation, or ``None``
    when the layer is fully off (``TFS_BLOCK_RETRIES=0`` and no fault
    injection) — the None fast path keeps the default dispatch loops
    byte-for-byte identical to the pre-round-9 engine, which is what the
    suite's trace/compile fences pin."""
    retries = block_retries()
    if retries <= 0 and not faults.active():
        return None
    return FrameRetrySession(num_blocks, retries, verb=verb, pool=pool)


class FrameRetrySession:
    """One verb invocation's retry bookkeeping: the per-block attempt
    loop, the shared per-frame detector budget, quarantine reporting,
    and the counters the verb span records."""

    def __init__(
        self,
        num_blocks: int,
        retries: Optional[int] = None,
        verb: str = "",
        pool=None,
        detector: Optional[resilience.FailureDetector] = None,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.per_block = block_retries() if retries is None else int(retries)
        self.verb = verb
        self.pool = pool
        # ONE detector per frame: classification lives in resilience (no
        # duplicated tables) and its restart budget is the frame-level
        # bound — per_block retries for every block is the ceiling
        self.detector = detector or resilience.FailureDetector(
            max_restarts=max(self.per_block, 1) * max(num_blocks, 1),
            backoff_s=block_backoff_s(),
        )
        self._sleep = sleep
        self.retries = 0
        self.oom_splits = 0
        # sharded-cache recovery (round 10): blocks whose resident shard
        # could not be used (home device quarantined / shard evicted
        # mid-run) and were rebuilt from the authoritative host copy
        self.cache_restages = 0

    # -- per-block loop ------------------------------------------------------

    def run(
        self,
        bi: int,
        n_rows: int,
        attempt_fn: Callable[[int, Optional[int]], Any],
        device=None,
        oom_split: Optional[Callable[[BaseException], Any]] = None,
        row_range: Optional[Tuple[int, int]] = None,
    ):
        """Run ``attempt_fn(attempt, device_index)`` for block ``bi``
        with injection, classification, backoff, and budgets applied.

        ``attempt_fn`` MUST re-stage its inputs on every attempt past the
        first (the donation-safety half of the retry contract: a buffer
        handed to a donating executable is dead whether the dispatch
        succeeded or not).  ``device`` is an int pool-device index, a
        zero-arg callable returning the current effective index (the
        quarantine-aware pools pass this), or None.  ``oom_split`` is the
        verb's degradation closure: called with the OOM exception, it
        either returns the block's outputs computed from split
        sub-ranges or raises :class:`BlockExecutionError`.
        """
        lo, hi = row_range if row_range is not None else (0, n_rows)
        attempt = 0
        while True:
            # cooperative cancellation: every attempt (first try and
            # every retry) is a checkpoint, so a request whose deadline
            # passed during a block's compute or backoff sleep surfaces
            # DeadlineExceeded here instead of burning retry budget
            cancellation.checkpoint()
            dev_i = device() if callable(device) else device
            try:
                faults.maybe_inject(bi, attempt, dev_i, n_rows)
                return attempt_fn(attempt, dev_i)
            except BaseException as exc:  # noqa: BLE001 - classified below
                if isinstance(exc, cancellation.Cancelled):
                    raise  # a cancel is an instruction, not a failure
                if faults.is_oom(exc):
                    if oom_split is not None:
                        return oom_split(exc)
                    raise BlockExecutionError(
                        f"{self.verb}: block {bi} rows [{lo}, {hi}) "
                        f"exhausted device memory and this dispatch "
                        f"cannot degrade by splitting ({exc})"
                    ) from exc
                if not self.detector.is_transient(exc):
                    raise
                if self.pool is not None and dev_i is not None:
                    # quarantine decisions must see every failure,
                    # including the one that exhausts the budget
                    self.pool.note_block_failure(dev_i)
                if attempt >= self.per_block:
                    if self.per_block <= 0:
                        raise  # retries pinned off: surface untouched
                    raise resilience.RestartBudgetExceeded(
                        f"{self.verb}: block {bi} rows [{lo}, {hi}) failed "
                        f"{attempt + 1} times ({ENV_RETRIES}="
                        f"{self.per_block}); last error: {exc!r}"
                    ) from exc
                delay = self.detector.on_failure(exc)
                # the detector's exponent grows with FRAME-cumulative
                # restarts (right for one restarted step, wrong for many
                # independent blocks: unrelated blocks would inherit each
                # other's backoff).  Bound the sleep by the BLOCK's own
                # attempt index — per-task backoff, Spark-style — while
                # the detector keeps metering the frame budget.
                delay = min(
                    delay,
                    self.detector.backoff_s
                    * self.detector.backoff_factor ** attempt,
                )
                self.retries += 1
                observability.note_block_retry()
                observability.instant(
                    "engine.retry",
                    "faults",
                    verb=self.verb,
                    block=bi,
                    attempt=attempt + 1,
                    device=dev_i,
                )
                logger.warning(
                    "%s: block %d (device %s) transient failure, retry "
                    "%d/%d after %.3fs: %r",
                    self.verb,
                    bi,
                    dev_i,
                    attempt + 1,
                    self.per_block,
                    delay,
                    exc,
                )
                # never sleep a backoff for a request that is already
                # cancelled / past deadline (the loop-top checkpoint
                # would catch it anyway, but only after the sleep)
                cancellation.checkpoint()
                self._sleep(delay)
                attempt += 1

    # -- accounting ----------------------------------------------------------

    def note_split(self, bi: int) -> None:
        """One binary OOM split performed for block ``bi``."""
        self.oom_splits += 1
        observability.note_oom_split()
        observability.instant(
            "engine.oom_split", "faults", verb=self.verb, block=bi
        )

    def note_cache_restage(self) -> None:
        """One cached block rebuilt from its authoritative host copy
        because its resident shard was unusable (quarantined home
        device, or evicted between scheduling and dispatch)."""
        self.cache_restages += 1

    def events(self) -> bool:
        """Whether anything recovery-worthy happened (gates the span
        annotation so fault-free spans keep their exact prior shape)."""
        return bool(
            self.retries
            or self.oom_splits
            or self.cache_restages
            or (self.pool is not None and self.pool.quarantined)
        )

    def record(self) -> dict:
        """The ``fault_tolerance`` span annotation."""
        rec: dict = {
            "retries": self.retries,
            "oom_splits": self.oom_splits,
            "retry_budget_per_block": self.per_block,
        }
        if self.cache_restages:
            rec["cache_restages"] = self.cache_restages
        if self.pool is not None:
            rec["failures_per_device"] = list(self.pool.failures)
            rec["quarantined_devices"] = sorted(self.pool.quarantined)
        return rec
