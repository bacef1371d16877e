"""Failure detection + elastic recovery: restartable step drivers.

The reference has NO failure handling of its own — it delegates wholesale to
Spark task retry/lineage (SURVEY.md §5 "Failure detection"), which replays a
failed partition's work from the RDD lineage.  A TPU pod has no lineage to
replay: the unit of recovery is the *checkpointed step*.  This module is
that story, made concrete:

* ``run_restartable`` — drives an iterative step function with periodic
  checkpoints; on a device/runtime failure it restores the last durable
  state and resumes, up to ``max_restarts``.  Transient failure classes
  (preemption, halted device, collective timeout) are distinguished from
  programming errors (shape/type errors re-raise immediately — retrying a
  deterministic bug is Spark's pathology, not a feature worth copying).
* ``FailureDetector`` — classifies exceptions and keeps a restart budget
  with exponential backoff.

Elasticity note: resuming onto a *different* device topology is supported by
construction — ``Checkpointer.restore(target=...)`` re-shards saved arrays
to whatever mesh the resumed process builds (tested in
``tests/test_transformer.py::test_checkpoint_restore_onto_different_mesh``);
the driver only needs to rebuild its mesh from the surviving
``jax.devices()`` before calling ``run_restartable`` again.
"""

from __future__ import annotations

import logging
import random
import time
from typing import Any, Callable, Optional, Tuple

from jax.errors import JaxRuntimeError

from . import cancellation

_log = logging.getLogger("tensorframes_tpu.resilience")

# exception text fragments that indicate the *runtime* (not the program)
# failed: device preemption / halt, RPC loss, collective timeouts.  NOTE:
# deliberately does NOT include a bare "internal: " — XLA tags deterministic
# compiler bugs INTERNAL too, and retrying those masks the real failure
# (ADVICE r2); internal errors are transient only with preemption/halt/
# collective context, which the other markers already capture.
_TRANSIENT_MARKERS = (
    "preempt",
    "halted",
    "unavailable",
    "deadline exceeded",
    "socket closed",
    "connection reset",
    "collective",
    "slice has been terminated",
    "data transfer",
)

# deterministic program errors: retrying cannot help
_FATAL_TYPES = (TypeError, ValueError, KeyError, AttributeError)

# network-loss exception types are transient regardless of message text
_TRANSIENT_TYPES: tuple = (ConnectionError, TimeoutError)


# jax/XLA runtime-failure exception types for type-first classification.
# ``JaxRuntimeError`` wraps every XLA status (UNAVAILABLE preemptions and
# INTERNAL compiler bugs alike), so membership alone proves nothing — it
# unlocks the status-code check below, nothing more.
_RUNTIME_TYPES = (JaxRuntimeError,)

# XLA runtime errors open with their absl status code; these codes mean the
# *infrastructure* went away mid-call (vs INTERNAL / INVALID_ARGUMENT which
# tag compiler or program bugs) and are safe to retry on that basis alone.
_TRANSIENT_XLA_STATUS = ("unavailable", "aborted", "cancelled")


class RestartBudgetExceeded(RuntimeError):
    """The step kept failing after ``max_restarts`` recoveries."""


class FailureDetector:
    """Classifies failures and meters restarts with exponential backoff."""

    def __init__(
        self,
        max_restarts: int = 3,
        backoff_s: float = 1.0,
        backoff_factor: float = 2.0,
        jitter: float = 0.0,
        rng: Optional[random.Random] = None,
    ):
        self.max_restarts = max_restarts
        self.backoff_s = backoff_s
        self.backoff_factor = backoff_factor
        # decorrelated jitter (round 9): 0.0 keeps the exact exponential
        # sequence (existing callers/tests unchanged); 1.0 is the classic
        # uniform(base, 3*prev) rule, values between scale the random
        # span.  ``rng`` is injectable so jittered tests stay exact.
        self.jitter = float(jitter)
        self._rng = rng if rng is not None else random.Random()
        self._prev_delay = backoff_s
        self.restarts = 0

    def is_transient(self, exc: BaseException, _depth: int = 0) -> bool:
        """Type-first classification (ADVICE r2): fatal program-error types
        never retry; network-loss types always do; everything else —
        including ``JaxRuntimeError`` — retries only when the message shows
        runtime-failure context (preemption/halt/collective/...), so XLA
        INTERNAL compiler bugs surface immediately instead of burning the
        restart budget.  An inconclusive exception with an explicit
        ``raise ... from`` cause defers to the cause's classification
        (bounded walk), so a wrapped staging/transfer failure keeps its
        underlying transience.  Cooperative cancellation
        (``cancellation.Cancelled``/``DeadlineExceeded``) is never
        transient — its message contains "deadline exceeded" (a
        transient marker for REAL infrastructure deadlines), but
        retrying a deliberately cancelled request would defeat the
        cancel, so the type check wins."""
        if isinstance(exc, cancellation.Cancelled):
            return False
        if isinstance(exc, _FATAL_TYPES):
            return False
        if isinstance(exc, _TRANSIENT_TYPES):
            return True
        if isinstance(exc, _RUNTIME_TYPES):
            if str(exc).lower().lstrip().startswith(_TRANSIENT_XLA_STATUS):
                return True
        text = f"{type(exc).__name__}: {exc}".lower()
        if any(m in text for m in _TRANSIENT_MARKERS):
            return True
        if _depth < 4 and exc.__cause__ is not None:
            return self.is_transient(exc.__cause__, _depth + 1)
        return False

    def on_failure(self, exc: BaseException) -> float:
        """Record a failure; returns the backoff to sleep, or raises."""
        if not self.is_transient(exc):
            _log.error("non-transient failure, surfacing: %r", exc)
            raise exc
        self.restarts += 1
        if self.restarts > self.max_restarts:
            raise RestartBudgetExceeded(
                f"step failed {self.restarts} times; last error: {exc!r}"
            ) from exc
        delay = self.backoff_s * self.backoff_factor ** (self.restarts - 1)
        if self.jitter > 0.0:
            # decorrelated jitter: draw uniform(base, hi) where hi grows
            # with the PREVIOUS delay (3x rule), scaled by ``jitter``;
            # capped at the un-jittered exponential ceiling so a lucky
            # streak cannot exceed the deterministic worst case
            hi = self.backoff_s + (
                3.0 * self._prev_delay - self.backoff_s
            ) * self.jitter
            delay = self._rng.uniform(self.backoff_s, max(self.backoff_s, hi))
            delay = min(
                delay,
                self.backoff_s
                * self.backoff_factor ** max(self.max_restarts - 1, 0),
            )
        self._prev_delay = delay
        _log.warning(
            "transient failure (%s); restart %d/%d after %.1fs",
            exc,
            self.restarts,
            self.max_restarts,
            delay,
        )
        return delay


def run_restartable(
    step_fn: Callable[[Any, int], Any],
    state: Any,
    num_steps: int,
    checkpointer=None,
    checkpoint_every: int = 100,
    start_step: Optional[int] = None,
    detector: Optional[FailureDetector] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> Tuple[Any, int]:
    """Run ``state = step_fn(state, i)`` for ``i in [start, num_steps)`` with
    checkpoint-based recovery.

    * With a ``checkpointer`` (``tensorframes_tpu.checkpoint.Checkpointer``),
      state is saved every ``checkpoint_every`` steps and — when
      ``start_step`` is None — the run RESUMES from the latest checkpoint
      if one exists (the restart-after-crash entry path: just rerun the
      same driver).
    * On a transient runtime failure, the last checkpointed state is
      restored and the loop continues from there; ``detector`` governs
      classification, backoff, and the restart budget.

    Returns ``(final_state, steps_run_this_call)``.
    """
    detector = detector or FailureDetector()
    step = start_step if start_step is not None else 0
    if checkpointer is not None and start_step is None:
        latest = checkpointer.latest_step()
        if latest is not None:
            state = checkpointer.restore(latest, target=state)
            step = latest + 1
            _log.info("resuming from checkpoint step %d", latest)
    steps_run = 0
    while step < num_steps:
        try:
            state = step_fn(state, step)
        except BaseException as exc:  # noqa: BLE001 - classified below
            delay = detector.on_failure(exc)
            sleep(delay)
            if checkpointer is not None:
                latest = checkpointer.latest_step()
                if latest is not None:
                    state = checkpointer.restore(latest, target=state)
                    step = latest + 1
                    _log.info(
                        "restored step %d after failure; resuming", latest
                    )
                    continue
            # no checkpoint to fall back to: retry the same step
            continue
        if (
            checkpointer is not None
            and checkpoint_every > 0
            and step % checkpoint_every == 0
        ):
            checkpointer.save(step, state, wait=True)
        step += 1
        steps_run += 1
    return state, steps_run
