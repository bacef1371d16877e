"""Per-verb timing and profiling hooks.

The reference's observability is a Logging trait + log4j config + pervasive
``logDebug``/``logTrace`` in its data plane (``Logging.scala:5-9``,
``TFDataOps.scala:34-35``, ``PythonInterface.initialize_logging``,
``PythonInterface.scala:29-44``).  The TPU-native equivalents:

* ``initialize_logging(level)`` — one-call logger setup (the
  ``initialize_logging`` analog; PySpark misconfigured log4j, ad-hoc scripts
  misconfigure ``logging`` the same way);
* ``enable()`` — opt-in per-verb phase spans.  Every verb then logs
  ``validate / dispatch / sync`` wall times (the phases that matter on an
  async data plane: dispatch = host work to enqueue all blocks, sync =
  time to materialise results);
* **spans** — :class:`span` / :func:`instant`, the ONE primitive every
  emission site calls.  Each span is a ``jax.profiler.TraceAnnotation``
  named ``tfs:<name>``, so a profiler session around the workload
  (``jax.profiler.start_trace(dir)`` ... ``stop_trace()``) shows the
  program's host spans on the device trace's clock, arguments as event
  stats — the real tool for on-device timeline analysis — and, with the
  flight recorder on, an event in the ring below.  Always, whatever
  is on: where a span ends it adds its count and its nanoseconds to one
  table keyed by its name, which ``counters()`` carries as
  ``span_n.<name>`` / ``span_ns.<name>`` — every span is a counter and
  is declared nowhere else.  jax's compile durations, reported after
  the fact, go into the same table (``compile.frontend`` / ``.backend``
  / ``.cache_load``).  The ``*_ns`` time counters of PR 26 are twins of
  six of those entries, kept for the benchmark files that read them;
* ``last_spans()`` — the most recent spans as dicts (programmatic access;
  what ``bench.py`` surfaces as its phase breakdown).
* **retrace counters** (round 7) — always-on cumulative counts of
  program-function traces (``program_traces``, noted by ``Program.call``
  per traced application, attributed to the enclosing verb), XLA backend
  compiles (``backend_compiles``) and persistent-compilation-cache
  hits/misses, the latter two fed by ``jax.monitoring`` listeners.
  ``counters()`` snapshots them; enabled spans attach the per-verb delta
  as ``retrace``; ``bench.py`` attaches the per-config delta to every
  record — compile counts are *proven*, not asserted.
* **flight recorder** (round 13) — an opt-in bounded ring buffer
  (``TFS_TRACE=1``, capacity ``TFS_TRACE_EVENTS``) of the same spans,
  for when no profiler is at hand, at *block* granularity: engine
  serial/pooled/sharded dispatches,
  per-lane staging, overlapped D2H readback, retry/quarantine/OOM-split
  instants, cache evictions/spills, streaming windows, and the bridge
  request lifecycle.  ``dump_trace(path)`` exports Chrome-trace JSON —
  one track per device and per staging lane — that Perfetto /
  ``chrome://tracing`` open directly, so pool occupancy and H2D/compute
  overlap become visually inspectable.  Disabled (the default), every
  emission site is one boolean check.
* **latency histograms** (round 13) — always-on log2-bucket latency
  distributions for every verb and every bridge method
  (``latency_snapshot()`` derives p50/p95/p99), replacing "latency only
  exists in bench postprocessing".  One ``bisect`` into 28 buckets plus
  a dict increment per verb call.
* **metrics exposition** (round 13) — ``metrics_text()`` renders the
  counters, gauges (``peak_host_bytes``, HBM budget occupancy, trace
  depth/drops, registered providers), and latency histograms in
  Prometheus text format; served as the bridge's ungated ``metrics``
  RPC and, with ``TFS_METRICS_PORT`` set, a stdlib-HTTP ``/metrics``
  endpoint (:func:`maybe_start_metrics_server`).
* **request-scoped telemetry** (round 15) — a correlation context on a
  ``contextvars.ContextVar``: :func:`request_ledger` (or the bridge
  server, automatically per gated request) installs a
  :class:`RequestLedger` that every counter bump, trace event, span,
  and latency sample is attributed to WITHOUT perturbing the
  process-global counters — the ledger mirrors the exact deltas, so a
  single request's ledger matches ``counters_delta`` over its window
  bit for bit.  Trace events carry the active ``cid`` (correlation
  id), staging-lane worker threads inherit the context
  (``prefetch.Prefetcher`` copies it), finished ledgers fold into
  bounded-cardinality per-tenant ``tfs_request_*`` metrics, and
  requests slower than ``TFS_SLOW_REQUEST_MS`` emit one structured
  (JSON) log line.  With no active request the whole layer is one
  contextvar read per block.

Deliberately cheap: a disabled verb span is one ``if``; a counter bump is
one dict increment under an uncontended lock (bridge handler threads bump
concurrently since round 11; the paths are at most per-block, never
per-element); a span with no profiler session and the recorder off is an
idle ``TraceAnnotation``, two clock reads, two adds into the calling
thread's own table (no lock) and one boolean check.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import copy
import itertools
import json
import logging
from . import envutil
import threading
import time
import uuid
from typing import (
    Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple,
)

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .envutil import env_float, env_int, warn_once

logger = logging.getLogger("tensorframes_tpu")
_verb_log = logging.getLogger("tensorframes_tpu.verbs")

_MAX_SPANS = 256

_state: Dict[str, Any] = {
    "enabled": False,
    "spans": [],
}

# -- retrace counters ---------------------------------------------------------

# jax.monitoring event names (stable since jax 0.4.x): one duration event
# per XLA backend compile; one plain event per persistent-cache hit/miss
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the duration events kept in the span table (jax 0.9.0), by the name
# each is kept under.  ``backend_compile_duration`` is taken around
# ``compiler.compile_or_get_cached``, so ``compile.backend`` CONTAINS the
# persistent cache's load where there was one (``compile.cache_load``:
# ``cache_retrieval_time_sec``, read and deserialise, reported on a hit
# only).  ``compile.frontend`` is tracing to a jaxpr plus lowering it to
# an MLIR module.  A jit traced inside another's trace reports its own
# trace too (hundreds of them in a model's set-up), inside the outer
# one's time: jax announces each of the two events as it STARTS
# (``record_scalar`` under the same name), so the depth is known and
# only the outermost is kept — every stretch of a thread's time once.
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.frontend",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.frontend",
    _BACKEND_COMPILE_EVENT: "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_counters: Dict[str, int] = {
    "program_traces": 0,
    "backend_compiles": 0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
    "pool_blocks": 0,
    # fault tolerance (round 9): the recovery layer's evidence counters
    "block_retries": 0,
    "block_oom_splits": 0,
    "devices_quarantined": 0,
    "faults_injected": 0,
    "pool_copy_fallbacks": 0,
    # sharded frame cache (round 10): H2D traffic actually staged, shard
    # servings, and LRU budget evictions — the counters that let a bench
    # record PROVE a cached epoch paid zero host->device bytes
    "h2d_bytes_staged": 0,
    "cache_shard_hits": 0,
    "cache_evictions": 0,
    # bridge serving resilience (round 11): deadline/shed/cancel/retry
    # evidence for the admission-controlled request path
    "bridge_deadline_exceeded": 0,
    "bridge_shed": 0,
    "bridge_retries": 0,
    "bridge_cancels": 0,
    "bridge_idem_hits": 0,
    "bridge_verbs_executed": 0,
    # out-of-core streaming frames (round 12): windows materialised, disk
    # spill traffic, and the host-RAM high-water gauge that lets a bench
    # record PROVE a streamed run never held the full frame on host
    "stream_windows": 0,
    "spill_bytes_written": 0,
    "spill_bytes_read": 0,
    "peak_host_bytes": 0,
    # lazy verb-graph planner (round 14): fused dispatches executed,
    # source columns pruned from staging, and sharded caches the
    # optimizer auto-inserted on twice-consumed subplans
    "plan_fused_dispatches": 0,
    "plan_columns_pruned": 0,
    "plan_cache_inserts": 0,
    # planner v2 (round 19): terminal reduce/aggregate folds fused into
    # the chain dispatch (no materialized intermediate), identical
    # subplans served from the cross-plan CSE registry instead of
    # re-executing, streaming windows routed through plan construction,
    # and the pooled readback volume (D2H bytes assembled to host) the
    # fused terminals eliminate
    "plan_fused_reduces": 0,
    "plan_cse_hits": 0,
    "plan_stream_windows": 0,
    "d2h_bytes_assembled": 0,
    # multi-tenant serving throughput (round 16, bridge/coalescer.py):
    # micro-batches dispatched, requests they carried, requests that
    # dispatched ALONE on a hot program (the coalesce_miss evidence),
    # warm program-pool hits, and SLO-scheduler sheds by reason
    "coalesced_batches": 0,
    "coalesced_requests": 0,
    "coalesce_solo_requests": 0,
    "warm_program_hits": 0,
    "fair_share_sheds": 0,
    "slo_sheds": 0,
    # static program analysis (round 17, tensorframes_tpu/analysis/):
    # row-independence questions answered from the one-time jaxpr
    # classification vs. those that fell back to the per-size compile
    # probe — the ratio tfs.doctor()'s ``indep_probe_churn`` rule reads
    "analysis_static_hits": 0,
    "analysis_probe_fallbacks": 0,
    # relational verbs (round 18, tensorframes_tpu/relational/): shuffle
    # spill-run traffic and join build/probe volume — the evidence that a
    # re-key ran through disk runs (not host RAM) and which join side did
    # the work; the ``shuffle_skew`` doctor rule reads the per-partition
    # stats the shuffle module keeps alongside these totals
    "shuffle_partitions_written": 0,
    "shuffle_bytes_spilled": 0,
    "join_build_rows": 0,
    "join_probe_rows": 0,
    # durable execution (round 20, tensorframes_tpu/recovery/): journal
    # boundary appends + bytes (the write-ahead cost a bench leg can
    # price), windows a resumed run SKIPPED from the journal vs re-ran
    # (the at-most-one-window-re-executed evidence), jobs resumed from a
    # journaled boundary, and zombie writes the fence rejected
    "journal_appends": 0,
    "journal_bytes_written": 0,
    "journal_windows_skipped": 0,
    "journal_resumes": 0,
    "journal_fence_rejections": 0,
    # elastic bridge fleet (round 21, bridge/fleet.py): client calls
    # rerouted to a healthy replica (draining or dead origin), durable
    # jobs that RESUMED on a different replica than the one that started
    # them (the journal-backed migration evidence), replicas the router
    # quarantined for flapping, and replica restarts the fleet performed
    # (rolling restarts included)
    "fleet_failovers": 0,
    "fleet_jobs_migrated": 0,
    "fleet_quarantines": 0,
    "fleet_replica_restarts": 0,
    # round 22: paged continuous decode — tokens the decode scheduler
    # generated (billed per tenant), KV pages the pool allocated/freed
    # (churn vs occupancy drives the kv_fragmentation doctor rule), and
    # bucket-coalesced prefill batches the disaggregated prefill lane ran
    "decode_tokens": 0,
    "kv_pages_allocated": 0,
    "kv_pages_freed": 0,
    "decode_prefill_batches": 0,
    # what the prefill lane ran: real prompt tokens, and the tokens its
    # executables computed (rows x bucket) — the rest is padding
    "decode_prefill_prompt_tokens": 0,
    "decode_prefill_run_tokens": 0,
    # the tokens a decode step attended over, summed over steps: what
    # every live slot held, the token it fed among them
    "decode_tokens_held": 0,
    # a block that keeps a state a slot and no pages (retention): the
    # prefill dispatches that resumed from a state left in the slot (a
    # prompt longer than one dispatch)
    "decode_prefill_resumes": 0,
    # a block that keeps a state a slot (retention, or a mixer beside
    # attention): the live slots whose state a decode step stepped, summed
    # over steps (the host's, at ``decode.step.emit``)
    "decode_state_slots_held": 0,
    # expert routing of a served model (``moe.experts_top1`` /
    # ``experts_topk``), counted on the device over live tokens only and
    # read back with a dispatch's tokens: layer-steps routed (expert
    # layers x dispatches), tokens routed (summed over layers; for top-k
    # the token-expert pairs computed HERE, on the experts this program
    # holds), over the same layer-steps the sum of the fullest held
    # expert's and of the held experts that got any, and the pairs the
    # router picked, whoever holds their experts.  A dense model bumps
    # none of them
    "moe_route_calls": 0,
    "moe_routed_tokens": 0,
    "moe_busiest_expert_tokens": 0,
    "moe_experts_touched": 0,
    "moe_picked_pairs": 0,
    # time counters (nanoseconds of time.perf_counter_ns, monotonic),
    # taken at the boundaries of the spans of the same name and bumped
    # once per step / prefill / block / verb.  Decode scheduler: steps,
    # and those of them whose executable attends through the paged-
    # attention kernel (``kv_pager.paged_kernel_fits``, evaluated once
    # when the scheduler builds its pool: all of a scheduler's or none);
    # the driver loop's wall time while any stream is active (busy) and
    # its three parts — the wait for a step's tokens, the whole of its
    # prefills, and host time, which is the rest (boundary, dispatch,
    # bookkeeping), so the parts sum to busy at every bump; and the
    # request-life stamps: requests admitted with their submit -> admit
    # wait, first tokens with their submit -> first-token time, and
    # first -> last token time over the tokens after the first
    "decode_steps": 0,
    "decode_kernel_steps": 0,
    # of them, those whose executable steps a mixer's state through the
    # Pallas kernel ``tfs_ssm_step`` (``kv_pager.ssm_kernel_fits``, asked
    # once a scheduler: all of its steps or none)
    "decode_ssm_kernel_steps": 0,
    # a stack of window layers among full ones: the keys its window
    # layers read, summed over live rows and steps (the host's, at
    # ``decode.step.emit``, beside ``decode_tokens_held``: a row holds at
    # most the window), and the steps whose window layers attend through
    # the paged-attention kernel over their ring (asked once a scheduler)
    "decode_window_tokens_held": 0,
    "decode_window_kernel_steps": 0,
    # of them, those whose latent block attends through the kernel's
    # latent form ``tfs_latent_attention`` (``kv_pager.latent_kernel_fits``,
    # asked once a scheduler: all of its steps or none)
    "decode_latent_kernel_steps": 0,
    # of them, those whose params held every q, k and v projection turned
    # to the layout the step's dots read in place (``transformer.OutIn``,
    # ``kv_pager.serving_params``; decided once a scheduler)
    "decode_proj_in_place_steps": 0,
    "decode_host_ns": 0,
    "decode_step_wait_ns": 0,
    "decode_prefill_ns": 0,
    "decode_busy_ns": 0,
    "decode_admitted": 0,
    "decode_queue_wait_ns": 0,
    "decode_first_tokens": 0,
    "decode_ttft_ns": 0,
    "decode_stream_ns": 0,
    "decode_stream_tokens": 0,
    # map verbs: verbs with their entry -> return time, entry -> first
    # block (head) and last block enqueued -> return (tail); block-loop
    # iterations with their start -> outputs-enqueued time; and the
    # pool's blocking readbacks (PoolRun._materialize)
    "map_verbs": 0,
    "map_verb_ns": 0,
    "map_head_ns": 0,
    "map_tail_ns": 0,
    "dispatch_blocks": 0,
    "dispatch_host_ns": 0,
    "readback_wait_ns": 0,
    # a program's params resident on the devices its blocks run on
    # (program._Residency): the block dispatches of the map verbs that
    # found the live params on the block's device (the originals' own
    # device, or one holding a replica; of ``dispatch_blocks``), and the
    # bytes copied to place replicas (the params' bytes once a device
    # and params state, not once a block)
    "param_replica_hits": 0,
    "param_bytes_placed": 0,
}
_by_verb: Dict[str, Dict[str, int]] = {}

# live host bytes currently accounted to streaming windows (the gauge
# behind peak_host_bytes); guarded by _counters_lock like the counters
_live_host_bytes = 0

# counters were single-thread-bumped until round 11; the bridge's
# ThreadingTCPServer handlers now increment them concurrently, and an
# unlocked ``+= 1`` interleaves and loses counts under exactly the load
# the bridge counters exist to measure.  One uncontended lock per bump
# is ~100ns on a path that is at most per-block, never per-element.
_counters_lock = threading.Lock()

# -- request-scoped telemetry (round 15) --------------------------------------
#
# One contextvar carries the active request's ledger; the bridge server
# installs it per gated request (alongside the round-11 cancel scope) and
# :func:`request_ledger` installs it for in-process callers.  Every
# counter bump mirrors into the active ledger (same key, same delta), so
# the ledger IS the counters-delta of its window, attributed to one
# correlation id — the substrate multi-tenant accounting bills against.
# Prefetch staging lanes copy the creating thread's context
# (``prefetch.Prefetcher``), so bytes staged on a worker thread are
# attributed to the request that staged them.  Ledger-off cost: one
# contextvar read per bump / per block.

ENV_SLOW_REQUEST_MS = "TFS_SLOW_REQUEST_MS"
ENV_TENANT_LABELS = "TFS_TENANT_LABELS"
DEFAULT_TENANT_LABELS = 16

# per-ledger latency label bound: a ledger lives for one request, but a
# request that touches many verbs must not grow an unbounded dict
_LEDGER_LATENCY_LABELS = 32

_request_ctx: "contextvars.ContextVar[Optional[RequestLedger]]" = (
    contextvars.ContextVar("tfs_request_ledger", default=None)
)


# correlation ids are (random process prefix) + (atomic counter): unique
# across processes and requests without paying uuid4's per-call urandom
# syscall (~35 µs in containers with slow entropy paths — measured; the
# id is minted per request AND per client call, so it sits on the
# serving hot path).  itertools.count.__next__ is atomic under the GIL.
# led by a letter no number starts with: a profiler session stores an
# annotation's argument that parses as a number AS a number (an
# all-digit id an int, "123e4567..." a float), and a search for the
# request's cid among the session's event stats then finds nothing
_cid_prefix = "c" + uuid.uuid4().hex[:7]
_cid_counter = itertools.count(1)


def new_correlation_id() -> str:
    """A fresh request correlation id (16 hex chars — compact enough
    for trace-event args, unique enough for a process's attribution
    window)."""
    return f"{_cid_prefix}{next(_cid_counter) & 0xFFFFFFFF:08x}"


class RequestLedger:
    """Counters-delta-style resource attribution for ONE request.

    Mirrors every counter bump made while the ledger is the active
    request context — including bumps from prefetch staging lanes, which
    inherit the context — so ``ledger.counters`` equals the
    process-global :func:`counters_delta` over the request's window,
    over the declared counters (the span table's ``span_n.*`` /
    ``span_ns.*`` entries are process-wide and feed no ledger; bit for
    bit when no other request runs concurrently; per-request exact
    always, because each bump lands in exactly the ledgers active on its
    thread).  Also tracks blocks/rows per device (the pool scheduler and
    serial loops report them) and a bounded per-verb latency summary.

    Ledgers NEST: a ledger constructed while another is active records
    into both (``parent`` chaining), so e.g. an ``explain(analyze=True)``
    run inside a bridge request never steals the outer request's
    attribution."""

    __slots__ = (
        "correlation_id",
        "tenant",
        "method",
        "parent",
        "counters",
        "blocks_per_device",
        "rows",
        "latency",
        "wall_s",
        "_t0",
        "_lock",
        "_finished",
    )

    def __init__(
        self,
        correlation_id: Optional[str] = None,
        tenant: Optional[str] = None,
        method: Optional[str] = None,
    ):
        self.correlation_id = correlation_id or new_correlation_id()
        self.tenant = tenant
        self.method = method
        self.parent = _request_ctx.get()
        self.counters: Dict[str, int] = {}
        self.blocks_per_device: Dict[int, int] = {}
        self.rows = 0
        self.latency: Dict[str, Dict[str, Any]] = {}
        self.wall_s: Optional[float] = None
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._finished = False

    # -- recording (called by the counter/latency layers) -------------------

    def add(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n
        if self.parent is not None:
            self.parent.add(key, n)

    def note_block(self, device: Optional[int] = 0, rows: int = 0) -> None:
        d = int(device) if device is not None else 0
        with self._lock:
            self.blocks_per_device[d] = self.blocks_per_device.get(d, 0) + 1
            self.rows += int(rows)
        if self.parent is not None:
            self.parent.note_block(device, rows)

    def absorb(
        self,
        counters: Optional[Mapping[str, int]] = None,
        blocks_per_device: Optional[Mapping[int, int]] = None,
        rows: int = 0,
    ) -> None:
        """Fold an externally-apportioned share into this ledger — the
        bridge coalescer's attribution path (round 16): one shared
        dispatch runs under a private batch ledger, and each
        participating request absorbs its exact row share of the batch's
        counters/blocks so the shares SUM to the batch's global delta."""
        with self._lock:
            for k, n in (counters or {}).items():
                if n:
                    self.counters[k] = self.counters.get(k, 0) + int(n)
            for d, n in (blocks_per_device or {}).items():
                if n:
                    d = int(d)
                    self.blocks_per_device[d] = (
                        self.blocks_per_device.get(d, 0) + int(n)
                    )
            self.rows += int(rows)
        if self.parent is not None:
            self.parent.absorb(counters, blocks_per_device, rows)

    def note_latency(self, kind: str, label: str, seconds: float) -> None:
        key = f"{kind}:{label}"
        with self._lock:
            m = self.latency.get(key)
            if m is None:
                if len(self.latency) >= _LEDGER_LATENCY_LABELS:
                    key = "other"
                    m = self.latency.get(key)
                if m is None:
                    m = self.latency[key] = {
                        "count": 0, "sum_s": 0.0, "max_s": 0.0
                    }
            m["count"] += 1
            m["sum_s"] += seconds
            if seconds > m["max_s"]:
                m["max_s"] = seconds
        if self.parent is not None:
            self.parent.note_latency(kind, label, seconds)

    # -- lifecycle ----------------------------------------------------------

    def finish(self) -> None:
        """Stamp the wall time, fold this request into the per-tenant
        ``tfs_request_*`` metrics, and emit the slow-request structured
        log when ``TFS_SLOW_REQUEST_MS`` is exceeded.  Idempotent."""
        if self._finished:
            return
        self._finished = True
        self.wall_s = time.perf_counter() - self._t0
        # only ROOT ledgers fold into the per-tenant aggregates: a
        # nested ledger (explain_analyze inside a bridge request)
        # already mirrored every delta into its parent, so folding both
        # would bill the same bytes twice and count one RPC as two
        # requests
        if self.parent is None:
            _fold_request_metrics(self)
        _maybe_log_slow_request(self)

    def snapshot(self) -> Dict[str, Any]:
        """A JSON-safe copy of the ledger (the ``attribution`` RPC
        payload and the slow-request log body)."""
        with self._lock:
            wall = (
                self.wall_s
                if self.wall_s is not None
                else time.perf_counter() - self._t0
            )
            return {
                "correlation_id": self.correlation_id,
                "tenant": self.tenant,
                "method": self.method,
                "wall_s": round(wall, 6),
                "counters": dict(self.counters),
                "blocks_per_device": {
                    str(d): n
                    for d, n in sorted(self.blocks_per_device.items())
                },
                "rows": self.rows,
                "latency": {
                    k: {
                        "count": v["count"],
                        "sum_s": round(v["sum_s"], 6),
                        "max_s": round(v["max_s"], 6),
                    }
                    for k, v in sorted(self.latency.items())
                },
            }


def apportion(total: int, weights: Sequence[int]) -> List[int]:
    """Split integer ``total`` proportionally to ``weights`` so the
    shares sum to ``total`` EXACTLY (largest-remainder method, ties to
    the earliest index — deterministic).  The bit-for-bit contract of
    shared-work ledger attribution hangs on this: the bridge coalescer
    splits batch deltas by row share, and the planner's CSE registry
    splits a deduplicated subplan's delta evenly across its consumers
    (``RequestLedger.absorb`` on each side)."""
    w = sum(weights)
    if w <= 0 or total == 0:
        out = [0] * len(weights)
        if weights and total:
            out[0] = total
        return out
    base = [total * wi // w for wi in weights]
    rem = total - sum(base)
    # fractional parts, largest first; index breaks ties deterministically
    order = sorted(
        range(len(weights)),
        key=lambda i: (-(total * weights[i] % w), i),
    )
    for i in order[:rem]:
        base[i] += 1
    return base


def current_request() -> Optional[RequestLedger]:
    """The active request's ledger, or None (one contextvar read)."""
    return _request_ctx.get()


def activate_request(ledger: RequestLedger):
    """Install ``ledger`` as the active request context on this thread
    (and, via context copy, on staging lanes it spawns).  Returns the
    reset token for :func:`deactivate_request` — the split form the
    bridge handler uses; in-process callers want
    :func:`request_ledger`."""
    return _request_ctx.set(ledger)


def deactivate_request(token) -> None:
    _request_ctx.reset(token)


@contextlib.contextmanager
def request_ledger(
    correlation_id: Optional[str] = None,
    tenant: Optional[str] = None,
    method: Optional[str] = None,
):
    """Scope a :class:`RequestLedger` over a ``with`` body::

        with observability.request_ledger(tenant="team-a") as led:
            tfs.map_blocks(program, frame)
        print(led.snapshot()["counters"]["h2d_bytes_staged"])

    Everything the body executes — engine dispatch, staging lanes,
    retries, cache traffic — is attributed to the ledger without
    touching the process-global counters' meaning."""
    led = RequestLedger(correlation_id, tenant=tenant, method=method)
    token = activate_request(led)
    try:
        yield led
    finally:
        deactivate_request(token)
        led.finish()


def note_request_block(device: Optional[int] = 0, rows: int = 0) -> None:
    """One block dispatched under the active request (serial loops call
    this; pooled loops report through :func:`note_pool_dispatch`).  With
    no active request this is ONE contextvar read — the ledger-off
    hot-path cost contract."""
    led = _request_ctx.get()
    if led is not None:
        led.note_block(device, rows)


def slow_request_threshold_ms() -> float:
    """``TFS_SLOW_REQUEST_MS`` (0 / unset = slow-request log off)."""
    return env_float(ENV_SLOW_REQUEST_MS, 0.0)


def _maybe_log_slow_request(led: RequestLedger) -> None:
    th = slow_request_threshold_ms()
    if th <= 0 or led.wall_s is None or led.wall_s * 1000.0 < th:
        return
    # ONE structured line: greppable prefix + machine-readable JSON body
    logger.warning(
        "slow_request %s",
        json.dumps(led.snapshot(), sort_keys=True, default=str),
    )


# per-tenant request aggregates behind the ``tfs_request_*`` metric
# families.  Label cardinality is BOUNDED (``TFS_TENANT_LABELS``): once
# the cap is reached, new tenants fold into "other" — a long-lived
# server's scrape size cannot grow with its tenant population.
_request_agg: Dict[str, Dict[str, float]] = {}
_request_agg_lock = threading.Lock()

_REQUEST_AGG_FIELDS = (
    "requests",
    "slow",
    "h2d_bytes",
    "traces",
    "retries",
    "pool_blocks",
    "shard_hits",
    "rows",
    "wall_seconds",
)


def _fold_request_metrics(led: RequestLedger) -> None:
    tenant = led.tenant or "default"
    cap = env_int(ENV_TENANT_LABELS, DEFAULT_TENANT_LABELS, floor=1)
    with led._lock:
        c = dict(led.counters)
    with _request_agg_lock:
        agg = _request_agg.get(tenant)
        if agg is None:
            if len(_request_agg) >= cap and tenant != "other":
                tenant = "other"
                agg = _request_agg.get(tenant)
            if agg is None:
                agg = _request_agg[tenant] = {
                    k: 0 for k in _REQUEST_AGG_FIELDS
                }
        agg["requests"] += 1
        agg["wall_seconds"] += led.wall_s or 0.0
        agg["h2d_bytes"] += c.get("h2d_bytes_staged", 0)
        agg["traces"] += c.get("program_traces", 0)
        agg["retries"] += c.get("block_retries", 0)
        agg["pool_blocks"] += c.get("pool_blocks", 0)
        agg["shard_hits"] += c.get("cache_shard_hits", 0)
        agg["rows"] += led.rows
        th = slow_request_threshold_ms()
        if th > 0 and (led.wall_s or 0.0) * 1000.0 >= th:
            agg["slow"] += 1


def request_metrics() -> Dict[str, Dict[str, float]]:
    """Per-tenant request aggregates (a copy)."""
    with _request_agg_lock:
        return {t: dict(v) for t, v in _request_agg.items()}


def reset_request_metrics() -> None:
    """Drop the per-tenant aggregates (tests / bench legs)."""
    with _request_agg_lock:
        _request_agg.clear()


def _bump(key: str, n: int = 1) -> None:
    with _counters_lock:
        _counters[key] += n
    led = _request_ctx.get()
    if led is not None:
        led.add(key, n)


def _bump_many(deltas: Mapping[str, int]) -> None:
    """Several counters under ONE lock take — how a step, a block or a
    verb bumps its count and its time counters together."""
    with _counters_lock:
        for key, n in deltas.items():
            _counters[key] += n
    led = _request_ctx.get()
    if led is not None:
        led.absorb(deltas)


# the verb currently executing on this thread (set by verb_span even when
# spans are disabled, so counter attribution never depends on enable())
_current_verb: "contextvars.ContextVar[Optional[str]]" = (
    contextvars.ContextVar("tfs_current_verb", default=None)
)
# analysis-only traces (eval_shape in Program.analyze, the segment
# compiler's jaxpr probes, serialization) must not read as retraces
_suppress_traces: "contextvars.ContextVar[bool]" = contextvars.ContextVar(
    "tfs_suppress_traces", default=False
)

_listeners_installed = False


def _verb_bump(kind: str) -> None:
    verb = _current_verb.get()
    if verb is not None:
        with _counters_lock:
            _by_verb.setdefault(
                verb, {"program_traces": 0, "backend_compiles": 0}
            )[kind] += 1


def note_program_trace() -> None:
    """Called by ``Program.call`` per traced application of the user
    program (jit only invokes the python function on a signature-cache
    miss, so in steady state this counter does not move)."""
    if _suppress_traces.get():
        return
    _bump("program_traces")
    _verb_bump("program_traces")


def note_pool_dispatch(device: Optional[int] = None, rows: int = 0) -> None:
    """Called by the device-pool scheduler (``ops/device_pool.py``) once
    per block dispatched through the pool — the always-on counter that
    lets a bench record prove pool utilisation rather than assert it.
    ``device``/``rows`` additionally attribute the block to the active
    request's ledger (blocks-per-device accounting, round 15)."""
    _bump("pool_blocks")
    led = _request_ctx.get()
    if led is not None:
        led.note_block(device, rows)


def note_block_retry() -> None:
    """One transient block-dispatch failure absorbed by the per-block
    retry loop (``ops/fault_tolerance.py``)."""
    _bump("block_retries")


def note_oom_split() -> None:
    """One OOM-degradation binary split performed on a map-verb block."""
    _bump("block_oom_splits")


def note_device_quarantined() -> None:
    """One pool device drained after repeated transient failures."""
    _bump("devices_quarantined")


def note_fault_injected() -> None:
    """One fault raised by the ``TFS_FAULT_INJECT`` harness
    (``faults.py``) — chaos evidence for tests and the bench."""
    _bump("faults_injected")


def note_pool_copy_fallback() -> None:
    """One ``copy_to_host_async`` failure in the pool readback window
    that fell back to synchronous readback (``PoolRun.submit``)."""
    _bump("pool_copy_fallbacks")


def note_h2d_bytes(n: int) -> None:
    """``n`` host bytes handed to ``jax.device_put`` by the engine's
    staging paths (prefetch lanes, ``stage_columns``, cache builds,
    pipeline entry staging).  The evidence counter behind the sharded
    frame cache: an epoch served entirely from HBM shards leaves this
    at zero."""
    _bump("h2d_bytes_staged", int(n))


def note_cache_shard_hit() -> None:
    """One block dispatch served from a resident frame-cache shard
    (``ops/frame_cache.py``) instead of host staging."""
    _bump("cache_shard_hits")


def note_cache_eviction() -> None:
    """One cached shard evicted back to its authoritative host copy by
    the ``TFS_HBM_BUDGET`` LRU."""
    _bump("cache_evictions")


def note_bridge_deadline_exceeded() -> None:
    """One bridge request cancelled at a block boundary because its
    ``deadline_ms`` passed (``bridge/server.py``)."""
    _bump("bridge_deadline_exceeded")


def note_bridge_shed() -> None:
    """One bridge request shed by admission control (``ServerBusy`` /
    ``Draining``) instead of queueing unboundedly."""
    _bump("bridge_shed")


def note_bridge_retry() -> None:
    """One client-side bridge call retried after a reconnect (safe
    methods and idempotency-tokened verb calls only)."""
    _bump("bridge_retries")


def note_bridge_cancel() -> None:
    """One in-flight bridge request cooperatively cancelled (graceful
    drain's straggler cancellation)."""
    _bump("bridge_cancels")


def note_bridge_idem_hit() -> None:
    """One bridge request served from the idempotency-token dedup cache
    instead of re-executing — the exactly-once evidence counter."""
    _bump("bridge_idem_hits")


def note_bridge_verb_executed() -> None:
    """One admission-gated bridge method actually executed (dedup hits
    and shed requests never bump this)."""
    _bump("bridge_verbs_executed")


def note_coalesced_batch(requests: int) -> None:
    """One coalesced micro-batch dispatched by the bridge coalescer
    (``bridge/coalescer.py``) carrying ``requests`` requests.  A batch
    of one request counts as a *solo* dispatch instead
    (:func:`note_coalesce_solo`) — the split feeds the ``coalesce_miss``
    doctor rule."""
    if requests <= 1:
        note_coalesce_solo()
        return
    _bump("coalesced_batches")
    _bump("coalesced_requests", requests)


def note_coalesce_solo() -> None:
    """One request that reached the coalescer but dispatched alone
    (nobody else arrived within ``TFS_BRIDGE_COALESCE_US``)."""
    _bump("coalesce_solo_requests")


def note_warm_program_hit() -> None:
    """One warm-program-pool lookup by the bridge that found the
    compiled Program resident (a miss rebuilds it from GraphDef bytes
    and counts nothing)."""
    _bump("warm_program_hits")


def note_fair_share_shed() -> None:
    """The SLO scheduler shed a request for exceeding its tenant's
    fair-share row budget under contention."""
    _bump("fair_share_sheds")


def note_slo_shed() -> None:
    """The SLO scheduler shed a request because the serving p99 was
    approaching ``TFS_BRIDGE_SLO_MS`` and the tenant was the dominant
    row consumer."""
    _bump("slo_sheds")


def note_plan_fused_dispatch() -> None:
    """One fused group (>= 2 adjacent map stages composed into one
    program) dispatched by the lazy planner (``ops/planner.py``)."""
    _bump("plan_fused_dispatches")


def note_plan_columns_pruned(n: int) -> None:
    """``n`` source columns a fused dispatch never staged because no
    downstream stage consumes them (dead-column pruning)."""
    _bump("plan_columns_pruned", int(n))


def note_plan_cache_insert() -> None:
    """One sharded cache auto-inserted by the planner on a subplan with
    >= 2 consumers."""
    _bump("plan_cache_inserts")


def note_plan_fused_reduce() -> None:
    """One terminal ``reduce_rows``/``reduce_blocks``/``aggregate``
    folded into the planned chain dispatch (``ops/planner.py`` round
    19): per-block partials computed on the chain's devices, no
    materialized intermediate frame."""
    _bump("plan_fused_reduces")


def note_plan_cse_hit() -> None:
    """One planned subplan served from the cross-plan common-
    subexpression registry instead of re-executing — concurrent waiters
    and later identical chains both count."""
    _bump("plan_cse_hits")


def note_plan_stream_window() -> None:
    """One streaming window executed through plan construction (fused
    map chain + dead-column pruning) instead of per-stage eager
    dispatch."""
    _bump("plan_stream_windows")


def note_readback(nbytes: int, ns: int) -> None:
    """One blocking readback of a pooled block (``pool.readback``,
    ``PoolRun._materialize``): ``nbytes`` device bytes assembled back to
    host — the D2H half of the round trip a fused terminal reduce
    eliminates — for which the dispatching thread stood blocked ``ns``."""
    _bump_many({"d2h_bytes_assembled": int(nbytes), "readback_wait_ns": ns})


def note_analysis_static_hit() -> None:
    """One row-independence question answered by the static classifier
    (``analysis/rowdep.py``) with NO per-size compile probe."""
    _bump("analysis_static_hits")


def note_analysis_probe_fallback() -> None:
    """One row-independence question the classifier could not answer
    (verdict UNKNOWN) that fell back to the per-size compile probe
    (``segment_compile.cached_rows_independent``)."""
    _bump("analysis_probe_fallbacks")


def note_shuffle_partition_written(n: int = 1) -> None:
    """``n`` per-partition spill runs written by the streaming shuffle
    (``relational/shuffle.py``) — one run per (window, non-empty
    partition)."""
    _bump("shuffle_partitions_written", int(n))


def note_shuffle_bytes_spilled(n: int) -> None:
    """``n`` bytes of shuffle run payload written to ``TFS_SPILL_DIR``
    (also counted in ``spill_bytes_written`` by the store; this counter
    isolates the shuffle's share)."""
    _bump("shuffle_bytes_spilled", int(n))


def note_join_build_rows(n: int) -> None:
    """``n`` build-side rows indexed by a join (once per broadcast
    build; once per partition for sort-merge)."""
    _bump("join_build_rows", int(n))


def note_join_probe_rows(n: int) -> None:
    """``n`` probe-side rows streamed through a join."""
    _bump("join_probe_rows", int(n))


def note_journal_append() -> None:
    """One window/epoch boundary committed to a durable job's journal
    (``recovery/journal.py``) — manifest atomically replaced."""
    _bump("journal_appends")


def note_journal_bytes(n: int) -> None:
    """``n`` bytes of journal payload (state ``.npz`` files) written to
    ``TFS_JOURNAL_DIR`` — the write-ahead overhead bench config 22
    prices per window."""
    _bump("journal_bytes_written", int(n))


def note_journal_window_skipped() -> None:
    """One already-journaled window a resumed run skipped at the table
    level (never built, never dispatched) — paired with
    ``stream_windows``, the proof that a resume re-executed at most the
    one unfinished window."""
    _bump("journal_windows_skipped")


def note_journal_resume() -> None:
    """One durable job adopted WITH journaled boundaries to resume from
    (a fresh adoption of an empty job does not count)."""
    _bump("journal_resumes")


def note_journal_fence_rejection() -> None:
    """One journal write refused because the writer's fence token was
    superseded — a zombie process tried to write after a successor
    adopted its job."""
    _bump("journal_fence_rejections")


def note_fleet_failover() -> None:
    """One client call rerouted to a different replica (the origin was
    draining, dead, or had forgotten the session) by the router-aware
    retry loop (``bridge/client.py`` + ``bridge/fleet.py``)."""
    _bump("fleet_failovers")


def note_fleet_job_migrated() -> None:
    """One durable job that RESUMED on a different replica than the one
    that started it — the failed-over re-issue adopted the journal fence
    and continued from the last window boundary."""
    _bump("fleet_jobs_migrated")


def note_fleet_quarantine() -> None:
    """One replica the fleet router quarantined for flapping (repeated
    up/down transitions inside the flap window) — the replica analog of
    ``devices_quarantined``."""
    _bump("fleet_quarantines")


def note_fleet_replica_restart() -> None:
    """One replica process the fleet restarted (rolling restarts and
    crash replacements alike)."""
    _bump("fleet_replica_restarts")


def note_decode_tokens(n: int) -> None:
    """``n`` tokens emitted by the paged decode scheduler (committed
    output only — drafts a speculative verify rejected don't count)."""
    _bump("decode_tokens", n)


def note_kv_pages_allocated(n: int) -> None:
    """``n`` KV pages reserved from the page pool for one sequence
    (``models/kv_pager.py``)."""
    _bump("kv_pages_allocated", n)


def note_kv_pages_freed(n: int) -> None:
    """``n`` KV pages returned to the pool at sequence retirement,
    cancellation, or deadline expiry."""
    _bump("kv_pages_freed", n)


def note_decode_driver(deltas: Mapping[str, int]) -> None:
    """What one step or one prefill of the decode scheduler's driver
    added to the ``decode_*`` counters (tokens, steps, prefill batches,
    the time counters and the request-life stamps), in one bump."""
    _bump_many(deltas)


def note_dispatch_block(ns: int, params_resident: bool = False) -> None:
    """One iteration of a map verb's block loop (``engine.block``):
    start -> outputs enqueued took ``ns``; ``params_resident``: the
    program's live params were on the device the block ran on."""
    deltas = {"dispatch_blocks": 1, "dispatch_host_ns": ns}
    if params_resident:
        deltas["param_replica_hits"] = 1
    _bump_many(deltas)


def note_params_placed(nbytes: int) -> None:
    """One replica of a program's params placed on a device its blocks
    run on (``program._Residency.replica``): ``nbytes`` copied there."""
    _bump("param_bytes_placed", int(nbytes))


def note_map_verb(verb_ns: int, head_ns: int, tail_ns: int) -> None:
    """One map verb returned: entry -> return ``verb_ns``, of which
    entry -> first block ``head_ns`` (``engine.head``) and last block
    enqueued -> return ``tail_ns`` (``engine.tail``)."""
    _bump_many({
        "map_verbs": 1,
        "map_verb_ns": verb_ns,
        "map_head_ns": head_ns,
        "map_tail_ns": tail_ns,
    })


def note_stream_window() -> None:
    """One streamed window materialised into host columns by the
    windowed reader (``streaming/reader.py``)."""
    _bump("stream_windows")


def note_spill_bytes_written(n: int) -> None:
    """``n`` bytes written to ``TFS_SPILL_DIR`` (window spool files or
    evicted cache shards) instead of being held in RAM / dropped."""
    _bump("spill_bytes_written", int(n))


def note_spill_bytes_read(n: int) -> None:
    """``n`` bytes restored from ``TFS_SPILL_DIR``."""
    _bump("spill_bytes_read", int(n))


def note_host_window_bytes(delta: int) -> None:
    """Adjust the live host-byte gauge by ``delta`` (positive when a
    window's host columns materialise, negative when the consumer moves
    past them).  ``peak_host_bytes`` tracks the high-water mark — the
    fixed-memory evidence for streamed runs: a stream over an N-byte
    frame that never exceeds a few windows of live bytes proves the
    out-of-core contract, where a counter of total bytes could not."""
    global _live_host_bytes
    with _counters_lock:
        _live_host_bytes = max(0, _live_host_bytes + int(delta))
        if _live_host_bytes > _counters["peak_host_bytes"]:
            _counters["peak_host_bytes"] = _live_host_bytes


def live_host_bytes() -> int:
    """The live host-byte gauge (streaming window columns currently
    materialised)."""
    with _counters_lock:
        return _live_host_bytes


def reset_peak_host_bytes() -> None:
    """Re-base ``peak_host_bytes`` to the current live gauge so a bench
    leg / test measures ITS OWN high-water, not an earlier run's.  (The
    peak is a gauge, not a monotonic counter — it is deliberately
    excluded from :func:`counters_delta`.)"""
    with _counters_lock:
        _counters["peak_host_bytes"] = _live_host_bytes


@contextlib.contextmanager
def suppress_trace_count():
    """Trace-counter suppression for analysis-time tracing (shape
    inference, jaxpr probes, export) — those are not retraces."""
    token = _suppress_traces.set(True)
    try:
        yield
    finally:
        _suppress_traces.reset(token)


def _on_event(name: str, **kw) -> None:
    if name == _CACHE_HIT_EVENT:
        _bump("persistent_cache_hits")
    elif name == _CACHE_MISS_EVENT:
        _bump("persistent_cache_misses")


_frontend_tls = threading.local()  # .depth: frontend events open on the thread


def _on_scalar(name: str, value: float, **kw) -> None:
    if _DURATION_SPANS.get(name) == "compile.frontend":
        _frontend_tls.depth = getattr(_frontend_tls, "depth", 0) + 1


def _on_event_duration(name: str, duration: float, **kw) -> None:
    kept = _DURATION_SPANS.get(name)
    if kept == "compile.frontend":
        # unannounced (another jax): depth 0, and every event is kept
        depth = _frontend_tls.depth = getattr(_frontend_tls, "depth", 1) - 1
        if depth > 0:
            kept = None  # inside another's: that one's time holds it
    if kept is not None:
        _span_add(kept, int(duration * 1e9))
    if name == _BACKEND_COMPILE_EVENT:
        _bump("backend_compiles")
        _verb_bump("backend_compiles")


def install_counters() -> None:
    """Register the jax.monitoring listeners feeding ``counters()``.

    Idempotent; called at package import (jax is already a hard
    dependency of the engine by then).  jax offers no per-listener
    deregistration, so the listeners live for the process — they are two
    dict increments per compile, nothing on the hot path.  The span
    table's ``compile.*`` names start at zero here, so a run in which
    jax reports no such duration reads 0, not nothing."""
    global _listeners_installed
    if _listeners_installed:
        return
    import jax.monitoring

    with _span_lock:
        for kept in _DURATION_SPANS.values():
            _span_ended.setdefault(kept, [0, 0])

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_scalar_listener(_on_scalar)
    _listeners_installed = True


def counters() -> Dict[str, Any]:
    """Snapshot of the cumulative retrace counters.

    ``program_traces`` counts traced applications of user programs
    (``Program.call`` invocations under tracing, analysis excluded);
    ``backend_compiles`` counts XLA compiles process-wide, including the
    engine's eager glue ops (slices/concats), so it is an upper bound on
    program compiles; ``by_verb`` attributes both to the verb that was
    running.  The span table rides along: ``span_n.<name>`` spans ended
    and ``span_ns.<name>`` their nanoseconds, one pair for every span
    name that has ended in the process.  Diff two snapshots
    (:func:`counters_delta`) to meter one region."""
    with _counters_lock:
        snap: Dict[str, Any] = dict(_counters)
        snap["by_verb"] = {k: dict(v) for k, v in _by_verb.items()}
    for name, (n, ns) in _span_totals().items():
        snap[_SPAN_N + name] = n
        snap[_SPAN_NS + name] = ns
    return snap


# high-water gauges among ``_counters``: read absolute (after
# ``reset_peak_host_bytes()``), never differenced
_GAUGE_COUNTERS = frozenset({"peak_host_bytes"})


def counters_delta(
    before: Dict[str, Any], after: Optional[Dict[str, Any]] = None
) -> Dict[str, int]:
    """``after - before`` for the scalar counters (``after`` defaults to
    a fresh snapshot): every key ``_counters`` declares but the gauges,
    and every entry of the span table ``after`` holds."""
    after = after if after is not None else counters()
    keys = [k for k in _counters if k not in _GAUGE_COUNTERS]
    keys += [k for k in after if k.startswith(_SPAN_KEYS)]
    return {k: after[k] - before.get(k, 0) for k in keys}


# -- flight recorder (round 13) -----------------------------------------------
#
# A bounded ring buffer of structured events, recorded at BLOCK (never
# per-element) granularity by the execution stack: engine dispatch loops,
# prefetch staging lanes, PoolRun readback, fault-tolerance instants,
# cache evictions/spills, streaming windows, and the bridge request
# lifecycle — every :class:`span` and :func:`instant`.  Off by default:
# a span then appends nothing.  Events carry perf_counter-derived
# microsecond timestamps relative to one process epoch; ``dump_trace``
# renders them as Chrome-trace JSON with one track ("thread") per device
# / staging lane, which Perfetto and chrome://tracing open directly.

ENV_TRACE = "TFS_TRACE"
ENV_TRACE_EVENTS = "TFS_TRACE_EVENTS"
DEFAULT_TRACE_EVENTS = 65536

_TRACE_TRUTHY = ("1", "true", "yes", "on")

_trace_lock = threading.Lock()
_trace_buf: "collections.deque" = collections.deque()
_trace_state: Dict[str, Any] = {
    # tri-state: None follows TFS_TRACE; True/False is an API pin
    # (enable_trace()/disable_trace()), which wins over the env so tests
    # control the recorder regardless of the suite's pinned baseline
    "override": None,
    "capacity": None,  # None follows TFS_TRACE_EVENTS
    "drops": 0,
    "epoch_ns": time.perf_counter_ns(),
    "on": False,  # trace_enabled()'s kept answer; resolved below
}


def trace_enabled() -> bool:
    """Whether the flight recorder is on (API override, else
    ``TFS_TRACE``).  Resolves the answer afresh and keeps it: a span
    consults the kept answer — one dict read, where reading the
    environment costs more than the whole of an idle span — so
    ``TFS_TRACE`` counts as it stood at import, or at the last call of
    this function, :func:`enable_trace` or :func:`disable_trace`."""
    ov = _trace_state["override"]
    on = (
        bool(ov)
        if ov is not None
        else envutil.env_raw(ENV_TRACE).lower() in _TRACE_TRUTHY
    )
    _trace_state["on"] = on
    return on


def enable_trace(capacity: Optional[int] = None) -> None:
    """Turn the flight recorder on (wins over ``TFS_TRACE``).
    ``capacity`` overrides ``TFS_TRACE_EVENTS`` for the ring buffer."""
    if capacity is not None:
        _trace_state["capacity"] = max(1, int(capacity))
    _trace_state["override"] = _trace_state["on"] = True


def disable_trace() -> None:
    """Pin the flight recorder off (wins over ``TFS_TRACE``)."""
    _trace_state["override"] = _trace_state["on"] = False


trace_enabled()  # TFS_TRACE as the process started


def clear_trace() -> None:
    """Drop every buffered event and reset the drop counter (the epoch
    is kept: timestamps stay comparable across clears)."""
    with _trace_lock:
        _trace_buf.clear()
        _trace_state["drops"] = 0


def _trace_capacity() -> int:
    cap = _trace_state["capacity"]
    if cap is not None:
        return cap
    return env_int(ENV_TRACE_EVENTS, DEFAULT_TRACE_EVENTS, floor=1)


def _trace_append(ev: Dict[str, Any]) -> None:
    cap = _trace_capacity()
    with _trace_lock:
        _trace_buf.append(ev)
        while len(_trace_buf) > cap:
            # ring semantics: the OLDEST event drops and is accounted —
            # a dump that hit capacity says how much history it lost
            _trace_buf.popleft()
            _trace_state["drops"] += 1


_now_ns = time.perf_counter_ns


def _ring_event(
    name: str, ph: str, track: str, t0_ns: int, dur_ns: Optional[int],
    args: Dict[str, Any],
) -> None:
    """Append one event to the ring (the recorder is known to be on)."""
    ev: Dict[str, Any] = {
        "name": name,
        "ph": ph,
        "track": track,
        "ts": round((t0_ns - _trace_state["epoch_ns"]) / 1e3, 3),
    }
    if dur_ns is not None:
        ev["dur"] = round(max(0, dur_ns) / 1e3, 3)
    if args:
        ev["args"] = args
    _trace_append(ev)


# -- the span table ------------------------------------------------------------
#
# Where a span ends it adds (1, its nanoseconds) to ``[count, ns]`` under
# its name; an instant adds (1, 0).  Always on, and the only declaration
# a span's counter has: ``counters()`` reads the table out as
# ``span_n.<name>`` / ``span_ns.<name>``.  A thread adds into a table of
# its own, so the path every span takes holds no lock (measured beside
# one lock for all: +150 ns a span against +350 on the dev box, and
# handler threads end ``decode.request`` / ``bridge.request`` while the
# driver thread ends ``decode.step.*``); ``_span_lock`` is taken by a
# thread's FIRST span of a name, which makes the entry, and by a
# snapshot, which therefore never meets a table that grows under it.
# Exact: N threads x M spans read N x M once the threads are quiet.  A
# snapshot of a thread in mid-span-end may see the count one ahead of
# the time.  The table relies on names being stable (a name built from
# a block index would grow it without bound); the request ledger is not
# fed from it.

_SPAN_N = "span_n."
_SPAN_NS = "span_ns."
_SPAN_KEYS = (_SPAN_N, _SPAN_NS)

_span_tls = threading.local()  # .table: the calling thread's {name: [n, ns]}
_span_lock = threading.Lock()
_span_tables: List[Tuple[threading.Thread, Dict[str, List[int]]]] = []
_span_ended: Dict[str, List[int]] = {}  # tables of threads that are gone


def _span_fold(
    into: Dict[str, List[int]], table: Dict[str, List[int]]
) -> None:
    for name, (n, ns) in table.items():
        e = into.get(name)
        if e is None:
            into[name] = [n, ns]
        else:
            e[0] += n
            e[1] += ns


def _span_sweep() -> None:
    """Fold the tables of threads that have ended into ``_span_ended``
    (``_span_lock`` held).  Liveness is asked before the table is read:
    a dead thread's table is final."""
    live = []
    for thread, table in _span_tables:
        if thread.is_alive():
            live.append((thread, table))
        else:
            _span_fold(_span_ended, table)
    _span_tables[:] = live


def _span_entry(name: str) -> List[int]:
    """The calling thread's ``[count, ns]`` for ``name``, made here on
    the thread's first span of that name."""
    with _span_lock:
        table = getattr(_span_tls, "table", None)
        if table is None:
            # a new thread: the moment to drop those that are gone, so a
            # server that is never scraped keeps one table a LIVE thread
            _span_sweep()
            table = _span_tls.table = {}
            _span_tables.append((threading.current_thread(), table))
        return table.setdefault(name, [0, 0])


def _span_add(name: str, ns: int) -> None:
    """One span of ``ns`` under ``name``: ``span.end``'s, an instant's
    (no time), or one timed by somebody else (jax's compile
    durations)."""
    try:
        e = _span_tls.table[name]
    except (AttributeError, KeyError):
        e = _span_entry(name)
    e[0] += 1
    e[1] += ns


def _span_totals() -> Dict[str, List[int]]:
    """``{name: [count, ns]}`` summed over every thread, live or gone."""
    with _span_lock:
        _span_sweep()
        total = {name: list(e) for name, e in _span_ended.items()}
        for _, table in _span_tables:
            _span_fold(total, table)
    return total


class span(_TraceAnnotation):
    """THE span primitive: one program span, written in one call to
    every place a span can go.

    * Always: it IS a ``jax.profiler.TraceAnnotation`` named
      ``"tfs:" + name`` on the calling thread.  With no profiler session
      it costs about a microsecond and records nothing; inside one —
      ``jax.profiler.start_trace(dir)`` ... ``stop_trace()`` around the
      workload, the benchmark's or an operator's — the span lands in the
      profiler's own trace, on the device trace's clock, with ``args``
      as event stats.
    * Always: where it ends, its count and its nanoseconds go into the
      span table under ``name`` (``span_n.<name>`` / ``span_ns.<name>``
      in :func:`counters`), the calling thread's own, no lock.
    * Only when the flight recorder is on (:func:`trace_enabled`): one
      complete ("X") event on ``track`` in the ring, for
      :func:`dump_trace`.

    Names are STABLE (``engine.block``, never ``map_blocks b3``; the
    span table keeps an entry a name for the life of the process): what
    varies rides in ``args`` (JSON-safe primitives).  The active
    request's ``cid`` is added to both.  The span starts when it is
    made and ends at :meth:`end`: ``with span(...) as sp:`` calls it, or
    ``sp = span(...)`` ... ``sp.end(**late)`` where the code between is
    a loop body with its own exits (a span never ended leaves no ring
    event, and its annotation closes when the object is dropped).
    ``late`` — arguments known only at the end (``shard_hit``) — reach
    the ring only, as does a ``track`` set meanwhile.  ``ns`` holds the
    duration once ended (``end`` returns it), so a time counter is
    taken at exactly the span's boundaries."""

    __slots__ = ("name", "track", "args", "ns", "_t0")

    def __init__(self, name: str, track: str, **args: Any):
        led = _request_ctx.get()
        if led is not None and "cid" not in args:
            # correlation (round 15): every span opened under a request
            # context carries its cid, so one search strings a request's
            # bridge/engine/staging events together
            args["cid"] = led.correlation_id
        _TraceAnnotation.__init__(self, "tfs:" + name, **args)
        self.name = name
        self.track = track
        self.args = args
        self._t0 = _now_ns()

    def end(self, **late: Any) -> int:
        ns = self.ns = _now_ns() - self._t0
        _TraceAnnotation.__exit__(self, None, None, None)
        _span_add(self.name, ns)
        if _trace_state["on"]:
            if late:
                self.args.update(late)
            _ring_event(self.name, "X", self.track, self._t0, ns, self.args)
        return ns

    def __exit__(self, exc_type, exc, tb) -> bool:
        # end() and _span_add, spelled out: two calls less on the path
        # every ``with span(...)`` takes (a decode step ends four)
        ns = self.ns = _now_ns() - self._t0
        _TraceAnnotation.__exit__(self, None, None, None)
        try:
            e = _span_tls.table[self.name]
        except (AttributeError, KeyError):
            e = _span_entry(self.name)
        e[0] += 1
        e[1] += ns
        if _trace_state["on"]:
            _ring_event(self.name, "X", self.track, self._t0, ns, self.args)
        return False


def instant(name: str, track: str = "events", **args: Any) -> None:
    """The instant form of :class:`span` — retries, quarantines,
    evictions, a request's admit / first-token / retire stamps: things
    that happen AT a moment.  A zero-length ``tfs:`` annotation in the
    profiler's trace, one more under ``span_n.<name>`` in the span
    table (no time), an "i" event in the ring when the recorder is
    on."""
    led = _request_ctx.get()
    if led is not None and "cid" not in args:
        args["cid"] = led.correlation_id
    with _TraceAnnotation("tfs:" + name, **args):
        pass
    _span_add(name, 0)
    if _trace_state["on"]:
        _ring_event(name, "i", track, _now_ns(), None, args)


def trace_depth() -> int:
    """Events currently buffered."""
    with _trace_lock:
        return len(_trace_buf)


def trace_drops() -> int:
    """Events dropped to the ring capacity since the last
    :func:`clear_trace`."""
    with _trace_lock:
        return _trace_state["drops"]


def trace_events(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The buffered events (oldest first; the last ``n`` when given), as
    DEEP copies — callers cannot mutate the live ring, nested ``args``
    dicts included (the same guarantee :func:`last_spans` makes)."""
    with _trace_lock:
        evs = list(_trace_buf)
    if n is not None:
        evs = evs[-n:]
    return [copy.deepcopy(ev) for ev in evs]


def dump_trace(path: str) -> str:
    """Write the buffered events as Chrome-trace JSON to ``path`` and
    return it.  One pseudo-thread per distinct track (named via
    ``thread_name`` metadata), so Perfetto / chrome://tracing render one
    swim lane per device, per staging lane, per bridge handler thread.
    ``otherData.dropped_events`` records how much history the ring lost."""
    with _trace_lock:
        events = [dict(ev) for ev in _trace_buf]
        drops = _trace_state["drops"]
    tracks = sorted({ev["track"] for ev in events})
    tids = {t: i + 1 for i, t in enumerate(tracks)}
    out: List[Dict[str, Any]] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 0,
            "tid": 0,
            "args": {"name": "tensorframes_tpu"},
        }
    ]
    for t, tid in tids.items():
        out.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 0,
                "tid": tid,
                "args": {"name": t},
            }
        )
    for ev in events:
        rec: Dict[str, Any] = {
            "name": ev["name"],
            "ph": ev["ph"],
            "pid": 0,
            "tid": tids[ev["track"]],
            "ts": ev["ts"],
        }
        if ev["ph"] == "X":
            rec["dur"] = ev["dur"]
        else:
            rec["s"] = "t"  # instant scope: thread
        if "args" in ev:
            rec["args"] = ev["args"]
        out.append(rec)
    payload = {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {"dropped_events": drops},
    }
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


# -- latency histograms (round 13) -------------------------------------------
#
# Always-on, lock-cheap latency distributions: log2 buckets from ~1 µs to
# 64 s (28 counters per series), one bisect + three scalar updates per
# observation.  Two families: ``("verb", <verb>)`` recorded by every
# verb_span exit, and ``("bridge", <method>)`` recorded by the bridge
# server around the WHOLE request (admission wait included).  Quantiles
# are derived by linear interpolation inside the landing bucket — exact
# to the bucket's factor-of-2 bounds, which is what p50/p95/p99 SLO
# tracking needs without per-sample storage.

_LATENCY_MIN_EXP = -20  # 2**-20 s ≈ 0.95 µs
_LATENCY_MAX_EXP = 6  # 64 s; beyond that lands in the +Inf bucket
_LATENCY_BOUNDS = [
    2.0 ** e for e in range(_LATENCY_MIN_EXP, _LATENCY_MAX_EXP + 1)
]


def _latency_quantile(
    counts: Sequence[int], count: int, max_: float, q: float
) -> float:
    """Estimated ``q``-quantile over one series' state: linear
    interpolation inside the bucket the rank lands in (the overflow
    bucket interpolates up to the observed max)."""
    if count == 0:
        return 0.0
    target = q * count
    cum = 0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if cum + c >= target:
            lo = _LATENCY_BOUNDS[i - 1] if i > 0 else 0.0
            hi = (
                _LATENCY_BOUNDS[i]
                if i < len(_LATENCY_BOUNDS)
                else max(max_, lo)
            )
            return lo + (hi - lo) * (target - cum) / c
        cum += c
    return max_


class _LatencyHisto:
    """One series' bucket counts + count/sum/max (no per-sample state).

    Round-15 torn-read fix: each histogram carries its OWN lock.
    ``record`` mutates four fields; before this round the global
    ``_latency_lock`` covered both recording and the WHOLE scrape
    render, so a scrape serialized every concurrent verb's latency
    recording for its full duration — and any reader skipping the
    global lock could observe a half-applied observation (count moved,
    sum not yet).  Now recording takes only this lock, and readers copy
    a consistent state tuple per series (:meth:`snapshot_state`) then
    render outside all locks."""

    __slots__ = ("lock", "counts", "count", "sum", "max")

    def __init__(self):
        self.lock = threading.Lock()
        self.counts = [0] * (len(_LATENCY_BOUNDS) + 1)  # + overflow
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def record(self, seconds: float) -> None:
        with self.lock:
            self.counts[bisect.bisect_left(_LATENCY_BOUNDS, seconds)] += 1
            self.count += 1
            self.sum += seconds
            if seconds > self.max:
                self.max = seconds

    def snapshot_state(self) -> Tuple[List[int], int, float, float]:
        """A consistent point-in-time copy of (counts, count, sum, max)
        — no observation can be half-visible across the four fields."""
        with self.lock:
            return list(self.counts), self.count, self.sum, self.max

    def quantile(self, q: float) -> float:
        counts, count, _, max_ = self.snapshot_state()
        return _latency_quantile(counts, count, max_, q)


_latency_lock = threading.Lock()
_latency: Dict[Tuple[str, str], _LatencyHisto] = {}

# kind -> (Prometheus family, label name); unknown kinds render
# generically as tfs_<kind>_latency_seconds{label=...}
_LATENCY_FAMILIES = {"verb": "verb", "bridge": "method"}


def record_latency(kind: str, label: str, seconds: float) -> None:
    """Record one observation into the ``(kind, label)`` series (and
    into the active request's ledger, round 15)."""
    with _latency_lock:
        h = _latency.get((kind, label))
        if h is None:
            h = _latency[(kind, label)] = _LatencyHisto()
    h.record(seconds)
    led = _request_ctx.get()
    if led is not None:
        led.note_latency(kind, label, seconds)


def _latency_state() -> List[Tuple[str, str, List[int], int, float, float]]:
    """A consistent snapshot of every series: the registry is copied
    under the registry lock — so :func:`reset_latency`'s clear is atomic
    with respect to any scrape (a scrape sees the whole pre-reset set or
    none of it, never a half-cleared mix) — then each series' state is
    copied under its own lock.  Rendering happens outside all locks."""
    with _latency_lock:
        items = sorted(_latency.items())
    return [
        (kind, label) + h.snapshot_state() for (kind, label), h in items
    ]


def latency_snapshot() -> Dict[str, Dict[str, Any]]:
    """Per-series summary — ``{"verb:map_blocks": {count, sum_s, max_s,
    p50_s, p95_s, p99_s}, ...}`` — the programmatic face of the
    histograms (``metrics_text`` is the operator face)."""
    out: Dict[str, Dict[str, Any]] = {}
    for kind, label, counts, count, sum_, max_ in _latency_state():
        out[f"{kind}:{label}"] = {
            "count": count,
            "sum_s": round(sum_, 6),
            "max_s": round(max_, 6),
            "p50_s": round(_latency_quantile(counts, count, max_, 0.50), 9),
            "p95_s": round(_latency_quantile(counts, count, max_, 0.95), 9),
            "p99_s": round(_latency_quantile(counts, count, max_, 0.99), 9),
        }
    return out


def reset_latency() -> None:
    """Drop every latency series (tests / bench legs metering their own
    window).  Atomic w.r.t. concurrent scrapes: readers copy the
    registry under the same lock, so a scrape racing the reset renders
    either the full pre-reset set or the empty post-reset one."""
    with _latency_lock:
        _latency.clear()


# -- metrics exposition (round 13) -------------------------------------------

ENV_METRICS_PORT = "TFS_METRICS_PORT"

# gauge providers: components with live state the exposition should poll
# (the bridge server registers its admission gauges here so the stdlib
# HTTP endpoint sees them without observability importing the bridge)
_gauges_lock = threading.Lock()
_gauge_providers: Dict[str, Callable[[], float]] = {}


def register_gauge(name: str, fn: Callable[[], Any]) -> None:
    """Register a zero-arg callable polled by :func:`metrics_text`
    (last registration wins; provider exceptions skip the gauge rather
    than failing the scrape).  A provider returning a number becomes
    gauge ``name``; a provider returning a Mapping contributes one
    gauge per item — the grouped form exists so related gauges (the
    bridge's admission inflight/queued/draining) come from ONE state
    snapshot per scrape instead of three racing reads."""
    with _gauges_lock:
        _gauge_providers[name] = fn


def unregister_gauge(name: str, fn: Optional[Callable] = None) -> None:
    """Remove gauge ``name`` — only when still bound to ``fn`` if given,
    so a closed server cannot unregister its replacement's provider."""
    with _gauges_lock:
        if fn is None or _gauge_providers.get(name) is fn:
            _gauge_providers.pop(name, None)


def _fmt_metric(v: Any) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    f = float(v)
    return str(int(f)) if f.is_integer() and abs(f) < 1e15 else repr(f)


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def metrics_text(
    extra_gauges: Optional[Mapping[str, Any]] = None
) -> str:
    """The process's metrics in Prometheus text exposition format
    (0.0.4): every scalar counter as ``tfs_<name>_total``, the span
    table as ``tfs_span_total{span=...}`` and
    ``tfs_span_seconds_total{span=...}``, the gauges
    (host-byte high-water, HBM budget occupancy, trace-recorder
    depth/drops, registered providers, ``extra_gauges``), and the
    latency histograms with derived p50/p95/p99 quantile gauges.  Served
    by the bridge's ungated ``metrics`` RPC and the optional
    ``TFS_METRICS_PORT`` HTTP endpoint."""
    lines: List[str] = []
    emitted: set = set()  # family names already declared (no dup TYPEs)
    c = counters()
    for k in sorted(c):
        if k == "by_verb" or k in _GAUGE_COUNTERS or k.startswith(_SPAN_KEYS):
            # peak_host_bytes is a gauge, not a counter; the span table
            # is two labelled families, below
            continue
        name = f"tfs_{k}_total"
        emitted.add(name)
        lines.append(f"# TYPE {name} counter")
        lines.append(f"{name} {_fmt_metric(c[k])}")
    spans = sorted(_span_totals().items())
    for fam, field, per in (
        ("tfs_span_total", 0, 1),
        ("tfs_span_seconds_total", 1, 1e9),
    ):
        emitted.add(fam)
        lines.append(f"# TYPE {fam} counter")
        for name, entry in spans:
            lines.append(
                f'{fam}{{span="{_escape_label(name)}"}} '
                f"{_fmt_metric(entry[field] / per)}"
            )
    gauges: Dict[str, Any] = {
        "tfs_peak_host_bytes": c["peak_host_bytes"],
        "tfs_live_host_bytes": live_host_bytes(),
        "tfs_trace_buffer_events": trace_depth(),
        "tfs_trace_dropped_events": trace_drops(),
    }
    try:  # lazy: frame_cache imports observability, never the reverse
        from .ops import frame_cache

        gauges["tfs_hbm_budget_bytes"] = frame_cache.hbm_budget()
        gauges["tfs_hbm_resident_bytes"] = (
            frame_cache.budget_bytes_resident()
        )
    except Exception:  # noqa: BLE001 — a scrape must never fail on this
        pass
    with _gauges_lock:
        providers = dict(_gauge_providers)
    for name, fn in providers.items():
        try:
            v = fn()
        except Exception:  # noqa: BLE001 — skip a sick provider
            continue
        if isinstance(v, collections.abc.Mapping):
            gauges.update(v)  # grouped provider: one snapshot, N gauges
        else:
            gauges[name] = v
    for k, v in (extra_gauges or {}).items():
        gauges[k] = v
    for name in sorted(gauges):
        if name in emitted:
            # a provider/extra gauge colliding with a counter family
            # would emit a duplicate TYPE line and break strict
            # Prometheus parsers — the counter wins, the gauge is
            # skipped (register under a distinct name instead)
            continue
        emitted.add(name)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {_fmt_metric(gauges[name])}")
    # per-tenant request attribution (round 15): bounded-cardinality
    # labelled families fed by finished RequestLedgers
    req = request_metrics()
    if req:
        for field in _REQUEST_AGG_FIELDS:
            fam = f"tfs_request_{field}_total"
            if fam in emitted:
                continue  # defensive: never emit a duplicate family
            emitted.add(fam)
            lines.append(f"# TYPE {fam} counter")
            for tenant in sorted(req):
                lines.append(
                    f'{fam}{{tenant="{_escape_label(tenant)}"}} '
                    f"{_fmt_metric(req[tenant][field])}"
                )
    # latency histograms: rendered from consistent per-series snapshots
    # (round 15 — no lock is held while formatting, so a scrape cannot
    # serialize concurrent verbs' record_latency calls)
    by_kind: Dict[str, List[Tuple[str, List[int], int, float, float]]] = {}
    for kind, label, counts, count, sum_, max_ in _latency_state():
        by_kind.setdefault(kind, []).append(
            (label, counts, count, sum_, max_)
        )
    for kind in sorted(by_kind):
        fam = f"tfs_{kind}_latency_seconds"
        lab = _LATENCY_FAMILIES.get(kind, "label")
        lines.append(f"# TYPE {fam} histogram")
        for label, counts, count, sum_, max_ in by_kind[kind]:
            sel = f'{lab}="{_escape_label(label)}"'
            cum = 0
            for i, cnt in enumerate(counts):
                cum += cnt
                le = (
                    repr(_LATENCY_BOUNDS[i])
                    if i < len(_LATENCY_BOUNDS)
                    else "+Inf"
                )
                lines.append(
                    f'{fam}_bucket{{{sel},le="{le}"}} {cum}'
                )
            lines.append(f"{fam}_sum{{{sel}}} {repr(sum_)}")
            lines.append(f"{fam}_count{{{sel}}} {count}")
        qfam = f"tfs_{kind}_latency_quantile_seconds"
        lines.append(f"# TYPE {qfam} gauge")
        for label, counts, count, sum_, max_ in by_kind[kind]:
            sel = f'{lab}="{_escape_label(label)}"'
            for qname, q in (
                ("p50", 0.50), ("p95", 0.95), ("p99", 0.99)
            ):
                lines.append(
                    f'{qfam}{{{sel},q="{qname}"}} '
                    f"{repr(_latency_quantile(counts, count, max_, q))}"
                )
    return "\n".join(lines) + "\n"


_metrics_httpd = None
_metrics_httpd_lock = threading.Lock()


def start_metrics_server(port: int, host: str = "127.0.0.1"):
    """Serve ``GET /metrics`` (Prometheus text) on a stdlib HTTP server
    running on a daemon thread; returns the server (``.server_address``
    carries the bound port — ``port=0`` binds ephemeral).  Idempotent:
    a process runs at most one metrics server."""
    import http.server

    class _MetricsHandler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - http.server API
            if self.path.split("?", 1)[0] != "/metrics":
                self.send_response(404)
                self.end_headers()
                return
            body = metrics_text().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type",
                "text/plain; version=0.0.4; charset=utf-8",
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # noqa: D102 - silence stderr
            pass

    global _metrics_httpd
    with _metrics_httpd_lock:
        if _metrics_httpd is not None:
            return _metrics_httpd
        httpd = http.server.ThreadingHTTPServer((host, port), _MetricsHandler)
        httpd.daemon_threads = True
        threading.Thread(
            target=httpd.serve_forever, name="tfs-metrics", daemon=True
        ).start()
        _metrics_httpd = httpd
        logger.info(
            "metrics endpoint serving on http://%s:%d/metrics",
            *httpd.server_address[:2],
        )
    return httpd


def stop_metrics_server() -> None:
    global _metrics_httpd
    with _metrics_httpd_lock:
        httpd, _metrics_httpd = _metrics_httpd, None
    if httpd is not None:
        httpd.shutdown()
        httpd.server_close()


def maybe_start_metrics_server():
    """Start the ``/metrics`` endpoint when ``TFS_METRICS_PORT`` names a
    port (> 0); None otherwise.  Called by ``BridgeServer.__init__`` so
    a served deployment gets scrape-ability from the env alone; safe to
    call repeatedly.  A bind failure (port already held — e.g. two
    server processes on one host, or a stale restart) logs once and
    returns None: optional telemetry must never stop the data plane
    from starting.  Call :func:`start_metrics_server` directly when a
    failed bind should be an error."""
    port = env_int(ENV_METRICS_PORT, 0)
    if port <= 0:
        return None
    try:
        return start_metrics_server(port)
    except OSError as e:
        warn_once(
            logger,
            f"observability:metrics-port:{port}",
            "could not bind the %s=%d metrics endpoint (%s); continuing "
            "without it",
            ENV_METRICS_PORT,
            port,
            e,
        )
        return None


def initialize_logging(level=logging.INFO, stream=None) -> None:
    """Configure the framework loggers with a sane handler/format.

    Reference analog: ``PythonInterface.initialize_logging``
    (``PythonInterface.scala:29-44``)."""
    handler = logging.StreamHandler(stream)
    handler.setFormatter(
        logging.Formatter(
            "%(asctime)s %(name)s %(levelname)s %(message)s"
        )
    )
    logger.handlers[:] = [handler]
    logger.setLevel(level)
    logger.propagate = False


def enable() -> None:
    """Turn on per-verb phase spans (``last_spans``).  For a device
    timeline, wrap the workload in ONE profiler session —
    ``jax.profiler.start_trace(dir)`` ... ``jax.profiler.stop_trace()``
    — and every :class:`span` of the program lands in it beside the
    device operations (``docs/OBSERVABILITY.md``)."""
    _state["enabled"] = True


def disable() -> None:
    _state["enabled"] = False


def is_enabled() -> bool:
    return bool(_state["enabled"])


def last_spans(n: int = 10) -> List[Dict[str, Any]]:
    """The most recent verb spans, newest last — DEEP copies, so a
    caller mutating a returned record's nested ``retrace`` / annotation
    dicts (bench postprocessing does exactly that) can never corrupt
    the live buffer."""
    return [copy.deepcopy(s) for s in _state["spans"][-n:]]


class _Span:
    """One verb invocation's phase timings."""

    __slots__ = ("verb", "meta", "phases", "_t0", "_last", "_counters0")

    def __init__(self, verb: str, meta: Dict[str, Any]):
        self.verb = verb
        self.meta = meta
        led = _request_ctx.get()
        if led is not None:
            # request correlation (round 15): the span record names the
            # request it ran under, like every trace event does
            meta.setdefault("cid", led.correlation_id)
        self.phases: Dict[str, float] = {}
        # counters() copies UNDER the counters lock: bridge handler
        # threads (and pool lane fallbacks) bump concurrently, and an
        # unlocked dict(_counters) can observe a torn mid-update view
        # exactly when the span's retrace delta matters most
        self._counters0 = counters()
        self._t0 = time.perf_counter()
        self._last = self._t0

    def mark(self, phase: str) -> None:
        """Close the current phase under ``phase``."""
        now = time.perf_counter()
        self.phases[phase] = self.phases.get(phase, 0.0) + (now - self._last)
        self._last = now

    def annotate(self, key: str, value: Any) -> None:
        """Attach structured metadata to this span's record (e.g. the
        engine's prefetch/overlap stats, a roofline digest)."""
        self.meta[key] = value

    def _finish(self) -> Dict[str, Any]:
        total = time.perf_counter() - self._t0
        rec = {
            "verb": self.verb,
            **self.meta,
            "retrace": counters_delta(self._counters0),
            "phases_s": {k: round(v, 6) for k, v in self.phases.items()},
            "total_s": round(total, 6),
        }
        spans = _state["spans"]
        spans.append(rec)
        del spans[:-_MAX_SPANS]
        _verb_log.info(
            "%s rows=%s blocks=%s %s total=%.4fs",
            self.verb,
            self.meta.get("rows"),
            self.meta.get("blocks"),
            " ".join(f"{k}={v:.4f}s" for k, v in self.phases.items()),
            total,
        )
        return rec


class _NullSpan:
    __slots__ = ()

    def mark(self, phase: str) -> None:  # noqa: D102
        pass

    def annotate(self, key: str, value: Any) -> None:  # noqa: D102
        pass


_NULL = _NullSpan()

_MAP_VERBS = ("map_blocks", "map_rows")


@contextlib.contextmanager
def verb_span(verb: str, rows: int, blocks: int):
    """Context manager wrapping one verb invocation.

    Yields a span with ``.mark(phase)``; a no-op singleton when disabled.
    Always tags the thread with the verb name so the retrace counters
    attribute traces/compiles per verb even with spans disabled; always
    records the verb's wall time into the latency histograms (round 13)
    and opens the whole-verb :class:`span` (``engine.map`` for the two
    map verbs, ``engine.verb`` for the rest, on the ``verbs`` track)."""
    token = _current_verb.set(verb)
    sp = span(
        "engine.map" if verb in _MAP_VERBS else "engine.verb",
        "verbs", verb=verb, rows=rows, blocks=blocks,
    )
    try:
        if not _state["enabled"]:
            yield _NULL
            return
        vspan = _Span(verb, {"rows": rows, "blocks": blocks})
        try:
            yield vspan
        except BaseException:
            # failed verbs must still record: the span is the diagnostic
            vspan.meta["failed"] = True
            raise
        finally:
            vspan._finish()
    finally:
        _current_verb.reset(token)
        ns = sp.end()
        if not verb.startswith("bridge:"):
            # bridge methods are recorded end-to-end (admission wait
            # included) by the server itself — recording the execution
            # span here too would double-count the family
            record_latency("verb", verb, ns / 1e9)
