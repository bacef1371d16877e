"""Sharded HBM frame cache: block-affinity placement + LRU byte budget.

``frame.cache()`` (round 2) pins a frame's columns in device memory so
iterative pipelines pay zero H2D traffic — the Spark ``df.cache()``
analog the reference's demos rely on (``kmeans_demo.py`` caches before
iterating).  But the round-2 cache lives on ONE device, and the engine
deliberately kept device-resident frames off the device pool
(``engine.py``: "splitting a cached column across the pool would shuffle
HBM") — so the exact workloads caching exists for forfeited the whole
round-8 multi-device speedup.

This module removes that trade by changing the *placement unit* from the
column to the **block shard**: ``cache(sharded=True)`` (or
``TFS_CACHE_SHARDED=auto`` while a device pool is active) places each
block's column slices directly on that block's pool device — the same
deterministic least-loaded assignment the scheduler uses
(:func:`tensorframes_tpu.ops.device_pool.assign`), so a later verb's
block->device plan MATCHES the residency plan and every block executes
on the device that already holds it.  The engine's affinity dispatch
(``block_loop.place`` -> ``"affinity"``) then runs device-resident frames
across the whole pool with no staging lanes and no H2D.

Design rules:

* **The host copy stays authoritative.**  A sharded cache never replaces
  the frame's host columns — the shards are an acceleration layer.  That
  is what makes LRU eviction free (drop the shard, the bytes are still
  on host), fault-tolerance re-staging possible (a quarantined device's
  cached blocks rebuild on a healthy device from host), and retry
  semantics unchanged (every retry re-stages fresh host buffers).
* **Shards are shared state: never donated, never mutated.**  The
  affinity dispatch always uses the non-donating executables, exactly
  like the round-2 single-device cache.
* **Bounded HBM** (``TFS_HBM_BUDGET`` bytes, 0/unset = unlimited): every
  resident shard is bytes-accounted in one process-wide LRU; inserting
  past the budget evicts the least-recently-used shard (any cache, any
  frame) back to its authoritative host copy and counts
  ``cache_evictions``.  An evicted block simply re-stages from host on
  its next use — correctness never depends on residency.
* **Donation-adoption** (``Pipeline`` pooled chains): a pooled map
  chain's per-device output buffers are adopted in place as the cached
  shards of the successor frame — the next epoch of an iterative
  pipeline reads them straight from HBM with zero re-staging — while the
  overlapped D2H readback still materialises the authoritative host
  copy.  Adopted shards obey the same budget.

Knobs:

* ``TFS_CACHE_SHARDED`` — ``auto`` (default: shard when the device pool
  resolves >= 2 devices), ``1``/``always`` (shard whenever >= 2 local
  devices exist, pool knob or not), ``0``/``off`` (never shard;
  ``cache()`` keeps the round-2 single-device behavior).
* ``TFS_HBM_BUDGET`` — resident-shard byte budget (accepts plain bytes
  or ``K``/``M``/``G`` suffixes; 0/unset = unlimited).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import observability
from .. import envutil
from ..envutil import parse_bytes, warn_once
from . import device_pool

logger = logging.getLogger("tensorframes_tpu.frame_cache")

ENV_SHARDED = "TFS_CACHE_SHARDED"
ENV_BUDGET = "TFS_HBM_BUDGET"
ENV_TENANT_BUDGET = "TFS_CACHE_TENANT_BUDGET"

def _warn_once(key: str, msg: str, *args) -> None:
    warn_once(logger, "frame_cache:" + key, msg, *args)


def tenant_budget() -> int:
    """Per-tenant resident-shard byte budget
    (``TFS_CACHE_TENANT_BUDGET``; 0 = no per-tenant cap, round 19).

    Layered UNDER ``TFS_HBM_BUDGET``: a tenant whose resident shards
    would exceed this cap evicts its OWN least-recently-used shards
    first, so one tenant's epoch loop cannot flush every other
    tenant's warm shards out of the shared LRU.  Tenant identity is
    billed from real PR 10 ledger usage: the request ledger active when
    a cache is built/adopted names the owning tenant."""
    raw = envutil.env_raw(ENV_TENANT_BUDGET)
    if not raw.strip():
        return 0
    parsed = parse_bytes(raw)
    if parsed is None:
        _warn_once(
            "tenant_budget:" + raw,
            "%s=%r is malformed; use bytes or a K/M/G suffix. "
            "Treating as no per-tenant cap.",
            ENV_TENANT_BUDGET,
            raw,
        )
        return 0
    return parsed


def _request_tenant() -> Optional[str]:
    """The tenant the active request chain attributes work to (nested
    ledgers may leave ``tenant`` on an outer ledger only)."""
    led = observability.current_request()
    while led is not None:
        if led.tenant:
            return led.tenant
        led = led.parent
    return None


def hbm_budget() -> int:
    """Resident-shard byte budget (``TFS_HBM_BUDGET``; 0 = unlimited).

    Accepts plain bytes or a ``K``/``M``/``G`` binary suffix
    (``envutil.parse_bytes``).  Read per call so tests and bench legs
    can flip it mid-process."""
    raw = envutil.env_raw(ENV_BUDGET)
    if not raw.strip():
        return 0
    parsed = parse_bytes(raw)
    if parsed is None:
        _warn_once(
            "budget:" + raw,
            "%s=%r is malformed; use bytes or a K/M/G suffix. "
            "Treating as unlimited.",
            ENV_BUDGET,
            raw,
        )
        return 0
    return parsed


def shard_devices(explicit: Optional[bool] = None) -> List[Any]:
    """The devices a new sharded cache would place on, or ``[]`` when
    sharding should not engage.

    ``explicit=None`` follows ``TFS_CACHE_SHARDED``: ``auto`` shards
    exactly when the device pool resolves (>= 2 devices), so a cached
    frame's residency plan matches the scheduler that will consume it;
    ``1``/``always`` shards over all local devices even with the pool
    knob off; ``0``/``off`` never shards.  ``explicit=True``/``False``
    (the ``cache(sharded=)`` argument) overrides the env the same way."""
    raw = envutil.env_raw(ENV_SHARDED, "auto").lower()
    if explicit is None:
        if raw in ("0", "off", "false", "no", "none"):
            return []
        if raw in ("1", "always", "true", "yes", "force"):
            explicit = True
        elif raw in ("", "auto"):
            return device_pool.pool_devices()
        else:
            _warn_once(
                "sharded:" + raw,
                "%s=%r is malformed; use 'auto', '1'/'always' or "
                "'0'/'off'. Falling back to 'auto'.",
                ENV_SHARDED,
                raw,
            )
            return device_pool.pool_devices()
    if not explicit:
        return []
    devs = device_pool.pool_devices()
    if devs:
        return devs
    import jax

    devs = list(jax.local_devices())
    return devs if len(devs) >= 2 else []


def _delete_spill_files(spill, tag: str, spilled: set) -> None:
    """GC finalizer body for spill-backed caches: remove whatever shard
    files are still on disk (``delete`` tolerates already-gone keys)."""
    for bi in list(spilled):
        spill.delete(f"{tag}-{bi}")


def array_nbytes(a) -> int:
    """Byte size of one (host or device) array."""
    nb = getattr(a, "nbytes", None)
    if nb is not None:
        return int(nb)
    arr = np.asarray(a)
    return int(arr.nbytes)


class FrameCache:
    """Per-frame shard bookkeeping: ``blocks[bi]`` is a dict of
    device-resident column arrays for block ``bi`` (or ``None`` when the
    block was evicted / never fit the budget), all living on
    ``devices[assignment[bi]]``.

    A cache is attached to exactly one :class:`~tensorframes_tpu.frame.
    TensorFrame` (``frame._cache``) whose host columns remain the
    authoritative copy; the engine consults :func:`active_cache` per
    verb and falls back to host staging for any non-resident block.

    ``spill`` (round 12, out-of-core streaming): a
    :class:`tensorframes_tpu.streaming.spill.SpillStore` (or any object
    with ``put``/``get``/``delete``).  With it set, the cache's frame is
    declared to have NO durable host copy (a streamed window the reader
    has moved past), so the budget LRU's eviction path cannot simply
    drop a shard — :meth:`evict` writes the shard's bytes to disk first
    and :meth:`shard` restores them (disk -> host -> affinity device,
    re-charged against the budget) on the block's next use.  Without
    ``spill`` the round-10 behavior is untouched: eviction is free
    because the host columns are authoritative.

    Known scope limit, deliberate for round 12: a ``TensorFrame``
    object still pins its host column arrays for its own lifetime, so
    while a windowed frame is LIVE its host copy could also serve
    re-staging — the disk copy pays off against lifecycle, not liveness
    (it is what survives once host-column release for windowed caches
    lands; ROADMAP open item).  The mechanism, counters, and tests are
    the contract this round establishes."""

    def __init__(
        self,
        devices: Sequence[Any],
        assignment: Sequence[int],
        adopted: bool = False,
        spill: Optional[Any] = None,
    ):
        self.devices = list(devices)
        self.assignment = list(assignment)
        self.blocks: List[Optional[Dict[str, Any]]] = [None] * len(
            self.assignment
        )
        self.nbytes: List[int] = [0] * len(self.assignment)
        self.adopted = adopted
        self.spill = spill
        # per-tenant budget attribution (round 19): the request ledger
        # active at build/adopt time names the owner; None bills to the
        # shared (un-tenanted) pool, which has no per-tenant cap
        self.tenant: Optional[str] = _request_tenant()
        self._spilled: set = set()
        self._spill_tag = f"shard-{os.getpid()}-{id(self):x}"
        if spill is not None:
            # a cache dropped without uncache() must not leak its spill
            # files on disk; the finalizer holds no reference back to
            # the cache (the set is shared, not captured via self)
            weakref.finalize(
                self, _delete_spill_files, spill, self._spill_tag,
                self._spilled,
            )

    # -- residency -----------------------------------------------------------

    def insert(self, bi: int, shard: Dict[str, Any]) -> bool:
        """Account block ``bi``'s shard against the HBM budget and make
        it resident; returns False (shard dropped) when the budget
        cannot hold it even after evicting every other resident shard."""
        nbytes = sum(array_nbytes(v) for v in shard.values())
        if not _budget.charge(self, bi, nbytes):
            return False
        self.blocks[bi] = dict(shard)
        self.nbytes[bi] = nbytes
        return True

    def _spill_key(self, bi: int) -> str:
        return f"{self._spill_tag}-{bi}"

    def shard(self, bi: int) -> Optional[Dict[str, Any]]:
        """Block ``bi``'s resident shard (LRU-touched), or None.  A
        spill-backed cache restores an evicted shard from disk —
        disk -> host -> the block's affinity device, re-charged against
        the budget (which may evict another shard) — so a windowed
        frame's bytes survive LRU churn instead of vanishing.  The disk
        copy is KEPT after a restore: shards are immutable, so it stays
        valid and the next eviction of this block is a free pointer
        drop instead of a full re-serialize (``_spilled`` therefore
        means "valid disk copy exists", resident or not)."""
        s = self.blocks[bi]
        if s is not None:
            _budget.touch(self, bi)
            return s
        if self.spill is not None and bi in self._spilled:
            host = self.spill.get(self._spill_key(bi))
            if host is None:  # spill file lost: nothing to restore
                self._spilled.discard(bi)
                return None
            import jax

            dev = self.devices[self.assignment[bi]]
            staged = {}
            for name, arr in host.items():
                observability.note_h2d_bytes(arr.nbytes)
                staged[name] = jax.device_put(arr, dev)
            if self.insert(bi, staged):
                observability.instant(
                    "cache.spill_restore", "cache", block=bi
                )
                return self.blocks[bi]
            # the budget cannot hold it even now — the disk copy stays
            # the only copy; the caller falls back
        return None

    def evict(self, bi: int) -> None:
        """Drop block ``bi``'s shard (budget eviction / release path).
        With a durable host copy that is free; a spill-backed cache
        (windowed frame, no host authority) writes the shard to
        ``TFS_SPILL_DIR`` first so the bytes survive — unless a valid
        disk copy from an earlier eviction already exists (shards are
        immutable, so re-writing identical bytes would be pure I/O
        waste in exactly the tight-budget thrash regime spill serves)."""
        shard = self.blocks[bi]
        spilled_now = False
        if (
            shard is not None
            and self.spill is not None
            and bi not in self._spilled
        ):
            host = {k: np.asarray(v) for k, v in shard.items()}
            self.spill.put(self._spill_key(bi), host)
            self._spilled.add(bi)
            spilled_now = True
        if shard is not None:
            observability.instant(
                "cache.evict",
                "cache",
                block=bi,
                bytes=self.nbytes[bi],
                spilled=spilled_now,
            )
        self.blocks[bi] = None
        self.nbytes[bi] = 0

    def block_host(self, bi: int, name: str) -> np.ndarray:
        """Block ``bi``'s column ``name`` as a HOST array, read from the
        resident shard or the spill file WITHOUT charging the budget —
        the read-only materialisation path behind released host columns
        (:class:`SpillBackedColumnData`)."""
        s = self.blocks[bi]
        if s is not None and name in s:
            return np.asarray(s[name])
        if self.spill is not None and bi in self._spilled:
            host = self.spill.get(self._spill_key(bi))
            if host is not None and name in host:
                return host[name]
        raise RuntimeError(
            f"released column {name!r}: block {bi} has neither a "
            f"resident shard nor a spill copy (spill file lost?)"
        )

    def release(self) -> None:
        """Drop every shard and refund the budget (``uncache()``)."""
        _budget.release(self)
        for bi in range(len(self.blocks)):
            self.blocks[bi] = None
            self.nbytes[bi] = 0
        if self.spill is not None:
            for bi in sorted(self._spilled):
                self.spill.delete(self._spill_key(bi))
            self._spilled.clear()

    # -- stats ---------------------------------------------------------------

    def resident_blocks(self) -> int:
        return sum(1 for b in self.blocks if b is not None)

    def resident_bytes_per_device(self) -> List[int]:
        out = [0] * len(self.devices)
        for bi, b in enumerate(self.blocks):
            if b is not None:
                out[self.assignment[bi]] += self.nbytes[bi]
        return out

    def record(self) -> dict:
        """The ``frame_cache`` span annotation body."""
        rec = {
            "devices": len(self.devices),
            "blocks": len(self.blocks),
            "resident_blocks": self.resident_blocks(),
            "resident_bytes_per_device": self.resident_bytes_per_device(),
            "adopted": self.adopted,
        }
        if self.spill is not None:
            rec["spilled_blocks"] = len(self._spilled)
        return rec


class _HbmBudget:
    """Process-wide LRU over every resident shard of every live cache.

    Entries hold weak cache references so a frame dropped without
    ``uncache()`` cannot pin budget forever — its entries fall out on
    the next charge walk.  ``charge`` evicts least-recently-used shards
    (across caches) until the new shard fits; a shard larger than the
    whole budget is refused rather than thrashing everything out."""

    def __init__(self):
        self._lock = threading.Lock()
        # key: (id(cache), bi) ->
        #     (weakref(cache), bi, nbytes, tenant, pinned)
        # ``pinned`` (round 22): the entry is accounting for memory that
        # CANNOT be evicted-and-restored (a live decode sequence's KV
        # pages — evicting them would corrupt in-flight generation, not
        # just cost a re-stage).  Pinned entries are skipped by every
        # eviction walk; when a PINNED charge cannot fit after evicting
        # all unpinned shards, charge() returns False and the caller
        # surfaces a typed admission refusal instead of OOMing mid-step.
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self.total_bytes = 0
        # per-tenant resident bytes (round 19, TFS_CACHE_TENANT_BUDGET)
        self.tenant_bytes: Dict[str, int] = {}
        # per-tenant LRU key index (ordered set mirroring _entries'
        # recency for that tenant's shards): the self-first eviction's
        # victim lookup is O(1) instead of a scan of every tenant's
        # entries under the global lock
        self.tenant_keys: Dict[str, "collections.OrderedDict"] = {}

    def _drop(self, key) -> Optional[tuple]:
        """Unaccount one entry (lock held); returns ``(cache, bi)``
        when the caller should run the cache's eviction hook, or None
        for dead/refunded entries.  The hook runs OUTSIDE the lock —
        spill-backed eviction does disk I/O (``FrameCache.evict``), and
        a process-wide lock must never wait on a disk write."""
        ref, bi, nbytes, tenant, _pinned = self._entries.pop(key)
        self.total_bytes -= nbytes
        if tenant is not None:
            left = self.tenant_bytes.get(tenant, 0) - nbytes
            if left > 0:
                self.tenant_bytes[tenant] = left
            else:
                self.tenant_bytes.pop(tenant, None)
            keys = self.tenant_keys.get(tenant)
            if keys is not None:
                keys.pop(key, None)
                if not keys:
                    self.tenant_keys.pop(tenant, None)
        cache = ref()
        return (cache, bi) if cache is not None else None

    def _prune(self) -> None:
        """Drop entries whose cache was garbage-collected without an
        explicit ``uncache()`` — their shards are already freed, so they
        must not keep pinning budget."""
        for key in [k for k, v in self._entries.items() if v[0]() is None]:
            self._drop(key)

    def _lru_victim(self, keys) -> Optional[tuple]:
        """Oldest UNPINNED key in ``keys`` (lock held), or None when
        everything remaining is pinned (live KV pages are not evictable
        — round 22)."""
        for k in keys:
            entry = self._entries.get(k)
            if entry is not None and not entry[4]:
                return k
        return None

    def charge(
        self, cache: FrameCache, bi: int, nbytes: int, pinned: bool = False
    ) -> bool:
        budget = hbm_budget()
        t_budget = tenant_budget()
        tenant = getattr(cache, "tenant", None)
        evictions: list = []
        admitted = True
        with self._lock:
            self._prune()
            key = (id(cache), bi)
            if key in self._entries:
                self._drop(key)  # re-insert: refund, no eviction hook
            if budget and nbytes > budget:
                # refusal, not eviction: the shard was never resident,
                # so the eviction counter (LRU churn evidence) stays put
                return False
            if tenant is not None and t_budget and nbytes > t_budget:
                return False  # one shard over the whole tenant cap
            if tenant is not None and t_budget:
                # over-budget tenants evict their OWN LRU shards first
                # (round 19): other tenants' warm shards stay resident
                while (
                    admitted
                    and self.tenant_bytes.get(tenant, 0) + nbytes > t_budget
                ):
                    keys = self.tenant_keys.get(tenant)
                    vkey = self._lru_victim(keys or ())
                    if vkey is None:
                        # the tenant's remaining residency is all pinned
                        # pages (round 22): a further PINNED charge is a
                        # typed per-tenant admission refusal; an
                        # unpinned shard falls through to the global
                        # walk (accounting drift tolerance, as before)
                        admitted = not pinned
                        break
                    victim = self._drop(vkey)
                    if victim is not None:
                        evictions.append(victim)
            if admitted and budget:
                while self.total_bytes + nbytes > budget:
                    vkey = self._lru_victim(self._entries)
                    if vkey is None:
                        # nothing evictable is left.  Pinned charge:
                        # refuse instead of over-committing live decode
                        # memory (the caller surfaces retry_after_ms).
                        # Unpinned shard: keep the PR 5 semantics
                        # (insert once the walk is exhausted).
                        admitted = not pinned
                        break
                    victim = self._drop(vkey)
                    if victim is not None:
                        evictions.append(victim)
            if admitted:
                self._entries[key] = (
                    weakref.ref(cache), bi, nbytes, tenant, pinned
                )
                self.total_bytes += nbytes
                if tenant is not None:
                    self.tenant_bytes[tenant] = (
                        self.tenant_bytes.get(tenant, 0) + nbytes
                    )
                    self.tenant_keys.setdefault(
                        tenant, collections.OrderedDict()
                    )[key] = None
        # eviction hooks after the lock is released: a reader that races
        # in between sees either the still-resident shard (fine: shards
        # are immutable) or the evicted/spilled state.  Hooks run on the
        # refusal path too — their entries were already unaccounted, so
        # skipping them would leave resident shards the budget no longer
        # tracks.
        for victim, vbi in evictions:
            victim.evict(vbi)
            observability.note_cache_eviction()
        return admitted

    def touch(self, cache: FrameCache, bi: int) -> None:
        with self._lock:
            key = (id(cache), bi)
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                tenant = entry[3]
                if tenant is not None:
                    keys = self.tenant_keys.get(tenant)
                    if keys is not None and key in keys:
                        keys.move_to_end(key)

    def release(self, cache: FrameCache) -> None:
        with self._lock:
            for key in [
                k for k in self._entries if k[0] == id(cache)
            ]:
                self._drop(key)  # refund only: release() is not eviction


_budget = _HbmBudget()


def budget_bytes_resident() -> int:
    """Total bytes currently accounted by the LRU (test/bench surface;
    dead caches are pruned first so the number reflects live shards)."""
    with _budget._lock:
        _budget._prune()
    return _budget.total_bytes


def budget_bytes_by_tenant() -> Dict[str, int]:
    """Resident bytes per tenant (the ``TFS_CACHE_TENANT_BUDGET``
    accounting; un-tenanted caches are not listed)."""
    with _budget._lock:
        _budget._prune()
        return dict(_budget.tenant_bytes)


# ---------------------------------------------------------------------------
# host-column release for windowed frames (round 18)
# ---------------------------------------------------------------------------
#
# A windowed frame's host columns were, until this round, pinned for the
# frame object's whole lifetime even after a spill-backed sharded cache
# held every byte in HBM or on disk — defeating the HBM-resident path
# for epochs over windowed frames (the round-12 "known scope limit").
# ``release_host_columns`` swaps the cached columns' host arrays for a
# lazy stand-in that re-materialises block slices from the shard / spill
# copies on demand, so the frame stays fully usable (any verb, any
# fallback path) while its host bytes drop to zero.

ENV_RELEASE_HOST = "TFS_RELEASE_HOST"


def release_host_enabled() -> bool:
    """``TFS_RELEASE_HOST``: unset/``auto`` = release windowed frames'
    host columns once a spill-backed sharded cache covers them;
    ``0``/``off`` = keep the pre-round-18 pinning."""
    raw = envutil.env_raw(ENV_RELEASE_HOST, "auto").lower()
    return raw not in ("0", "off", "false", "no")


class SpillBackedColumnData:
    """Lazy host stand-in for a released windowed column: ``len`` /
    ``shape`` / ``dtype`` answer from metadata, slicing re-materialises
    exactly the covering blocks from the cache's shard or spill copies
    (``FrameCache.block_host``), and ``__array__`` rebuilds the whole
    column — so every host fallback path still works, it just pays a
    read instead of holding the bytes."""

    _tfs_released = True

    def __init__(self, cache: FrameCache, name: str, offsets, dtype,
                 cell_shape):
        self._cache = cache
        self._name = name
        self._offsets = tuple(int(o) for o in offsets)
        self.dtype = np.dtype(dtype)
        self._cell = tuple(int(d) for d in cell_shape)
        self._n = self._offsets[-1]

    @property
    def shape(self):
        return (self._n,) + self._cell

    @property
    def ndim(self) -> int:
        return 1 + len(self._cell)

    @property
    def nbytes(self) -> int:
        total = self._n * self.dtype.itemsize
        for d in self._cell:
            total *= d
        return total

    def __len__(self) -> int:
        return self._n

    def _materialize(self, start: int, stop: int) -> np.ndarray:
        if start >= stop:
            return np.empty((0,) + self._cell, self.dtype)
        offs = self._offsets
        parts = []
        for bi in range(len(offs) - 1):
            lo, hi = offs[bi], offs[bi + 1]
            if hi <= start or lo >= stop:
                continue
            block = self._cache.block_host(bi, self._name)
            parts.append(block[max(start - lo, 0):stop - lo])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            start, stop, step = idx.indices(self._n)
            if step != 1:
                return self._materialize(0, self._n)[idx]
            return self._materialize(start, stop)
        if isinstance(idx, (int, np.integer)):
            i = int(idx)
            if i < 0:
                i += self._n
            return self._materialize(i, i + 1)[0]
        # fancy indexing and everything else: full materialisation
        return self._materialize(0, self._n)[idx]

    def __iter__(self):
        offs = self._offsets
        for bi in range(len(offs) - 1):
            if offs[bi + 1] > offs[bi]:
                yield from self._cache.block_host(bi, self._name)

    def __array__(self, dtype=None, copy=None):
        arr = self._materialize(0, self._n)
        return arr if dtype is None else arr.astype(dtype)

    def __repr__(self):
        return (
            f"SpillBackedColumnData[{self._name}: shape={self.shape}, "
            f"{self.dtype}]"
        )


def is_released(data) -> bool:
    """Whether ``data`` is a released-column stand-in."""
    return getattr(data, "_tfs_released", False)


def release_host_columns(frame) -> int:
    """Release ``frame``'s cached host column arrays: every cached
    block's bytes are guaranteed a durable home first (resident shards
    spill on eviction; never-resident blocks are spilled here), then
    each cached column's ``data`` becomes a :class:`SpillBackedColumnData`.
    Returns the host bytes released (0 when nothing was releasable).

    Requires a spill-backed sharded cache whose block count matches the
    frame — anything else leaves the frame untouched (host columns
    without a disk fallback must stay authoritative)."""
    cache = getattr(frame, "_cache", None)
    if (
        cache is None
        or cache.spill is None
        or len(cache.assignment) != frame.num_blocks
    ):
        return 0
    cached_names = None
    for shard in cache.blocks:
        if shard is not None:
            cached_names = set(shard)
            break
    if cached_names is None:
        # nothing resident: names come from the spill copies, or give up
        for bi in sorted(cache._spilled):
            host = cache.spill.get(cache._spill_key(bi))
            if host is not None:
                cached_names = set(host)
                break
    if not cached_names:
        return 0
    # durability first: a block that never fit the budget (insert
    # refused) has neither shard nor spill copy — write it now, from
    # the host bytes we are about to drop
    for bi in range(frame.num_blocks):
        if cache.blocks[bi] is None and bi not in cache._spilled:
            block = frame.block(bi)
            host = {
                n: np.asarray(block[n]) for n in sorted(cached_names)
            }
            cache.spill.put(cache._spill_key(bi), host)
            cache._spilled.add(bi)
    released = 0
    for col in frame.columns:
        name = col.info.name
        d = col.data
        if (
            name in cached_names
            and isinstance(d, np.ndarray)
            and d.dtype != object
        ):
            released += d.nbytes
            col.data = SpillBackedColumnData(
                cache, name, frame.offsets, d.dtype, d.shape[1:]
            )
    if released:
        observability.instant(
            "cache.release_host", "cache", bytes=released,
            blocks=frame.num_blocks,
        )
    return released


# ---------------------------------------------------------------------------
# frame attachment
# ---------------------------------------------------------------------------


def attach(frame, cache: Optional[FrameCache]):
    """Attach ``cache`` to ``frame`` (or detach with None); returns the
    frame.  The attribute lives on the frame object, not the columns, so
    derived frames (select/repartition/verb outputs) never inherit a
    stale shard layout — their offsets may no longer match."""
    frame._cache = cache
    return frame


def active_cache(frame) -> Optional[FrameCache]:
    """The frame's sharded cache when it is usable: attached, block
    count matching the frame's current partitioning, and at least one
    resident — or spill-restorable — shard.  Anything else (fully
    evicted with no spill, repartitioned-away) returns None and the
    host paths take over.  The spilled clause matters for windowed
    frames: a spill-backed cache whose every shard was evicted to disk
    must still dispatch through the affinity path, where ``shard()``
    restores blocks from ``TFS_SPILL_DIR`` — otherwise the spilled
    bytes would be unreachable dead weight."""
    cache = getattr(frame, "_cache", None)
    if cache is None:
        return None
    if len(cache.assignment) != frame.num_blocks:
        return None
    if cache.resident_blocks() == 0 and not cache._spilled:
        return None
    return cache


def build(
    frame,
    col_names: Sequence[str],
    devices: Optional[Sequence[Any]] = None,
    spill: Optional[Any] = None,
) -> Optional[FrameCache]:
    """Stage ``col_names``'s block slices onto their block-affinity
    devices and return the resulting cache (None when sharding cannot
    engage: < 2 devices or a 0-block frame).

    Placement reuses :func:`device_pool.assign` on the frame's block
    sizes — deterministic least-loaded, the SAME plan the pooled
    dispatch computes — so execution affinity is placement affinity.
    Transfers are async ``device_put`` calls issued back to back per
    device (the ``stage_columns`` policy, at block granularity) and are
    the one H2D cost a cached loop ever pays (counted in
    ``h2d_bytes_staged``).

    ``spill``: a disk store for evicted shards — passed by
    ``frame.cache()`` for windowed frames (no durable host authority;
    see :class:`FrameCache`)."""
    import jax

    if devices is None:
        devices = shard_devices(True)
    devices = list(devices)
    if (
        not col_names
        or len(devices) < 2
        or frame.num_blocks < 1
        or frame.num_rows == 0
    ):
        return None
    assignment = device_pool.assign(frame.block_sizes, len(devices))
    cache = FrameCache(devices, assignment, spill=spill)
    names = list(col_names)
    for bi in range(frame.num_blocks):
        block = frame.block(bi)
        dev = devices[assignment[bi]]
        shard = {}
        for name in names:
            arr = np.asarray(block[name])
            observability.note_h2d_bytes(arr.nbytes)
            shard[name] = jax.device_put(arr, dev)
        cache.insert(bi, shard)
    return cache


def adopt(
    frame,
    devices: Sequence[Any],
    assignment: Sequence[int],
    out_blocks: Sequence[Optional[Dict[str, Any]]],
) -> Optional[FrameCache]:
    """Adopt a pooled run's per-device OUTPUT buffers as ``frame``'s
    cached shards (donation-adoption): the buffers already live on their
    block's execution device, so the successor frame of an iterative
    chain is born sharded-cached — its next epoch reads HBM directly,
    zero re-staging.  The host columns assembled by the overlapped D2H
    readback remain the authoritative copy.  Returns the attached cache
    (budget-guarded per block), or None when nothing was adoptable."""
    if len(devices) < 2 or not out_blocks:
        return None
    cache = FrameCache(devices, list(assignment), adopted=True)
    adopted = 0
    for bi, outs in enumerate(out_blocks):
        if not outs:
            continue
        if cache.insert(bi, outs):
            adopted += 1
    if adopted == 0:
        return None
    attach(frame, cache)
    return cache
