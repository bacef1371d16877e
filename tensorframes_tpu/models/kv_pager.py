"""Paged KV cache for continuous decode (round 22).

The contiguous decode cache (``models/decode.py``) allocates ``[B, S]``
KV slots up front per generation call — a serving population of mixed
prompt/continuation lengths therefore reserves worst-case HBM for every
sequence, which is exactly the fragmentation PagedAttention/Orca-style
serving removed (PAPERS.md).  This module is the paged layout:

* a process-level :class:`PagePool` owns ``[n_layers, n_pages, P, kvh,
  Dh]`` k/v page arrays (``P = TFS_DECODE_PAGE_TOKENS``) and a free
  list; **physical page 0 is the trash page** — never allocated, it
  absorbs the writes of pad tokens and idle decode slots so no write
  path needs a validity mask;
* each live sequence holds a **page table** (one int32 row mapping its
  ``pos // P`` slots to physical pages) and charges its reserved pages
  against the PR 5 frame-cache LRU (``ops/frame_cache._HbmBudget``)
  as PINNED entries under ``TFS_HBM_BUDGET`` with per-tenant billing
  via ``TFS_CACHE_TENANT_BUDGET`` — frame shards evict to host to make
  room, but pages themselves are never evicted: when nothing evictable
  remains, allocation fails as a typed :class:`PagesExhausted` refusal
  the serving layer surfaces with ``retry_after_ms`` instead of OOMing
  mid-step;
* :func:`apply_paged` runs a token chunk against the paged cache with
  **gather-based attention that is bit-identical to the contiguous
  path**: the projection half is ``transformer._attn_qkv`` (the SAME
  ops, shared by construction), the gathered ``kp[tables]`` view hands
  the unmodified ``transformer._cache_attention`` a cache of the same
  sequence capacity, and masked slots contribute exact zeros (softmax
  of ``-inf`` is exactly 0, and ``0 * v`` terms are accumulation-
  neutral), so stale page contents never perturb a single bit;
* :func:`paged_prefill` is the serving prefill — ONE admitted prompt
  and nothing else.  A prefill starts at position 0, so the only keys
  its queries may see are the chunk's own: it writes them to the pages
  as :func:`apply_paged` would (the shared :func:`_page_write`) but
  attends over them directly, with no gather, and takes the head at
  the prompt's last position alone.  Nothing in it scales with the
  slot count or the capacity but the page pools and the table row.

Bit-identity contract: a paged sequence whose table spans ``n_pages_seq
= cap // P`` pages attends over ``S' = cap`` gathered slots.  Compare
against the contiguous path at the SAME capacity (``decode.generate``'s
``cache_len=cap``) — matching reduction extents keep CPU/TPU
accumulation order identical; the suite pins this per step and for
whole generations.  The prefill's reduction extent is its bucket: the
slots it leaves out had exact-zero weight, so it agrees with
:func:`apply_paged` to f32 rounding (layer 0's k/v bit for bit), which
the suite pins, with the first token, on the same row.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tfm
from .. import observability
from ..envutil import env_int as _env_int
from ..ops import frame_cache

ENV_PAGE_TOKENS = "TFS_DECODE_PAGE_TOKENS"
DEFAULT_PAGE_TOKENS = 16


def page_tokens() -> int:
    """``TFS_DECODE_PAGE_TOKENS``: tokens per KV page (default 16)."""
    return _env_int(ENV_PAGE_TOKENS, DEFAULT_PAGE_TOKENS, floor=1)


class PagesExhausted(RuntimeError):
    """Typed page-pool admission refusal: the free list (or the pinned
    HBM/tenant budget) cannot cover a sequence's page reservation.  The
    serving layer maps this to ``server_busy`` + ``retry_after_ms`` —
    the page-granular analog of the admission gate's shed, and the
    reason a paged decode step can never OOM mid-flight."""

    def __init__(self, needed: int, free: int, reason: str = "pool"):
        self.needed = int(needed)
        self.free = int(free)
        self.reason = reason  # "pool" (free list) | "budget" | "tenant"
        # deterministic backoff: scale with the shortfall, a page's
        # lifetime being bounded by its sequence's remaining tokens
        self.retry_after_ms = int(min(1000, 50 * max(1, needed - free)))
        super().__init__(
            f"KV page pool exhausted ({reason}): need {needed} page(s), "
            f"{free} free; retry after {self.retry_after_ms}ms"
        )


class _SeqPages:
    """One sequence's budget face: the object the frame-cache LRU holds
    (weakly) for the sequence's pinned page charge.  ``evict`` refuses
    by doing nothing — pinned entries are skipped by the eviction walks,
    this hook exists only as a defensive no-op."""

    __slots__ = ("tenant", "pages", "__weakref__")

    def __init__(self, tenant: Optional[str]):
        self.tenant = tenant
        self.pages: List[int] = []

    def evict(self, bi: int) -> None:  # pragma: no cover — never walked
        pass


class PagePool:
    """Fixed-size physical KV page pool shared by every decode slot.

    ``k_pages``/``v_pages`` are ``[n_layers, n_pages, P, kvh, Dh]``
    functional jax arrays; the serving driver threads them through the
    prefill/step executables and stores the returned (updated) arrays.
    The pool object itself only manages the free list and the budget
    accounting — page CONTENTS are owned by whoever holds the arrays.

    Page 0 is the trash page: idle slots and pad tokens write there, so
    every scatter is unconditional.  It is excluded from the free list
    and from capacity accounting."""

    def __init__(
        self,
        cfg: tfm.TransformerConfig,
        n_pages: int,
        tokens_per_page: Optional[int] = None,
        dtype=None,
    ):
        P = page_tokens() if tokens_per_page is None else int(tokens_per_page)
        if P < 1:
            raise ValueError(f"tokens_per_page must be >= 1, got {P}")
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the trash page), "
                f"got {n_pages}"
            )
        self.cfg = cfg
        self.tokens_per_page = P
        self.n_pages = int(n_pages)
        dtype = dtype or cfg.dtype
        kvh, dh, n = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        shape = (n, self.n_pages, P, kvh, dh)
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)
        # one page's HBM across all layers, k and v together — the unit
        # the budget LRU accounts
        self.page_bytes = int(
            2 * n * P * kvh * dh * jnp.dtype(dtype).itemsize
        )
        self._lock = threading.Lock()
        # LIFO free list (page 0 reserved as trash)
        self._free = list(range(self.n_pages - 1, 0, -1))
        self.allocated_total = 0  # monotonic (telemetry)
        self.freed_total = 0

    # -- allocation ----------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable pages (trash page excluded)."""
        return self.n_pages - 1

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def used_count(self) -> int:
        with self._lock:
            return self.capacity - len(self._free)

    def allocate(
        self, n: int, tenant: Optional[str] = None
    ) -> Tuple[_SeqPages, List[int]]:
        """Reserve ``n`` physical pages for one sequence.  Returns the
        budget charge handle (keep it referenced for the sequence's
        lifetime — the LRU holds it weakly) and the page ids.  Raises
        :class:`PagesExhausted` when the free list or the pinned budget
        charge refuses — atomically: a refused allocation takes
        nothing."""
        n = int(n)
        if n <= 0:
            raise ValueError(f"allocate({n}): need a positive page count")
        charge = _SeqPages(tenant)
        with self._lock:
            if n > len(self._free):
                raise PagesExhausted(n, len(self._free), reason="pool")
            # the budget charge is PINNED: frame shards may be evicted
            # to make room, live pages never are — an unpayable charge
            # is a refusal here, not an OOM three steps from now
            if not frame_cache._budget.charge(
                charge, 0, n * self.page_bytes, pinned=True
            ):
                raise PagesExhausted(n, len(self._free), reason="budget")
            pages = [self._free.pop() for _ in range(n)]
            self.allocated_total += n
        charge.pages = pages
        observability.note_kv_pages_allocated(n)
        return charge, pages

    def free(self, charge: _SeqPages) -> None:
        """Return a sequence's pages to the free list and refund its
        budget charge (retirement, cancellation, and deadline expiry
        all land here).  Contents are NOT scrubbed — stale values are
        unreachable through any live table and masked to exact zero
        weight even when a recycled page sits inside a new sequence's
        gather window."""
        pages = charge.pages
        if not pages:
            return
        charge.pages = []
        with self._lock:
            self._free.extend(pages)
            self.freed_total += len(pages)
        frame_cache._budget.release(charge)
        observability.note_kv_pages_freed(len(pages))

    def stats(self) -> Dict[str, int]:
        with self._lock:
            free = len(self._free)
        return {
            "page_tokens": self.tokens_per_page,
            "pages_total": self.capacity,
            "pages_free": free,
            "pages_used": self.capacity - free,
            "page_bytes": self.page_bytes,
            "allocated_total": self.allocated_total,
            "freed_total": self.freed_total,
        }


def pages_for(tokens: int, tokens_per_page: int) -> int:
    """Pages needed to hold ``tokens`` sequence positions."""
    return max(1, -(-int(tokens) // int(tokens_per_page)))


def init_tables(batch: int, max_pages: int) -> jnp.ndarray:
    """All-trash page tables [batch, max_pages] — every slot maps to
    physical page 0 until a sequence's reservation is written in."""
    return jnp.zeros((batch, max_pages), jnp.int32)


# ---------------------------------------------------------------------------
# paged forward
# ---------------------------------------------------------------------------


@jax.named_scope("page_write")
def _page_write(kp, vp, k, v, positions, tables):
    """Scatter a chunk's k/v ``[B, L, kvh, Dh]`` into one layer's pages
    at ``tables[b, pos // P]``, offset ``pos % P``.  Returns the updated
    ``(kp, vp)``."""
    B, L, kvh, dh = k.shape
    P = kp.shape[1]
    page_slot = positions // P  # [B, L]
    offset = positions % P
    max_pages = tables.shape[1]
    # positions past a row's table (bucket padding that overruns
    # the sequence capacity) write the trash page, never a
    # clamped real slot
    dest = jnp.where(
        page_slot < max_pages,
        jnp.take_along_axis(
            tables, jnp.minimum(page_slot, max_pages - 1), axis=1
        ),
        0,
    )  # [B, L]
    flat_dest = dest.reshape(B * L)
    flat_off = offset.reshape(B * L)
    kp = kp.at[flat_dest, flat_off].set(
        k.astype(kp.dtype).reshape(B * L, kvh, dh), mode="drop"
    )
    vp = vp.at[flat_dest, flat_off].set(
        v.astype(vp.dtype).reshape(B * L, kvh, dh), mode="drop"
    )
    return kp, vp


def _attn_out(bp, x, att, cfg):
    """x + Wo(att): the residual half both paged blocks end their
    attention with.  att: [B, L, h, Dh]."""
    B, L = att.shape[:2]
    att = att.reshape(B, L, cfg.n_heads * cfg.head_dim)
    return x + tfm.shard(
        att @ tfm.weight(bp["wo"], cfg.dtype), ("dp", "ep"), "sp", None
    )


def _paged_block(bp, x, positions, cfg, kp, vp, tables):
    """One decoder block against one layer's page arrays.

    ``kp``/``vp``: [n_pages, P, kvh, Dh]; ``tables``: [B, max_pages];
    ``positions``: [B, L] absolute positions (per-row frontiers).  The
    chunk's k/v scatter to ``tables[b, pos // P]`` at offset ``pos %
    P`` — table slots a sequence never reserved hold 0, so pad tokens
    and idle slots write the trash page.  Attention gathers the table's
    pages into a [B, max_pages * P] contiguous view and runs the
    UNMODIFIED ``transformer._cache_attention`` on it: positions past a
    row's frontier are masked to exact zero weight, so stale page
    contents (previous tenants included) never contribute a bit."""
    B = x.shape[0]
    dt = cfg.dtype
    P = kp.shape[1]
    # scope names are metadata: a profiler session groups the device
    # operations of a step under attention / page_write / page_gather
    with jax.named_scope("attention"):
        q, k, v = tfm._attn_qkv(bp, x, positions, cfg)
        kvh, dh = k.shape[2:]
        kp, vp = _page_write(kp, vp, k, v, positions, tables)
        with jax.named_scope("page_gather"):
            # gather each row's pages into its contiguous cache view
            ck = kp[tables].reshape(B, tables.shape[1] * P, kvh, dh)
            cv = vp[tables].reshape(B, tables.shape[1] * P, kvh, dh)
        att = tfm._cache_attention(
            q, ck.astype(dt), cv.astype(dt), positions
        )
        x = _attn_out(bp, x, att, cfg)
    x, _aux = tfm._mlp_residual(bp, x, cfg)
    return x, kp, vp


def _prefill_block(bp, x, positions, cfg, kp, vp, tables):
    """:func:`_paged_block` for a chunk that STARTS its sequence
    (``positions`` count from 0): the same projections and page write,
    but the only keys such a chunk's queries may see are its own, which
    the layer has just computed — so attention runs causally over the
    chunk's k/v (rounded to the page dtype, as the pages hold them) and
    nothing is gathered back from the pages."""
    dt = cfg.dtype
    with jax.named_scope("attention"):
        q, k, v = tfm._attn_qkv(bp, x, positions, cfg)
        kp, vp = _page_write(kp, vp, k, v, positions, tables)
        att = tfm._cache_attention(
            q, k.astype(kp.dtype).astype(dt), v.astype(vp.dtype).astype(dt),
            positions,
        )
        x = _attn_out(bp, x, att, cfg)
    x, _aux = tfm._mlp_residual(bp, x, cfg)
    return x, kp, vp


def apply_paged(
    params: tfm.Params,
    tokens: jnp.ndarray,
    tables: jnp.ndarray,
    indices: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    cfg: tfm.TransformerConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run a token chunk against the paged cache.

    ``tokens`` [B, L] continue each row's sequence at ``indices`` [B]
    (per-row frontiers — the decode scheduler's slots advance
    independently, unlike the contiguous cache's single scalar index);
    ``tables`` [B, max_pages] map sequence page slots to physical
    pages.  Returns ``(logits [B, L, V] f32, k_pages', v_pages')``.

    Prefill passes the whole (bucket-padded) prompt at ``indices = 0``;
    decode passes one token per row.  Pad-token queries produce logits
    the caller discards, and their k/v land in the trash page (or in
    positions later overwritten before any query can attend to them),
    so no masking beyond the causal one exists anywhere."""
    B, L = tokens.shape
    positions = indices[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    x = tfm.embed_lookup(params["embed"], tokens, cfg.dtype)

    def step(x, layer):
        bp, kp, vp = layer
        x, kp, vp = _paged_block(bp, x, positions, cfg, kp, vp, tables)
        return x, (kp, vp)

    x, (kps, vps) = jax.lax.scan(
        step, x, (params["blocks"], k_pages, v_pages)
    )
    with jax.named_scope("head"):
        x = tfm._rms_norm(x, params["ln_f"])
        logits = jnp.einsum(
            "bld,dv->blv",
            x,
            tfm.weight(params["lm_head"], cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return logits, kps, vps


# ---------------------------------------------------------------------------
# serving executables (the decode scheduler's two compiled dispatches)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def paged_decode_step(params, toks, tables, indices, k_pages, v_pages, cfg):
    """One greedy decode step for the whole slot batch: toks [B] ->
    next tokens [B].  Fixed [max_slots] shapes — the ONE executable the
    scheduler reuses for every step of every request population (idle
    slots decode garbage into the trash page that nobody reads)."""
    logits, k_pages, v_pages = apply_paged(
        params, toks[:, None], tables, indices, k_pages, v_pages, cfg
    )
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return nxt, k_pages, v_pages


@functools.partial(jax.jit, static_argnames=("cfg",))
def paged_prefill(params, toks, table, last_pos, k_pages, v_pages, cfg):
    """Prefill of ONE newly admitted sequence, and of nothing else: toks
    [1, Lb] (the prompt padded to its own bucket), ``table`` [1,
    max_pages] its page-table row, ``last_pos`` [1] its final REAL
    position.  Every prompt token's k/v land in the sequence's pages
    (pad positions past the reservation in the trash page) exactly as
    :func:`apply_paged` would write them, but a prefill starts at
    position 0, so it attends over the chunk's own k/v with no gather
    (:func:`_prefill_block`) and needs the head at ONE position: apart
    from the page pools and the table row nothing here scales with the
    slot count or the capacity.  Returns the first greedy token [1] —
    argmax over the logits at the prompt's frontier, exactly what the
    contiguous ``generate`` samples from ``logits[:, -1]``.  One
    executable per prompt bucket (the ladder bounds the grid)."""
    B, L = toks.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = tfm.embed_lookup(params["embed"], toks, cfg.dtype)

    def step(x, layer):
        bp, kp, vp = layer
        x, kp, vp = _prefill_block(bp, x, positions, cfg, kp, vp, table)
        return x, (kp, vp)

    x, (k_pages, v_pages) = jax.lax.scan(
        step, x, (params["blocks"], k_pages, v_pages)
    )
    with jax.named_scope("head"):
        x = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)[:, 0]
        x = tfm._rms_norm(x, params["ln_f"])
        logits = jnp.einsum(
            "bd,dv->bv",
            x,
            tfm.weight(params["lm_head"], cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok0, k_pages, v_pages
