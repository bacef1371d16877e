"""Paged KV cache for continuous decode (round 22).

The contiguous decode cache (``models/decode.py``) allocates ``[B, S]``
KV slots up front per generation call — a serving population of mixed
prompt/continuation lengths therefore reserves worst-case HBM for every
sequence, which is exactly the fragmentation PagedAttention/Orca-style
serving removed (PAPERS.md).  This module is the paged layout:

* a process-level :class:`PagePool` builds ``[n_layers, kvh, n_pages, P,
  Dh]`` k/v page arrays (``P = TFS_DECODE_PAGE_TOKENS``) and owns a free
  list.  The ARRAYS have one owner, whoever runs the executables: the
  decode scheduler takes them from its pool at construction
  (:meth:`PagePool.take`) and from then on holds the one buffer pair
  there is.  Both serving executables donate the pools, the layer scan
  carries the stacks and writes a step's tokens into them where they
  lie, and the kernel reads a layer's pages out of the stack by its
  index — so no pool is ever sliced out of the stack, stacked back or
  copied, from the scheduler's construction to its close (PR 33).  The
  layout is head-major because the decode kernel dictates it (PR 30):
  one page of one head is ``[P, Dh]``, whole native tiles (one 4 KB tile
  at P=16, Dh=128 in bf16) that a DMA moves as they lie, whatever
  ``kvh`` is; with the heads inside the page (``[P, kvh, Dh]``) a model
  of 2 kv heads filled a quarter of each tile.  **Physical page 0 is
  the trash page** — never allocated, it absorbs the writes of pad
  tokens and idle decode slots so no write path needs a validity mask;
* each live sequence holds a **page table** (one int32 row mapping its
  ``pos // P`` slots to physical pages) and charges its reserved pages
  against the PR 5 frame-cache LRU (``ops/frame_cache._HbmBudget``)
  as PINNED entries under ``TFS_HBM_BUDGET`` with per-tenant billing
  via ``TFS_CACHE_TENANT_BUDGET`` — frame shards evict to host to make
  room, but pages themselves are never evicted: when nothing evictable
  remains, allocation fails as a typed :class:`PagesExhausted` refusal
  the serving layer surfaces with ``retry_after_ms`` instead of OOMing
  mid-step;
* :func:`apply_paged` runs a token chunk against the paged cache.  The
  general path is **gather-based attention that is bit-identical to the
  contiguous path**: the projection half is ``transformer._attn_qkv``
  (the SAME ops, shared by construction), the gathered ``kp[layer, :,
  tables]`` view hands the unmodified ``transformer._cache_attention``
  a cache of the same sequence capacity, and masked slots contribute
  exact zeros
  (softmax of ``-inf`` is exactly 0, and ``0 * v`` terms are
  accumulation-neutral), so stale page contents never perturb a single
  bit;
* a ONE-token chunk whose shapes fit (:func:`paged_kernel_fits`: decided
  at trace time from what the call is given, no knob) attends instead
  through ``parallel/paged_attention.py``: the Pallas kernel reads each
  row's pages in place through its table row, up to its frontier and no
  further, so a decode step moves the K and V a sequence HOLDS and not
  its capacity.  Same mathematics (f32 scores, softmax and accumulation;
  exact zero weight past the frontier), another order of summation: it
  agrees with the gather path to rounding, not bit for bit;
* a latent block (``BlockSpec(attention="mla")``, ``models/mla.py``) has
  ONE pool where the others have a pair: ``[n_layers, 1, n_pages, P,
  row_width]``, a token's normed latent and rotated key part as one row.
  It travels where the K pool does (``v_pages`` is None), is written by
  the same :func:`_page_write`, donated and carried by the scan alike; a
  one-token step whose shapes fit (:func:`latent_kernel_fits`) attends over
  the rows each slot holds, in place and in the latent space, through the
  kernel's latent form (``tfs_latent_attention``: one copy a page, the values the
  keys' first columns); any other decode chunk gathers the table's pages
  as rows, and a prefill attends over its own rows expanded by head
  (:func:`_latent_attention`);
* a retention block (``BlockSpec(attention="retention")``,
  ``models/retention.py``) has NO pages: a sequence's whole past is a
  float32 state of fixed size a layer (34 MB at heads of 128), held per
  decode slot in ``PagePool.retention`` — ``(S [n_layers, slots, kvh, O,
  dh, dh], z [n_layers, slots, kvh, O, dh])`` — an argument and a result
  of both executables, donated and carried by the scan like the pools.  A
  decode step updates and reads a live slot's state in one pass of the
  kernel ``parallel/retention.py`` (or the ``jnp`` step where it does not
  fit); a prefill runs the chunked form from the slot's state, or from
  zeros where the dispatch starts the sequence, and leaves the state of
  the prompt's last real token.  A "page" of such a pool is one slot's
  state, all layers: what a sequence costs at any length;
* a block with a state-space mixer beside its attention
  (``BlockSpec(mixer=SSMSpec(...))``, ``models/ssm.py``: Falcon-H1's
  Mamba-2) keeps BOTH: the pages of its attention and, in
  ``PagePool.retention``, a state a slot — ``(S [n_layers, slots, heads,
  P, N] float32, tail [n_layers, slots, d_conv - 1, conv_dim])`` — passed
  to both executables as ``retention`` and donated, as a retention
  block's is.  A decode step steps a live slot's state in one pass of the
  kernel ``parallel/ssm.py`` (or ``ssm.step``); a prefill runs SSD's
  chunked form and overwrites the slot's state and tail whole.  A
  sequence is charged its pages and its slot's state together;
* a stack whose attention differs by layer (``BlockSpec(layer_types=...,
  window=...)``: Trinity's window layers among full ones) keeps each
  kind's pages in a pool of its own, ``k_pages`` and ``v_pages`` each a
  pair ``(full [n_full, kvh, n_pages, P, Dh], window [n_window, kvh,
  window_pages, P, Dh])``, a layer indexed by its rank among its kind.
  A sequence's table row is its full table and then its RING, the
  :func:`ring_pages` pages a window layer holds of it at any length:
  logical page ``p`` lies in ring slot ``p % ring``, so a window layer
  writes over the page that has fallen behind its window, and a prompt
  longer than the ring writes only what the ring keeps (the earlier pages
  to the trash page).  The layer scan runs the stack as runs of like
  layers (:func:`_scan_layers`), a sequence is charged both pools' pages;
* :func:`paged_prefill` is the serving prefill — ONE admitted prompt
  and nothing else.  A prefill starts at position 0, so the only keys
  its queries may see are the chunk's own: it writes them to the pages
  as :func:`apply_paged` would (the shared :func:`_page_write`) but
  attends over them directly, with no gather, and takes the head at
  the prompt's last position alone.  Nothing in it scales with the
  slot count or the capacity but the page pools and the table row.

Bit-identity contract (the gather path): a paged sequence whose table
spans ``n_pages_seq = cap // P`` pages attends over ``S' = cap`` gathered
slots.  Compare
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
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cca, mla, moe, retention, ssm
from . import transformer as tfm
from .. import observability
from ..envutil import env_int as _env_int
from ..ops import frame_cache
from ..parallel import flash, paged_attention
from ..parallel import retention as retention_kernel
from ..parallel import ssm as ssm_kernel

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

    ``k_pages``/``v_pages`` are ``[n_layers, kvh, n_pages, P, Dh]``
    functional jax arrays — head-major, so a page of one head is whole
    native tiles whatever ``kvh`` is (the module docstring says why).
    The pool object itself only manages the free list and the budget
    accounting — page CONTENTS are owned by whoever holds the arrays,
    and the serving executables DONATE them, so they have one holder:
    the serving driver takes them (:meth:`take`), threads them through
    the prefill/step executables and keeps the returned (updated)
    arrays, the same buffers from its construction to its close.  A pool
    that was taken from keeps its shapes, free list and accounting and
    holds no array (``k_pages`` / ``v_pages`` / ``conv_state`` are None).

    Page 0 is the trash page: idle slots and pad tokens write there, so
    every scatter is unconditional.  It is excluded from the free list
    and from capacity accounting.

    For a ``cca`` block the pool also holds ``conv_state``, the per-slot
    convolution state (``slots`` rows a layer; None for other blocks).
    ``retention`` is the per-slot state a block retains and the
    executables donate, taken by :meth:`take_retention`: for a
    ``retention`` block ``(S, z)`` in float32 and NO pages (``k_pages`` /
    ``v_pages`` None; a page is then one slot's state, so ``n_pages`` is
    the slots and one more, and a sequence allocates ONE); for a block
    with a mixer ``(S, tail)`` (``models/ssm.py``) beside its pages; None
    for the others.  A sequence's charge is its pages and its slot's
    state, ``n * page_bytes + state_bytes``, both read off the arrays'
    own shapes.

    A stack whose attention differs by layer (``cfg.block.layer_types``)
    has TWO pools of pages: ``k_pages`` and ``v_pages`` are pairs ``(full,
    window)``, the full layers' ``n_pages`` pages and the window layers'
    ``window_pages``, each with its own trash page 0.  A window layer holds
    at most its :attr:`ring` of pages of a sequence at any length, so the
    window pool is every slot's ring and no more, ``slots * ring + 1``
    pages, and slot ``s`` owns the ring :meth:`ring_of` ``(s)``: nothing
    there is allocated or refused.  A sequence takes ``n`` full pages from
    the free list and is charged ``n * page_bytes + min(n, ring) *
    window_page_bytes``, what it holds of both pools."""

    def __init__(
        self,
        cfg: tfm.TransformerConfig,
        n_pages: int,
        tokens_per_page: Optional[int] = None,
        dtype=None,
        slots: Optional[int] = None,
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
        self.dtype = jnp.dtype(dtype or cfg.dtype)
        # the second kind of per-sequence state: a ``cca`` block's decode
        # step needs the previous position's convolution inputs, a fixed
        # ``cca.state_width`` values a layer whatever the length.  One
        # array [n_layers, slots, width] beside the pages, indexed by the
        # decode slot, threaded through the executables like the pages
        self.slots = None
        if (
            cfg.block.attention in ("cca", "retention")
            or cfg.block.mixer is not None
        ):
            if slots is None:
                raise ValueError(
                    f"a {cfg.block.attention!r} block with mixer "
                    f"{cfg.block.mixer} keeps a state per decode slot: "
                    f"PagePool(..., slots=max_slots)"
                )
            self.slots = int(slots)
        # a window layer's ring, and the window pool's pages
        self.ring = ring_pages(cfg, P) if cfg.block.window else 0
        self.window_pages = 0
        if self.ring:
            if slots is None:
                raise ValueError(
                    "a stack with window layers holds a ring of pages a "
                    "decode slot: PagePool(..., slots=max_slots)"
                )
            self.window_pages = int(slots) * self.ring + 1
        self.k_pages, self.v_pages, self.conv_state = self.zeros()
        self.retention = self.retention_zeros()

        def page_bytes(pools, n):
            return sum(
                pages.size // n * self.dtype.itemsize
                for pages in pools if pages is not None
            )

        # what the budget LRU accounts, read off the arrays' own shapes:
        # one page's HBM across all layers, of every pool there is (K and
        # V together, or the one latent pool), and one slot's retained
        # state, all layers.  A pool with no pages (retention) has the
        # slot's state as its page
        self.window_page_bytes = 0
        if self.ring:
            self.page_bytes = page_bytes(
                (self.k_pages[0], self.v_pages[0]), self.n_pages
            )
            self.window_page_bytes = page_bytes(
                (self.k_pages[1], self.v_pages[1]), self.window_pages
            )
        else:
            self.page_bytes = page_bytes(
                (self.k_pages, self.v_pages), self.n_pages
            )
        self.state_bytes = sum(
            a.size // self.slots * a.dtype.itemsize
            for a in self.retention or ()
        )
        if not self.page_bytes:
            self.page_bytes, self.state_bytes = self.state_bytes, 0
        self._lock = threading.Lock()
        # LIFO free list (page 0 reserved as trash)
        self._free = list(range(self.n_pages - 1, 0, -1))
        self.allocated_total = 0  # monotonic (telemetry)
        self.freed_total = 0

    def zeros(self):
        """Arrays of the pool's shapes, all zero: ``(k_pages, v_pages,
        conv_state)``, the last None unless the block is ``cca``.  A
        latent block (``mla``) has ONE pool, a token's row of
        ``mla.row_width`` values laid out as one head; it travels where
        the K pool does, and ``v_pages`` is None."""
        cfg = self.cfg
        if not holds_pages(cfg):
            return None, None, None
        if cfg.block.attention == "mla":
            heads, widths = 1, (mla.row_width(cfg), None)
        else:
            heads, widths = cfg.n_kv_heads, (cfg.head_dim,) * 2
        state = None
        if cfg.block.attention == "cca":
            state = cca.init_state(cfg, self.slots, self.dtype)

        def pool(layers, n_pages, w):
            return None if w is None else jnp.zeros(
                (layers, heads, n_pages, self.tokens_per_page, w), self.dtype
            )

        if self.ring:
            n_full = cfg.block.layer_types.count("full")
            k, v = (
                (pool(n_full, self.n_pages, w),
                 pool(cfg.n_layers - n_full, self.window_pages, w))
                for w in widths
            )
            return k, v, state
        k, v = (pool(cfg.n_layers, self.n_pages, w) for w in widths)
        return k, v, state

    def take(self):
        """Hand the arrays to their one holder: ``(k_pages, v_pages,
        conv_state)``, of which the pool then keeps no reference — a
        second holder would pin a copy the executables' donation could
        not reuse (and read a deleted buffer after the first dispatch)."""
        arrays = self.k_pages, self.v_pages, self.conv_state
        self.k_pages = self.v_pages = self.conv_state = None
        return arrays

    def retention_zeros(self):
        """The retained state of the pool's slots, all zero: a retention
        block's ``(S, z)`` in float32 whatever the pool's dtype, a mixer's
        ``(S, tail)`` (``ssm.init_state``); None for other blocks."""
        if self.cfg.block.attention == "retention":
            return retention.init_state(self.cfg, self.slots)
        if self.cfg.block.mixer is not None:
            return ssm.init_state(self.cfg, self.slots, self.dtype)
        return None

    def take_retention(self):
        """:meth:`take` for the retained state: both executables donate
        it, and 2-5 GB have no room for a second holder."""
        state, self.retention = self.retention, None
        return state

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
        nothing.  With window layers the charge also holds the sequence's
        pages of its slot's ring, ``min(n, ring)``."""
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
                charge, 0,
                n * self.page_bytes
                + min(n, self.ring) * self.window_page_bytes
                + self.state_bytes,
                pinned=True,
            ):
                raise PagesExhausted(n, len(self._free), reason="budget")
            pages = [self._free.pop() for _ in range(n)]
            self.allocated_total += n
        charge.pages = pages
        observability.note_kv_pages_allocated(n)
        return charge, pages

    def ring_of(self, slot: int) -> List[int]:
        """The window pool's pages slot ``slot`` owns: its ring."""
        return list(range(1 + slot * self.ring, 1 + (slot + 1) * self.ring))

    def free(self, charge: _SeqPages) -> None:
        """Return a sequence's pages to the free list and refund its
        budget charge (retirement, cancellation, and deadline expiry
        all land here).  Contents are NOT scrubbed — stale values are
        unreachable through any live table and masked to exact zero
        weight even when a recycled page sits inside a new sequence's
        gather window."""
        pages = charge.pages if charge is not None else None
        if not pages:  # nothing held (a request no slot was ever given)
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
        out = {
            "page_tokens": self.tokens_per_page,
            "pages_total": self.capacity,
            "pages_free": free,
            "pages_used": self.capacity - free,
            "page_bytes": self.page_bytes,
            "state_bytes": self.state_bytes,
            "allocated_total": self.allocated_total,
            "freed_total": self.freed_total,
        }
        if self.ring:
            out.update(
                window_pages_total=self.window_pages - 1,
                window_page_bytes=self.window_page_bytes,
            )
        return out


def holds_pages(cfg) -> bool:
    """Whether a block's pool holds pages: every kind's but a retention
    block's, whose whole past is its state."""
    return cfg.block.attention != "retention"


def pages_for(tokens: int, tokens_per_page: int) -> int:
    """Pages needed to hold ``tokens`` sequence positions."""
    return max(1, -(-int(tokens) // int(tokens_per_page)))


def ring_pages(cfg, tokens_per_page: int) -> int:
    """The pages a window layer holds of a sequence at any length: the
    window's and one more, so that the page a step writes is never one its
    window still reads."""
    return -(-int(cfg.block.window) // int(tokens_per_page)) + 1


def init_tables(batch: int, max_pages: int) -> jnp.ndarray:
    """All-trash page tables [batch, max_pages] — every slot maps to
    physical page 0 until a sequence's reservation is written in."""
    return jnp.zeros((batch, max_pages), jnp.int32)


# ---------------------------------------------------------------------------
# paged forward
# ---------------------------------------------------------------------------


@jax.named_scope("page_write")
def _page_write(kp, vp, k, v, positions, tables, layer, from_zero=False,
                ring=False, last_pos=None):
    """Scatter a chunk's k/v ``[B, L, kvh, Dh]`` into ``layer``'s pages of
    the stacked pools ``[n_layers, kvh, n_pages, P, Dh]`` at ``tables[b,
    pos // P]``, offset ``pos % P``, of every head.  Returns the updated
    ``(kp, vp)``: the same stacks, written where they lie — no layer's
    pool is sliced out or put back (PR 33).  A latent block's rows go the
    same way as the one pool there is (``vp`` and ``v`` None).

    The pool is written as windows of ``w`` rows of Dh, in the layout the
    kernel reads (a scatter windowed over (kvh, Dh) makes XLA turn the
    whole pool head-minor and back around every write), and XLA's scatter
    takes a window at a time, ~45 ns each whatever its size.  A chunk
    anywhere is written a token of a head a window (``w`` = 1: a decode
    step's 2 x 48 to 8 x 12 of them).  ``from_zero`` (static) says the
    chunk starts its sequence — ``positions`` is ``arange(L)`` on every
    row, which a prefill knows of itself — so it fills whole pages from
    each table's first and is written a page of a head a window (``w`` =
    P, a 4 KB tile: 8 x 64 windows for a 1,024 bucket, not 8 x 1,024),
    its last page padded with zeros where no query can look before a
    decode step has written there.

    ``ring`` (static): ``tables`` [B, ring] is a window layer's ring, which
    holds logical page ``p`` in slot ``p % ring``.  A prefill (``from_zero``)
    then writes only the pages the ring keeps, those of the last ``ring``
    up to the page of ``last_pos`` [B]: the earlier ones and the bucket's
    padding past it go to the trash page, since two writes to one slot in
    one scatter land in no defined order."""
    B, L, kvh, _ = k.shape
    n_layers, _, n_pages, P, _ = kp.shape
    max_pages = tables.shape[1]
    if from_zero:
        w, n = P, -(-L // P)
        page_slot = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
        window = 0
    else:
        w, n = 1, L
        page_slot = positions // P  # [B, L]
        window = (positions % P).reshape(B * n)
    if ring:
        keep = True
        if from_zero:
            last = (last_pos // P)[:, None]
            keep = (page_slot <= last) & (page_slot > last - max_pages)
        dest = jnp.where(
            keep,
            jnp.take_along_axis(tables, page_slot % max_pages, axis=1),
            0,
        ).reshape(B * n)
    else:
        # positions past a row's table (bucket padding that overruns
        # the sequence capacity) write the trash page, never a
        # clamped real slot
        dest = jnp.where(
            page_slot < max_pages,
            jnp.take_along_axis(
                tables, jnp.minimum(page_slot, max_pages - 1), axis=1
            ),
            0,
        ).reshape(B * n)
    heads = layer * kvh + jnp.arange(kvh, dtype=jnp.int32)[:, None]
    at = ((heads * n_pages + dest) * (P // w) + window).reshape(kvh * B * n)

    def put(pages, x):
        if pages is None:  # a latent block's one pool travels as ``kp``
            return None
        dh = x.shape[-1]
        x = jnp.pad(
            x.astype(pages.dtype), ((0, 0), (0, n * w - L), (0, 0), (0, 0))
        ).reshape(B * n, w, kvh, dh).transpose(2, 0, 1, 3)
        return pages.reshape(
            n_layers * kvh * n_pages * (P // w), w, dh
        ).at[at].set(
            x.reshape(kvh * B * n, w, dh), mode="drop"
        ).reshape(pages.shape)

    return put(kp, k), put(vp, v)


def _attn_out(bp, x, att, cfg):
    """x + Wo(att): the residual half both paged blocks end their
    attention with.  att: [B, L, h, Dh], or the heads joined already; ``x``
    the block's input.  A spec with ``attn_gate`` gates att elementwise by
    ``transformer.attn_gate`` of ``x`` first, one with ``sandwich`` norms
    Wo(att) before it joins the residual."""
    B, L = att.shape[:2]
    att = att.reshape(B, L, -1)
    if cfg.block.attn_gate:
        with jax.named_scope("gate"):
            att = att * tfm.attn_gate(bp, x, cfg)
    out = tfm.shard(
        att @ tfm.weight(bp["wo"], cfg.dtype), ("dp", "ep"), "sp", None
    )
    if cfg.block.sandwich:
        out = tfm._rms_norm(out, bp["ln_post_attn"], cfg.block.norm_eps)
    return x + tfm.times(
        out, cfg.block.multipliers.attention_out
    )


def _mlp_out(bp, out, cfg):
    """An expert layer's output as it joins the residual: normed under a
    ``sandwich`` spec (``transformer._mlp_residual`` does the same for the
    dense SwiGLU)."""
    if cfg.block.sandwich:
        return tfm._rms_norm(out, bp["ln_post_mlp"], cfg.block.norm_eps)
    return out


def _embed(params, tokens, cfg):
    return tfm.times(
        tfm.embed_lookup(params["embed"], tokens, cfg.dtype),
        cfg.block.multipliers.embedding,
    )


def _feed_forward(bp, x, cfg, layer, route):
    """The feed-forward half by the spec's kind.  ``route`` is None for a
    layer that routes nothing (the dense block's, and the leading dense
    layers of any), else ``(r_prev, live, experts)``: the router's carry
    (None for a router that keeps none), the tokens that count, and the
    expert weights of all expert layers (``moe.stack_experts``), read at
    ``layer`` less the dense layers before it.  Returns ``(x', routed)``:
    ``routed`` is ``(r, counts, chosen)``, the carry for the layer above,
    the live tokens (or pairs) each held expert got and each token's
    expert (or ``k`` of them); None without a router."""
    if route is None:
        x, _aux = tfm._mlp_residual(bp, x, cfg)
        return x, None
    r_prev, live, experts = route
    y = tfm._rms_norm(x, bp["ln2"], cfg.block.norm_eps)
    layer = layer - cfg.block.dense_layers if cfg.block.dense_layers else layer
    if cfg.block.ffn == "experts_topk":
        out, *routed = moe.experts_topk(bp, y, live, cfg, experts, layer)
        return x + _mlp_out(bp, out, cfg), (None, *routed)
    out, *routed = moe.experts_top1(bp, y, r_prev, live, cfg, experts, layer)
    return x + _mlp_out(bp, out, cfg), tuple(routed)


def _mesh_partitions() -> bool:
    """Whether the ambient mesh has an axis left to partition over, which
    a Mosaic kernel cannot be (``flash._per_shard``; the scheduler runs on
    one device)."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(t != jax.sharding.AxisType.Manual for t in mesh.axis_types)


def paged_kernel_fits(cfg, P: int, B: int, L: int, dtype) -> bool:
    """Whether a chunk attends through the Pallas kernel
    (``parallel/paged_attention.py``), decided at trace time from what
    the call is given: ``L`` tokens a row for ``B`` rows against pools of
    ``P``-token pages in ``dtype``.  It takes ONE token a row, heads of
    whole lane tiles, pages of whole sublane tiles of the pool's dtype
    (16 rows of bf16, 8 of f32: a page is then a run of native tiles),
    pools in the compute dtype, its buffers within VMEM (query heads
    come in whole groups by the configuration's own check) — and no mesh
    axis left to partition over, which a Mosaic kernel cannot be
    (``flash._per_shard``; the scheduler runs on one device).  It reads a
    K and a V pool by head: a latent block's one pool, whose values are
    its keys' first part, is :func:`latent_kernel_fits`'s.  Whatever it
    refuses takes the gather path."""
    dtype = jnp.dtype(dtype)
    return (
        L == 1
        and cfg.block.attention != "mla"
        and cfg.head_dim % 128 == 0
        and P % (32 // dtype.itemsize) == 0
        and dtype == jnp.dtype(cfg.dtype)
        and not _mesh_partitions()
        and paged_attention.vmem_bytes(
            B, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, P, dtype
        ) <= paged_attention.VMEM_BUDGET_BYTES
    )


def latent_kernel_fits(cfg, P: int, B: int, L: int, dtype) -> bool:
    """Whether an ``mla`` block's chunk attends through the kernel's latent
    form (``paged_attention.latent_attention``), decided at trace time as
    :func:`paged_kernel_fits` is: ONE token a row, rows and their value
    columns (the latent, ``kv_rank``) of whole lane tiles, pages of whole
    sublane tiles of the pool's dtype, the pool in the compute dtype,
    ``q`` and the output of ``B`` rows with the ring within VMEM — and no
    mesh axis left to partition over.  Whatever it refuses takes the
    gather path (``mla.attend_absorbed``)."""
    if L != 1 or cfg.block.attention != "mla":
        return False
    dtype = jnp.dtype(dtype)
    width, values = mla.row_width(cfg), cfg.block.latent.kv_rank
    return (
        width % 128 == 0
        and values % 128 == 0
        and P % (32 // dtype.itemsize) == 0
        and dtype == jnp.dtype(cfg.dtype)
        and not _mesh_partitions()
        and paged_attention.latent_vmem_bytes(
            B, cfg.n_heads, width, values, P, dtype
        ) <= paged_attention.VMEM_BUDGET_BYTES
    )


def _latent_attention(bp, x, positions, cfg, pages, tables, layer,
                      from_zero=False):
    """The attention half of an ``mla`` block against ``layer``'s pages of
    the ONE stacked latent pool ``[n_layers, 1, n_pages, P, width]``:
    ``(x', pages')``.  The chunk's rows (``mla.project``) are written as
    any K would be.  A chunk that starts its sequence (``from_zero``, a
    prefill) then attends over its own rows expanded by head, as the
    pages hold them.  A one-token chunk that fits
    (:func:`latent_kernel_fits`) attends in the latent space through the
    kernel, which reads each row's pages in place up to its frontier;
    any other gathers the table's pages into a ``[B, max_pages * P,
    width]`` view and attends over it in the latent space
    (``mla.attend_absorbed``).  Nothing is expanded by head either way."""
    dt = cfg.dtype
    q_n, q_r, row = mla.project(bp, x, positions, cfg)
    with jax.named_scope("latent_write"):
        pages, _ = _page_write(
            pages, None, row, None, positions, tables, layer, from_zero
        )
    B, L = x.shape[:2]
    P = pages.shape[3]
    if from_zero:
        rows = row[:, :, 0].astype(pages.dtype).astype(dt)
        att = mla.attend_expanded(bp, q_n, q_r, rows, positions, cfg)
    elif latent_kernel_fits(cfg, P, B, L, pages.dtype):
        q = mla.absorb(bp, q_n, q_r, pages.shape[-1], cfg)
        with jax.named_scope("latent_kernel"):
            # an idle row (its table all trash) reads the trash page, as
            # on the gather path
            lengths = jnp.clip(positions[:, 0] + 1, 1, tables.shape[1] * P)
            o_lat = paged_attention.latent_attention(
                q[:, 0], pages, tables, lengths, layer,
                cfg.block.latent.kv_rank, mla.softmax_scale(cfg),
            )
        att = mla.up(bp, o_lat[:, None], cfg)
    else:
        with jax.named_scope("page_gather"):
            rows = pages[layer, 0, tables].reshape(
                B, tables.shape[1] * P, -1
            ).astype(dt)
        att = mla.attend_absorbed(bp, q_n, q_r, rows, positions, cfg)
    return _attn_out(bp, x, att, cfg), pages


def retention_kernel_fits(cfg) -> bool:
    """Whether a retention block's decode step runs through the Pallas
    kernel (``parallel/retention.py``), decided at trace time like
    :func:`paged_kernel_fits`: heads of one lane tile and no mesh axis
    left to partition over.  What it refuses takes ``retention.step``."""
    return retention_kernel.fits(cfg.head_dim) and not _mesh_partitions()


def ssm_kernel_fits(cfg) -> bool:
    """Whether a mixer's decode step runs through the Pallas kernel
    (``parallel/ssm.py``), decided at trace time like
    :func:`retention_kernel_fits`: the state's shape and no mesh axis left
    to partition over.  What it refuses takes ``ssm.step``."""
    m = cfg.block.mixer
    return (
        ssm_kernel.fits(m.heads // m.groups, m.head_dim, m.d_state)
        and not _mesh_partitions()
    )


def _retention_step(bp, x, positions, cfg, st, live, layer):
    """The attention half of a ``retention`` block for ONE token a row
    against ``layer`` of the stacked state ``st = (S, z)``: ``(x', st')``.
    A live row's state is decayed, takes the token and is read out, in one
    pass where the kernel fits; a row that holds no sequence keeps its
    state untouched and reads zeros."""
    if x.shape[1] != 1:
        raise NotImplementedError(
            "a retention block steps one token a row; a chunk is a prefill"
        )
    S, z = st
    q, k, v, log_g = (
        t[:, 0] for t in retention.project(bp, x, positions, cfg)
    )
    with jax.named_scope("retention_step"):
        if retention_kernel_fits(cfg):
            y, S, z = retention_kernel.retention_step(
                retention.scaled(q), retention.scaled(k),
                v.astype(jnp.float32), log_g, S, z, live, layer,
            )
        else:
            y, s1, z1 = retention.step(q, k, v, log_g, S[layer], z[layer], live)
            S, z = S.at[layer].set(s1), z.at[layer].set(z1)
    return _attn_out(bp, x, y.astype(cfg.dtype)[:, None], cfg), (S, z)


def _retention_prefill(bp, x, positions, cfg, st, layer, slot, last_pos,
                       resume):
    """The attention half of a ``retention`` block for a chunk of ONE
    sequence: the chunked form from the slot's state of ``layer`` where
    the dispatch resumes a sequence, from zeros where it starts one — so
    nothing of the slot's previous tenant survives admission — leaving in
    the slot what the last real token leaves.  ``(x', st')``."""
    S, z = st
    q, k, v, log_g = (t[0] for t in retention.project(bp, x, positions, cfg))
    valid = jnp.arange(x.shape[1], dtype=jnp.int32) <= last_pos[0]
    # int32 by hand: with x64 on, a Python 0 beside them is an int64
    at = (jnp.asarray(layer, jnp.int32), slot[0].astype(jnp.int32))
    zero = jnp.int32(0)

    def held(a):
        mine = jax.lax.dynamic_slice(
            a, at + (zero,) * (a.ndim - 2), (1, 1) + a.shape[2:]
        )[0, 0]
        return jnp.where(resume, mine, 0.0)

    y, s1, z1 = retention.chunked(q, k, v, log_g, held(S), held(z), valid)
    S, z = (
        jax.lax.dynamic_update_slice(
            a, new[None, None], at + (zero,) * new.ndim
        )
        for a, new in ((S, s1), (z, z1))
    )
    return _attn_out(bp, x, y.astype(cfg.dtype)[None], cfg), (S, z)


class _Site(NamedTuple):
    """Where a layer of a stack whose attention differs by layer
    (``cfg.block.layer_types``) keeps its pages: its kind's pool of the
    pair (0 the full layers', 1 the window layers'), its index in that pool
    (its rank among its kind; traced), its window (0 for a full layer) and
    the width of a sequence's ring, which ends its table row."""

    pool: int
    at: jnp.ndarray
    window: int
    ring: int

    def select(self, kp, vp, tables):
        """This layer's pools and table out of the pairs and the row."""
        split = tables.shape[1] - self.ring
        table = tables[:, split:] if self.window else tables[:, :split]
        return kp[self.pool], vp[self.pool], table

    def put(self, pools, kp, vp):
        """The pairs with this layer's kind's pools written back."""
        pair = list(zip(*pools))
        pair[self.pool] = (kp, vp)
        return tuple(zip(*pair))


def _rotates(cfg, site) -> bool:
    """Whether a layer rotates q and k: all do but a full layer of a stack
    with ``layer_types`` (NoPE among window layers)."""
    return site is None or bool(site.window)


def _ring_positions(positions, P: int, ring: int):
    """The positions of a window layer's keys as a ring table gathers them,
    ``[B, ring * P]``, for one query a row at ``positions[:, 0]``: slot
    ``s`` holds the latest logical page ``p`` at or before the query's with
    ``p % ring == s``; a slot whose page would lie before the sequence holds
    no key, and is given a position past every query's."""
    cur = positions[:, :1] // P
    page = cur - (cur - jnp.arange(ring, dtype=jnp.int32)[None]) % ring
    pos = page[:, :, None] * P + jnp.arange(P, dtype=jnp.int32)
    return jnp.where(
        page[:, :, None] >= 0, pos, jnp.iinfo(jnp.int32).max
    ).reshape(pos.shape[0], ring * P)


# a prefill's own float32 scores [kvh, g, L, L] are held whole up to this
# many bytes (``transformer._cache_attention``); a longer chunk attends
# through the flash kernel, which holds a block of them at a time
PREFILL_SCORES_BYTES = 1 << 28


def _prefill_attention(q, k, v, positions, window=0):
    """Causal attention of a chunk that starts its sequence, over its own
    keys (``window`` > 0: the last ``window`` of them, static)."""
    B, L, h, _ = q.shape
    if B * h * L * L * 4 <= PREFILL_SCORES_BYTES:
        return tfm._cache_attention(q, k, v, positions, window)
    with jax.named_scope("flash_prefill"):
        return flash.flash_prefill(q, k, v, window)


def _paged_block(bp, x, positions, cfg, kp, vp, tables, layer, st=None,
                 route=None, site=None):
    """One decoder block against ``layer``'s pages of the stacked pools.

    ``kp``/``vp``: [n_layers, kvh, n_pages, P, Dh], written and read at
    ``layer`` where they lie; ``tables``: [B, max_pages];
    ``positions``: [B, L] absolute positions (per-row frontiers).  The
    chunk's k/v scatter to ``tables[b, pos // P]`` at offset ``pos %
    P`` — table slots a sequence never reserved hold 0, so pad tokens
    and idle slots write the trash page.  A one-token chunk that fits
    (:func:`paged_kernel_fits`) then attends over its pages in place,
    through the table, up to its frontier; any other gathers the table's
    pages of the layer into a [B, max_pages * P] contiguous view and runs
    the UNMODIFIED ``transformer._cache_attention`` on it.  Either way
    positions past a row's frontier have exact zero weight, so stale page
    contents (previous tenants included) never contribute a bit.

    A ``cca`` block (``L`` = 1) also takes the convolution state of all
    layers ``st`` [n_layers, B, width] and leaves this position's in the
    layer's rows; a block with a mixer takes its state ``st = (S, tail)``
    and adds the mixer's output (:func:`ssm.mix_step`, on the same input
    as attention) to attention's; an ``experts_top1`` block takes
    ``route`` (:func:`_feed_forward`).  Returns ``(x', kp', vp', st',
    routed)``, the last two None where the spec has no such thing.

    A layer of a stack whose attention differs by layer takes its
    :class:`_Site`, ``kp`` / ``vp`` the pairs of pools and ``tables`` the
    whole rows: it reads and writes its kind's pool at its rank, a window
    layer through its ring (``window_write``; ``window_kernel``, or the
    ring's gather under the window's mask), and returns the pairs."""
    if cfg.block.attention == "retention":
        # no pages: ``st`` is the state, ``tables[:, 0]`` says who is live
        with jax.named_scope("attention"):
            x, st = _retention_step(
                bp, x, positions, cfg, st, tables[:, 0] > 0, layer
            )
        x, routed = _feed_forward(bp, x, cfg, layer, route)
        return x, kp, vp, st, routed
    B, L = x.shape[:2]
    dt = cfg.dtype
    pools, at, window = None, layer, 0
    if site is not None:
        if site.window and L != 1:
            raise NotImplementedError(
                "a window layer steps one token a row; a chunk is a prefill"
            )
        pools, at, window = (kp, vp), site.at, site.window
        kp, vp, tables = site.select(kp, vp, tables)
    P = kp.shape[3]
    cap = tables.shape[1] * P
    x_in = x
    # scope names are metadata: a profiler session groups the device
    # operations of a step under attention / page_write / paged_kernel
    # (or page_gather, on the general path; window_write / window_kernel
    # for a window layer; latent_write / latent_kernel for a latent block)
    with jax.named_scope("attention"):
        if cfg.block.attention == "mla":
            x, kp = _latent_attention(bp, x, positions, cfg, kp, tables, layer)
        else:
            if cfg.block.attention == "cca":
                q, k, v, row = cca.qkv_step(bp, x, positions, st[layer], cfg)
                st = st.at[layer].set(row)
            else:
                q, k, v = tfm._attn_qkv(
                    bp, x, positions, cfg, _rotates(cfg, site)
                )
            kvh, dh = k.shape[2:]
            if window:
                with jax.named_scope("window_write"):
                    kp, vp = _page_write(
                        kp, vp, k, v, positions, tables, at, ring=True
                    )
            else:
                kp, vp = _page_write(kp, vp, k, v, positions, tables, at)
            if paged_kernel_fits(cfg, P, B, L, kp.dtype):
                with jax.named_scope(
                    "window_kernel" if window else "paged_kernel"
                ):
                    # an idle row (any index, its table all trash) attends
                    # the trash page like the gather path: at least one key,
                    # at most the capacity (a ring holds any length)
                    lengths = (
                        jnp.maximum(positions[:, 0] + 1, 1) if window
                        else jnp.clip(positions[:, 0] + 1, 1, cap)
                    )
                    att = paged_attention.paged_attention(
                        q[:, 0], kp, vp, tables, lengths, at, window=window
                    )[:, None]
            else:
                with jax.named_scope("page_gather"):
                    # gather each row's pages of the layer, straight from the
                    # stack, into its contiguous cache view: the two advanced
                    # indices lead, [B, max_pages, kvh, P, Dh]
                    ck, cv = (
                        jnp.moveaxis(pages[at, :, tables], 2, 3).reshape(
                            B, cap, kvh, dh
                        ).astype(dt)
                        for pages in (kp, vp)
                    )
                k_pos = (
                    _ring_positions(positions, P, tables.shape[1]) if window
                    else None
                )
                att = tfm._cache_attention(q, ck, cv, positions, window, k_pos)
            x = _attn_out(bp, x, att, cfg)
    if cfg.block.mixer is not None:
        with jax.named_scope("mixer"):
            m, st = ssm.mix_step(
                bp, x_in, st, tables[:, 0] > 0, layer, cfg,
                ssm_kernel_fits(cfg),
            )
        x = x + m
    x, routed = _feed_forward(bp, x, cfg, layer, route)
    if pools is not None:
        kp, vp = site.put(pools, kp, vp)
    return x, kp, vp, st, routed


def _prefill_block(bp, x, positions, cfg, kp, vp, tables, layer, st, slot,
                   last_pos, route=None, site=None):
    """:func:`_paged_block` for ONE sequence whose chunk STARTS it
    (``positions`` count from 0): the same projections and page write,
    but the only keys such a chunk's queries may see are its own, which
    the layer has just computed — so attention runs causally over the
    chunk's k/v (rounded to the page dtype, as the pages hold them) and
    nothing is gathered back from the pages.  A ``cca`` block's state
    row of ``slot`` [1] is OVERWRITTEN with what the prompt's last real
    position ``last_pos`` [1] leaves, and so are a mixer's state and tail
    (:func:`ssm.mix_prefill`), so nothing of the slot's previous tenant
    survives admission.  Returns ``(x', kp', vp', st', routed)``.

    A chunk whose float32 scores would pass ``PREFILL_SCORES_BYTES``
    attends through the flash kernel (``attention/flash_prefill``).  A
    layer with a :class:`_Site` takes its kind's pool as
    :func:`_paged_block` does; a window layer writes the pages its ring
    keeps and attends over the last ``window`` keys of each query."""
    dt = cfg.dtype
    tail = None
    x_in = x
    pools, at, window = None, layer, 0
    if site is not None:
        pools, at, window = (kp, vp), site.at, site.window
        kp, vp, tables = site.select(kp, vp, tables)
    with jax.named_scope("attention"):
        if cfg.block.attention == "mla":
            x, kp = _latent_attention(
                bp, x, positions, cfg, kp, tables, layer, from_zero=True
            )
        else:
            if cfg.block.attention == "cca":
                q, k, v, tail = cca.qkv_sequence(bp, x, positions, cfg)
            else:
                q, k, v = tfm._attn_qkv(
                    bp, x, positions, cfg, _rotates(cfg, site)
                )
            if window:
                with jax.named_scope("window_write"):
                    kp, vp = _page_write(
                        kp, vp, k, v, positions, tables, at, from_zero=True,
                        ring=True, last_pos=last_pos,
                    )
            else:
                kp, vp = _page_write(
                    kp, vp, k, v, positions, tables, at, from_zero=True
                )
            att = _prefill_attention(
                q, k.astype(kp.dtype).astype(dt), v.astype(vp.dtype).astype(dt),
                positions, window,
            )
            x = _attn_out(bp, x, att, cfg)
    if cfg.block.mixer is not None:
        with jax.named_scope("mixer"):
            m, st = ssm.mix_prefill(bp, x_in, st, layer, slot, last_pos, cfg)
        x = x + m
    x, routed = _feed_forward(bp, x, cfg, layer, route)
    if tail is not None:
        with jax.named_scope("attention/conv_state"):
            last = jnp.take_along_axis(
                tail, last_pos[:, None, None], axis=1
            )[:, 0]
            st = st.at[layer, slot].set(last.astype(st.dtype))
    if pools is not None:
        kp, vp = site.put(pools, kp, vp)
    return x, kp, vp, st, routed


def _runs(cfg):
    """The runs of a stack whose attention differs by layer: ``(stack,
    first, stop, kind)`` for each longest run of consecutive layers of one
    parameter stack (the leading dense layers', or the rest) and one
    attention kind, in layer order."""
    runs = []
    for layer in range(cfg.n_layers):
        stack = "dense_blocks" if layer < cfg.block.dense_layers else "blocks"
        kind = cfg.block.kind_of(layer)
        if runs and runs[-1][0] == stack and runs[-1][3] == kind:
            runs[-1][2] = layer + 1
        else:
            runs.append([stack, layer, layer + 1, kind])
    return [tuple(r) for r in runs]


def _scan_layers(block, x, params, k_pages, v_pages, state, live, cfg):
    """The layer scan both forwards share.  ``block(bp, x, kp, vp, st,
    layer, route) -> (x, kp, vp, st, routed)`` is one layer.  The scan
    slices the stacked block params a layer at a time and hands each its
    index; the stacked pools and the state are its CARRY beside ``x``
    (and a router's ``r``), so a layer writes its pages into the one
    buffer every layer shares and no pool is sliced out of the stack or
    stacked back (PR 33: as ``xs`` / ``ys`` each layer of each step copied
    its 25-50 MB pool twice).  What each layer reports of its routing is
    stacked.  For the dense block every extra is None, an empty pytree:
    the lowered program is the one without them.

    Layers of two kinds are two runs of the one scan over the one carry
    (ROADMAP M2): the spec's leading ``dense_layers`` first, on their own
    stack ``params["dense_blocks"]`` and with no route, so that
    :func:`_feed_forward` gives them the dense SwiGLU; then the rest.

    A stack whose attention differs by layer (``cfg.block.layer_types``:
    window layers among full ones) is scanned as its :func:`_runs`, one
    scan each over the one carry, whose pools are then the pairs of the two
    kinds: a run's kind is static, so its layers trace one body and read
    one pool, and each layer of it is given its :class:`_Site` (``block``
    takes it as an eighth argument) and indexes its parameters out of the
    whole stack.  The runs of one stack report what they route in layer
    order, joined."""
    first = cfg.block.dense_layers
    blocks, experts, r = params["blocks"], None, None
    if cfg.block.routes:
        blocks, experts = moe.stack_experts(blocks, cfg)
    if cfg.block.ffn == "experts_top1":
        r = jnp.zeros(x.shape[:2] + (cfg.block.router_hidden,), jnp.float32)
    if cfg.block.layer_types:
        stacks = {"dense_blocks": params.get("dense_blocks"), "blocks": blocks}
        ring = ring_pages(cfg, k_pages[1].shape[3]) if cfg.block.window else 0
        carry, reports, ranks = (x, r, k_pages, v_pages, state), [], {}
        for stack, a, b, kind in _runs(cfg):
            carry, report = _scan_run(
                block, carry, stacks[stack], a, b,
                first if stack == "blocks" else 0,
                _Site(
                    int(kind == "window"), jnp.int32(ranks.get(kind, 0) - a),
                    cfg.block.window if kind == "window" else 0, ring,
                ),
                None if stack == "dense_blocks" else experts, live,
            )
            ranks[kind] = ranks.get(kind, 0) + b - a
            if report is not None:
                reports.append(report)
        routed = None
        if reports:
            routed = tuple(jnp.concatenate(parts) for parts in zip(*reports))
        x, _, k_pages, v_pages, state = carry
        return x, k_pages, v_pages, state, routed

    def run(carry, blocks, layers, experts):
        def step(carry, xs):
            x, r, kp, vp, st = carry
            bp, layer = xs
            route = None if experts is None else (r, live, experts)
            x, kp, vp, st, routed = block(bp, x, kp, vp, st, layer, route)
            report = None
            if routed is not None:
                r, report = routed[0], routed[1:]
            return (x, r, kp, vp, st), report

        return jax.lax.scan(
            step, carry, (blocks, jnp.arange(*layers, dtype=jnp.int32))
        )

    carry = (x, r, k_pages, v_pages, state)
    if first:
        carry, _ = run(carry, params["dense_blocks"], (0, first), None)
    (x, _, k_pages, v_pages, state), routed = run(
        carry, blocks, (first, cfg.n_layers), experts
    )
    return x, k_pages, v_pages, state, routed


def _scan_run(block, carry, stack, a, b, offset, site, experts, live):
    """One run of :func:`_scan_layers` for a stack whose attention differs
    by layer: layers ``a .. b - 1``, their parameters indexed out of
    ``stack`` at ``layer - offset`` (the run's is never sliced out of the
    stack), each at ``site`` with its rank ``site.at + layer``.  Returns
    ``(carry, report)``, ``report`` the layers' routing stacked or None."""

    def step(carry, layer):
        x, r, kp, vp, st = carry
        bp = jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, layer - offset, 0, False),
            stack,
        )
        route = None if experts is None else (r, live, experts)
        x, kp, vp, st, routed = block(
            bp, x, kp, vp, st, layer, route, site._replace(at=site.at + layer)
        )
        report = None
        if routed is not None:
            r, report = routed[0], routed[1:]
        return (x, r, kp, vp, st), report

    return jax.lax.scan(step, carry, jnp.arange(a, b, dtype=jnp.int32))


def _routing(routed, n_experts):
    """What a dispatch reports of its routing, read back with the tokens:
    ``(stats, chosen)``, or None for a model that routes nothing.
    ``stats`` int32 [5] is what the dispatch adds to the ``moe_*``
    counters, from the per-layer counts [expert layers, held experts] of
    what each expert HELD here got: layer-steps routed, tokens (for top-k,
    token-expert pairs) computed here, the fullest expert's and the
    experts that got any, each summed over the layers; and last the pairs
    the router picked for live tokens, whoever holds their experts.
    ``chosen`` int32 [expert layers, B * L] (top-k: [.., B * L, k]) is
    every token's expert in every layer, in the router's own numbering
    (``n_experts`` for a token that is not live): the decisions
    themselves, which a reference needs beside the tokens because routing
    is discontinuous."""
    if routed is None:
        return None
    counts, chosen = routed
    stats = jnp.stack([
        jnp.int32(counts.shape[0]),
        jnp.sum(counts),
        jnp.sum(jnp.max(counts, axis=1)),
        jnp.sum(counts > 0),
        jnp.sum(chosen < n_experts),
    ]).astype(jnp.int32)
    return stats, chosen.reshape((chosen.shape[0], -1) + chosen.shape[3:])


def _head_logits(params, x, cfg, lead):
    with jax.named_scope("head"):
        x = tfm._rms_norm(x, params["ln_f"], cfg.block.norm_eps)
        return tfm.head(params, x, cfg, lead)


def _step_forward(params, tokens, tables, indices, k_pages, v_pages, cfg,
                  state=None):
    """A token chunk against the paged cache, whatever the block:
    ``(logits, k_pages', v_pages', state', stats)``; see
    :func:`apply_paged`.  ``state`` [n_layers, B, width] is the ``cca``
    convolution state of the rows (for a ``retention`` block the rows'
    ``(S, z)``, and no pages; for a mixer's ``(S, tail)``), ``stats`` is :func:`_routing` over the
    rows that hold a sequence (a reserved page: ``tables[:, 0]``)."""
    B, L = tokens.shape
    positions = indices[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    x = _embed(params, tokens, cfg)
    live = None
    if cfg.block.routes:
        live = jnp.broadcast_to(tables[:, :1] > 0, (B, L))

    def block(bp, x, kp, vp, st, layer, route, site=None):
        return _paged_block(
            bp, x, positions, cfg, kp, vp, tables, layer, st, route, site
        )

    x, kps, vps, state, routed = _scan_layers(
        block, x, params, k_pages, v_pages, state, live, cfg
    )
    logits = _head_logits(params, x, cfg, "bl")
    return logits, kps, vps, state, _routing(routed, cfg.moe_experts)


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
    return _step_forward(
        params, tokens, tables, indices, k_pages, v_pages, cfg
    )[:3]


def _prefill_forward(params, toks, table, last_pos, k_pages, v_pages, cfg,
                     state=None, slot=None, start=None):
    """ONE sequence from position 0, whatever the block: ``(logits [1, V]
    at last_pos, k_pages', v_pages', state', stats)``; see
    :func:`paged_prefill`.  A ``cca`` block's state row of ``slot`` [1]
    (a mixer's state and tail) is OVERWRITTEN with what the prompt's last
    real position leaves, so nothing of the slot's previous tenant
    survives admission; tokens past
    ``last_pos`` are padding, routed to no expert and counted nowhere.  A
    ``retention`` block's chunk may also CONTINUE its sequence, from
    position ``start`` [1] and the slot's state (``state`` is ``(S, z)``):
    a chunk at ``start`` 0 starts from zeros."""
    B, L = toks.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = _embed(params, toks, cfg)
    live = None
    if cfg.block.routes:
        live = positions <= last_pos[:, None]

    def block(bp, x, kp, vp, st, layer, route, site=None):
        if cfg.block.attention == "retention":
            with jax.named_scope("attention"):
                x, st = _retention_prefill(
                    bp, x, positions + start[:, None], cfg, st, layer, slot,
                    last_pos, start[0] > 0,
                )
            x, routed = _feed_forward(bp, x, cfg, layer, route)
            return x, kp, vp, st, routed
        return _prefill_block(
            bp, x, positions, cfg, kp, vp, table, layer, st, slot, last_pos,
            route, site,
        )

    x, k_pages, v_pages, state, routed = _scan_layers(
        block, x, params, k_pages, v_pages, state, live, cfg
    )
    x = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)[:, 0]
    logits = _head_logits(params, x, cfg, "b")
    return logits, k_pages, v_pages, state, _routing(routed, cfg.moe_experts)


# ---------------------------------------------------------------------------
# serving executables (the decode scheduler's two compiled dispatches)
# ---------------------------------------------------------------------------

# Both DONATE the pools (PR 33): the arrays passed in are consumed, and the
# ones returned are the same buffers, written where they lie.  A caller
# keeps what a call returns and never what it passed.  A ``cca`` block's
# state (5 MB at ZAYA1's widths, against the pools' 1 GB) is carried by the
# scan like the pools but NOT donated: the benchmark's fault test
# (``perfbench/tests/test_correct_zaya.py``) hands back the state it
# passed, to see that a stale one is caught.  A ``retention`` block's state
# IS its pool (5.45 GB at 20 slots of 8 layers: no second copy fits), an
# argument of its own, ``retention``, donated like the pools; a mixer's
# (2.16 GB at 128 slots of Falcon-H1's 4 layers) travels the same way,
# beside the pages.
_DONATED = ("k_pages", "v_pages", "retention")

# the projections ``transformer._attn_qkv`` and ``retention.project`` read
# through ``transformer.linear``, and beside them the output gate's
# (``transformer.attn_gate``), which reads the same normed input
_QKV = ("wq", "wk", "wv")
_TURNED = _QKV + ("w_attn_gate",)


def serving_params(params: tfm.Params, cfg) -> tfm.Params:
    """The params the serving executables are handed, turned once: a block
    that projects through ``transformer._attn_qkv`` or ``retention.project``
    holds each layer stack's ``wq``, ``wk`` and ``wv`` as
    :class:`transformer.OutIn` ``[L, out, in]``, the layout the chip's dot
    reads a layer's slice in.  Held ``[L, in, out]``, every layer of every
    step and prefill copied its slice to that layout before the product
    (48 MB a layer at Mistral's widths, 70 MB at Brumby's).  Other blocks
    and quantized leaves are returned as they are; a leaf sharded by name
    keeps its spec, turned with it."""
    if cfg.block.attention not in ("gqa", "retention"):
        return params

    def turn(a):
        if isinstance(a, tfm.QTensor):
            return a
        t = jnp.swapaxes(a, -1, -2)
        s = getattr(a, "sharding", None)
        if isinstance(s, jax.sharding.NamedSharding):
            spec = list(s.spec) + [None] * (a.ndim - len(s.spec))
            spec[-2], spec[-1] = spec[-1], spec[-2]
            t = jax.device_put(t, jax.sharding.NamedSharding(
                s.mesh, jax.sharding.PartitionSpec(*spec)
            ))
        return tfm.OutIn(t)

    out = dict(params)
    for stack in ("dense_blocks", "blocks"):
        if stack in out:
            out[stack] = {
                k: turn(v) if k in _TURNED else v for k, v in out[stack].items()
            }
    return out


def projects_in_place(params: tfm.Params) -> bool:
    """Whether every q, k and v projection of the params is held as
    :class:`transformer.OutIn` (``decode_proj_in_place_steps``)."""
    held = [
        params[stack][k] for stack in ("dense_blocks", "blocks")
        if stack in params for k in _QKV if k in params[stack]
    ]
    return bool(held) and all(isinstance(w, tfm.OutIn) for w in held)


def _results(tokens, k_pages, v_pages, state, stats, cfg):
    """What a serving executable returns: the dense block's three, and for
    any other block also its state and its routing (:func:`_routing`;
    either may be None), which a dense model has none of.  A retention
    block has no pages to return: its tokens and its state."""
    if cfg.block.stateless:
        return tokens, k_pages, v_pages
    if cfg.block.attention == "retention":
        return tokens, state
    return tokens, k_pages, v_pages, state, stats


@functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=_DONATED
)
def paged_decode_step(params, toks, tables, indices, k_pages, v_pages, cfg,
                      state=None, retention=None):
    """One greedy decode step for the whole slot batch: toks [B] ->
    next tokens [B].  Fixed [max_slots] shapes — the ONE executable the
    scheduler reuses for every step of every request population (idle
    slots decode garbage into the trash page that nobody reads).
    Returns ``(next, k_pages', v_pages')``; a block that is not the dense
    one (``cfg.block``) also takes the pool's convolution ``state`` and
    returns ``(..., state', stats)`` (:func:`_results`).  The pools are
    donated: the call consumes them and returns them updated in place.  A
    ``retention`` block takes no pools (None) but its state ``retention``
    ``(S, z)``, donated, and ``tables`` [B, 1] that say which rows hold a
    sequence (> 0): ``(next, (S', z'))``.  A block with a mixer takes the
    pools and its state as ``retention``, donated, and returns it where
    a ``cca`` block returns its ``state'``."""
    logits, k_pages, v_pages, state, stats = _step_forward(
        params, toks[:, None], tables, indices, k_pages, v_pages, cfg,
        state if retention is None else retention,
    )
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return _results(nxt, k_pages, v_pages, state, stats, cfg)


@jax.jit
def advance_step_inputs(nxt, tables, indices):
    """What the step after :func:`paged_decode_step` is fed, while no
    sequence joins or leaves: ``(toks', indices')``.  A row that holds a
    sequence (``tables[:, 0] > 0``) is fed the token the step just produced
    at one position further; an idle row keeps token 0 at index 0, so its
    writes keep landing on the trash page.  The tables do not change."""
    live = tables[:, 0] > 0
    return jnp.where(live, nxt, 0), indices + live.astype(indices.dtype)


@functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=_DONATED
)
def paged_prefill(params, toks, table, last_pos, k_pages, v_pages, cfg,
                  state=None, slot=None, retention=None, start=None):
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
    contiguous ``generate`` samples from ``logits[:, -1]`` — and the
    pools; a block that is not the dense one also takes the pool's
    convolution ``state`` and the sequence's ``slot`` [1] and returns
    ``(..., state', stats)`` as :func:`paged_decode_step` does, the pools
    donated as there.  One executable per prompt bucket (the ladder
    bounds the grid).  A ``retention`` block takes no pools and no table
    (None) but its state ``retention``, donated, the ``slot`` and the
    position ``start`` [1] the chunk continues its sequence from (0: it
    starts one), and returns ``(tok, (S', z'))``."""
    logits, k_pages, v_pages, state, stats = _prefill_forward(
        params, toks, table, last_pos, k_pages, v_pages, cfg,
        state if retention is None else retention, slot, start,
    )
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _results(tok0, k_pages, v_pages, state, stats, cfg)
