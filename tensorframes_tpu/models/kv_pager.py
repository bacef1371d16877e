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
* each attention kind a block may have (``BlockSpec.attention``) is ONE
  part of ``_KINDS``, and a state-space mixer beside attention is one
  more (:class:`_Mixer`): each owns the shapes of its pages, its state a
  decode slot, its half of a decode step and of a prefill, and the decode
  kernel its step takes (:class:`_Part`).  A latent block (``mla``) has
  ONE pool where the others have a pair; a ``retention`` block has NO
  pages, a sequence's whole past being a float32 state of fixed size, so
  a "page" of its pool is one slot's state; a ``cca`` block and a mixer
  keep a state a slot beside their pages.  :func:`_parts` is the one
  place the serving path looks a block's kind up, and the executables'
  convention is this module's alone (:func:`dispatch_args`,
  :func:`dispatch_results`): the decode scheduler knows no kind;
* a stack whose attention differs by layer (``BlockSpec(layer_types=...,
  window=...)``: Trinity's window layers among full ones) keeps each
  kind's pages in a pool of its own (:class:`PagePool`), a layer indexed
  by its rank among its kind (:class:`_Site`).  A sequence's table row is
  its full table and then its RING, the :func:`ring_pages` pages a window
  layer holds of it at any length: logical page ``p`` lies in ring slot
  ``p % ring``, so a window layer writes over the page that has fallen
  behind its window (:func:`_page_write`);
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
    holds no array (``k_pages`` / ``v_pages`` / ``state`` are None).

    Page 0 is the trash page: idle slots and pad tokens write there, so
    every scatter is unconditional.  It is excluded from the free list
    and from capacity accounting.

    ``state`` is the state a decode slot keeps, of whichever part of the
    block keeps one (:func:`_parts`; None where none does).  A retention
    block has NO pages (``k_pages`` / ``v_pages`` None): a page is then one
    slot's state, ``n_pages`` the slots and one more, and a sequence
    allocates ONE.  A state the executables donate (``donates_state``) is
    charged with a sequence's pages, ``n * page_bytes + state_bytes``, both
    read off the arrays' own shapes.

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
        self._parts = [p for p in _parts(cfg) if p is not None]
        # a window layer's window (tokens) and ring, and the window pool's
        # pages
        self.window = cfg.block.window
        self.ring = ring_pages(cfg, P) if self.window else 0
        if slots is None and (self.ring or any(p.state for p in self._parts)):
            raise ValueError(
                "this block keeps a state or a ring of pages a decode slot: "
                "PagePool(..., slots=max_slots)"
            )
        self.slots = None if slots is None else int(slots)
        self.window_pages = self.slots * self.ring + 1 if self.ring else 0
        self.donates_state = any(p.donated for p in self._parts)
        self.k_pages, self.v_pages, self.state = self.zeros()

        def page_bytes(pools, n):
            return sum(
                pages.size // n * self.dtype.itemsize
                for pages in pools if pages is not None
            )

        # what the budget LRU accounts, read off the arrays' own shapes:
        # one page's HBM across all layers, of every pool there is (K and
        # V together, or the one latent pool), and one slot's donated
        # state, all layers.  A pool with no pages (retention) has the
        # slot's state as its page
        full, window = (self.k_pages, self.v_pages), ()
        if self.ring:
            full, window = zip(self.k_pages, self.v_pages)
        self.page_bytes = page_bytes(full, self.n_pages)
        self.window_page_bytes = page_bytes(window, self.window_pages)
        self.state_bytes = sum(
            a.size // self.slots * a.dtype.itemsize
            for a in (self.state if self.donates_state else ())
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
        state)``, as the block's parts shape them (:func:`_parts`).  A
        latent block (``mla``) has ONE pool, a token's row of
        ``mla.row_width`` values laid out as one head; it travels where
        the K pool does, and ``v_pages`` is None."""
        cfg, parts = self.cfg, self._parts
        k = v = None
        if parts[0].pools is not None:
            heads, *widths = parts[0].pools(cfg)

            def pool(layers, n_pages, w):
                return None if w is None else jnp.zeros(
                    (layers, heads, n_pages, self.tokens_per_page, w),
                    self.dtype,
                )

            n_full = cfg.block.layer_types.count("full")
            k, v = (
                (pool(n_full, self.n_pages, w),
                 pool(cfg.n_layers - n_full, self.window_pages, w))
                if self.ring else pool(cfg.n_layers, self.n_pages, w)
                for w in widths
            )
        state = next(
            (p.state(cfg, self.slots, self.dtype) for p in parts if p.state),
            None,
        )
        return k, v, state

    def take(self):
        """Hand the arrays to their one holder: ``(k_pages, v_pages,
        state)``, of which the pool then keeps no reference — a second
        holder would pin a copy the executables' donation could not reuse
        (and read a deleted buffer after the first dispatch; a retention
        block's state is 2-5 GB, with no room for a second)."""
        arrays = self.k_pages, self.v_pages, self.state
        self.k_pages = self.v_pages = self.state = None
        return arrays

    # -- allocation ----------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable pages (trash page excluded)."""
        return self.n_pages - 1

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

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
    return _parts(cfg)[0].pools is not None


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


# ---------------------------------------------------------------------------
# the parts of a paged block: one object per attention kind, and the mixer
# ---------------------------------------------------------------------------


class _Chunk(NamedTuple):
    """What every layer of one dispatch reads beside its weights, pools and
    state: the tokens' ``positions`` [B, L] and the page ``tables``; for a
    prefill also its ``slot`` [1], the prompt's last real position
    ``last_pos`` [1] and the position ``start`` [1] it continues from."""

    positions: jnp.ndarray
    tables: Optional[jnp.ndarray]
    slot: Optional[jnp.ndarray] = None
    last_pos: Optional[jnp.ndarray] = None
    start: Optional[jnp.ndarray] = None


def _select(site, kp, vp, tables, layer):
    """A layer's pools, table, index and window: ``(kp, vp, tables, at,
    window, put)``.  A layer at a :class:`_Site` takes its kind's pools out
    of the pairs and its table out of the rows, at its rank, and ``put(kp,
    vp)`` gives back the pairs the scan carries; any other takes the stacks
    as they are, at ``layer``."""
    if site is None:
        return kp, vp, tables, layer, 0, lambda kp, vp: (kp, vp)
    split = tables.shape[1] - site.ring
    tables = tables[:, split:] if site.window else tables[:, :split]

    def put(k, v):
        pairs = list(zip(kp, vp))
        pairs[site.pool] = (k, v)
        return tuple(zip(*pairs))

    return kp[site.pool], vp[site.pool], tables, site.at, site.window, put


def _write(kp, vp, k, v, positions, tables, at, window, from_zero=False,
           last_pos=None):
    """:func:`_page_write` of a layer's chunk, a window layer's through its
    ring (``window_write``)."""
    if not window:
        return _page_write(
            kp, vp, k, v, positions, tables, at, from_zero=from_zero
        )
    with jax.named_scope("window_write"):
        return _page_write(
            kp, vp, k, v, positions, tables, at, from_zero=from_zero,
            ring=True, last_pos=last_pos,
        )


class _Part:
    """One part of a paged block, its attention of one kind or the mixer
    beside it, and what it owns: ``pools(cfg)``, the ``(heads, k width, v
    width)`` of its pages (a width None: no such pool; the attribute None:
    no pages); ``state(cfg, slots, dtype)``, its state of ``slots`` decode
    slots, zeros (the attribute None: no state); ``step`` and ``prefill``,
    its half of a decode step and of a prefill, against the stacked pools
    and state, written where they lie; ``kernels(pool, B)``, the decode
    kernel its one-token step of ``B`` rows takes, ``{counter: 1}`` (0
    where the shapes refuse it).  ``donated``: the executables take its
    state as ``retention``, donated, and a sequence is charged its slot's;
    ``turned``: it projects q, k and v through ``transformer.linear``
    (:func:`serving_params`)."""

    pools = state = None
    donated = turned = False

    # the most prompt tokens one prefill dispatch takes, a later one
    # resuming from the state the one before left in the slot (None: a
    # prompt goes whole)
    prefill_tokens = None

    def kernels(self, pool, B):
        return {}


class _Gqa(_Part):
    """Grouped-query attention over pages of K and V, ``[n_layers, kvh,
    n_pages, P, Dh]`` each.  A chunk's k/v scatter to ``tables[b, pos //
    P]`` at offset ``pos % P`` — table slots a sequence never reserved hold
    0, so pad tokens and idle slots write the trash page.  A one-token
    chunk that fits (:func:`paged_kernel_fits`) then attends over its pages
    in place, through the table, up to its frontier (``paged_kernel``);
    any other gathers the table's pages of the layer into a [B, max_pages *
    P] contiguous view and runs the UNMODIFIED ``transformer.
    _cache_attention`` on it (``page_gather``).  Either way positions past a
    row's frontier have exact zero weight, so stale page contents (previous
    tenants included) never contribute a bit.  A prefill's queries may see
    only its own keys, which the layer has just computed: it attends over
    them causally (rounded to the page dtype, as the pages hold them) and
    gathers nothing back (:func:`_prefill_attention`).

    A layer of a stack whose attention differs by layer takes its
    :class:`_Site`: it reads and writes its kind's pool at its rank, a
    window layer through its ring (``window_write``; ``window_kernel``, or
    the ring's gather under the window's mask) and, in a prefill, over the
    last ``window`` keys of each query."""

    turned = True

    def pools(self, cfg):
        return cfg.n_kv_heads, cfg.head_dim, cfg.head_dim

    def kernels(self, pool, B):
        keys = ("decode_kernel_steps",) + (
            ("decode_window_kernel_steps",) if pool.ring else ()
        )
        return dict.fromkeys(keys, int(paged_kernel_fits(
            pool.cfg, pool.tokens_per_page, B, 1, pool.dtype
        )))

    # a step's ``(q, k, v)`` and the state it leaves; a prefill's, and what
    # it leaves in the state once the block's feed-forward is done
    def qkv(self, bp, x, c, cfg, st, layer, site):
        return tfm._attn_qkv(bp, x, c.positions, cfg, _rotates(cfg, site)), st

    def qkv_prefill(self, bp, x, c, cfg, site):
        return tfm._attn_qkv(bp, x, c.positions, cfg, _rotates(cfg, site)), None

    def step(self, bp, x, c, cfg, kp, vp, st, layer, site):
        B, L = x.shape[:2]
        if site is not None and site.window and L != 1:
            raise NotImplementedError(
                "a window layer steps one token a row; a chunk is a prefill"
            )
        kp, vp, tables, at, window, put = _select(site, kp, vp, c.tables, layer)
        positions, dt, P = c.positions, cfg.dtype, kp.shape[3]
        cap = tables.shape[1] * P
        (q, k, v), st = self.qkv(bp, x, c, cfg, st, layer, site)
        kvh, dh = k.shape[2:]
        kp, vp = _write(kp, vp, k, v, positions, tables, at, window)
        if paged_kernel_fits(cfg, P, B, L, kp.dtype):
            with jax.named_scope("window_kernel" if window else "paged_kernel"):
                # an idle row (any index, its table all trash) attends the
                # trash page like the gather path: at least one key, at
                # most the capacity (a ring holds any length)
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
        return (_attn_out(bp, x, att, cfg), *put(kp, vp), st)

    def prefill(self, bp, x, c, cfg, kp, vp, st, layer, site):
        kp, vp, tables, at, window, put = _select(site, kp, vp, c.tables, layer)
        (q, k, v), leave = self.qkv_prefill(bp, x, c, cfg, site)
        kp, vp = _write(
            kp, vp, k, v, c.positions, tables, at, window, True, c.last_pos
        )
        dt = cfg.dtype
        att = _prefill_attention(
            q, k.astype(kp.dtype).astype(dt), v.astype(vp.dtype).astype(dt),
            c.positions, window,
        )
        return (_attn_out(bp, x, att, cfg), *put(kp, vp), st, leave)


class _Cca(_Gqa):
    """Compressed convolutional attention (``models/cca.py``): ``gqa``'s
    pages and a state a slot, ``[n_layers, slots, cca.state_width]``, the
    previous position's convolution inputs.  A step reads and writes its
    layer's rows; a prefill OVERWRITES the slot's row with what the
    prompt's last real position leaves (``attention/conv_state``), once
    the block's feed-forward is done."""

    turned = False

    def state(self, cfg, slots, dtype):
        return cca.init_state(cfg, slots, dtype)

    def qkv(self, bp, x, c, cfg, st, layer, site):
        q, k, v, row = cca.qkv_step(bp, x, c.positions, st[layer], cfg)
        return (q, k, v), st.at[layer].set(row)

    def qkv_prefill(self, bp, x, c, cfg, site):
        q, k, v, tail = cca.qkv_sequence(bp, x, c.positions, cfg)

        def leave(st, layer):
            with jax.named_scope("attention/conv_state"):
                last = jnp.take_along_axis(
                    tail, c.last_pos[:, None, None], axis=1
                )[:, 0]
                return st.at[layer, c.slot].set(last.astype(st.dtype))

        return (q, k, v), leave


class _Mla(_Part):
    """Latent attention (``models/mla.py``): ONE pool ``[n_layers, 1,
    n_pages, P, mla.row_width]``, a token's normed latent and rotated key
    part as one row (``mla.project``), written as any K would be
    (``latent_write``) and travelling where the K pool does (``vp`` None).
    A prefill attends over its own rows expanded by head, as the pages hold
    them.  A one-token step that fits (:func:`latent_kernel_fits`) attends
    in the latent space through the kernel's latent form, which reads each
    row's pages in place up to its frontier (``latent_kernel``); any other
    gathers the table's pages into a ``[B, max_pages * P, width]`` view and
    attends over it in the latent space (``mla.attend_absorbed``).  Nothing
    is expanded by head either way."""

    def pools(self, cfg):
        return 1, mla.row_width(cfg), None

    def kernels(self, pool, B):
        return {"decode_latent_kernel_steps": int(latent_kernel_fits(
            pool.cfg, pool.tokens_per_page, B, 1, pool.dtype
        ))}

    def _rows(self, bp, x, c, cfg, pages, layer, from_zero=False):
        q_n, q_r, row = mla.project(bp, x, c.positions, cfg)
        with jax.named_scope("latent_write"):
            pages, _ = _page_write(
                pages, None, row, None, c.positions, c.tables, layer, from_zero
            )
        return q_n, q_r, row, pages

    def step(self, bp, x, c, cfg, kp, vp, st, layer, site):
        q_n, q_r, _, kp = self._rows(bp, x, c, cfg, kp, layer)
        B, L = x.shape[:2]
        P, tables = kp.shape[3], c.tables
        if latent_kernel_fits(cfg, P, B, L, kp.dtype):
            q = mla.absorb(bp, q_n, q_r, kp.shape[-1], cfg)
            with jax.named_scope("latent_kernel"):
                # an idle row (its table all trash) reads the trash page, as
                # on the gather path
                cap = tables.shape[1] * P
                lengths = jnp.clip(c.positions[:, 0] + 1, 1, cap)
                o_lat = paged_attention.latent_attention(
                    q[:, 0], kp, tables, lengths, layer,
                    cfg.block.latent.kv_rank, mla.softmax_scale(cfg),
                )
            att = mla.up(bp, o_lat[:, None], cfg)
        else:
            with jax.named_scope("page_gather"):
                rows = kp[layer, 0, tables].reshape(
                    B, tables.shape[1] * P, -1
                ).astype(cfg.dtype)
            att = mla.attend_absorbed(bp, q_n, q_r, rows, c.positions, cfg)
        return _attn_out(bp, x, att, cfg), kp, vp, st

    def prefill(self, bp, x, c, cfg, kp, vp, st, layer, site):
        q_n, q_r, row, kp = self._rows(bp, x, c, cfg, kp, layer, True)
        rows = row[:, :, 0].astype(kp.dtype).astype(cfg.dtype)
        att = mla.attend_expanded(bp, q_n, q_r, rows, c.positions, cfg)
        return _attn_out(bp, x, att, cfg), kp, vp, st, None


class _Retention(_Part):
    """Power retention (``models/retention.py``): NO pages, a sequence's
    whole past a float32 state of fixed size a layer, ``(S [n_layers,
    slots, kvh, O, dh, dh], z [n_layers, slots, kvh, O, dh])``, donated.
    ``tables[:, 0] > 0`` says which rows of a step are live."""

    donated = turned = True

    def state(self, cfg, slots, dtype):
        return retention.init_state(cfg, slots)

    @property
    def prefill_tokens(self):
        return retention.PREFILL_TOKENS

    def step(self, bp, x, c, cfg, kp, vp, st, layer, site):
        """ONE token a row against ``layer`` of the stacked state ``st =
        (S, z)``.  A live row's state is decayed, takes the token and is
        read out, in one pass where the kernel fits; a row that holds no
        sequence keeps its state untouched and reads zeros."""
        live = c.tables[:, 0] > 0
        if x.shape[1] != 1:
            raise NotImplementedError(
                "a retention block steps one token a row; a chunk is a prefill"
            )
        S, z = st
        q, k, v, log_g = (
            t[:, 0] for t in retention.project(bp, x, c.positions, cfg)
        )
        with jax.named_scope("retention_step"):
            if retention_kernel_fits(cfg):
                y, S, z = retention_kernel.retention_step(
                    retention.scaled(q), retention.scaled(k),
                    v.astype(jnp.float32), log_g, S, z, live, layer,
                )
            else:
                y, s1, z1 = retention.step(
                    q, k, v, log_g, S[layer], z[layer], live
                )
                S, z = S.at[layer].set(s1), z.at[layer].set(z1)
        x = _attn_out(bp, x, y.astype(cfg.dtype)[:, None], cfg)
        return x, kp, vp, (S, z)

    def prefill(self, bp, x, c, cfg, kp, vp, st, layer, site):
        """A chunk of ONE sequence from position ``start``: the chunked
        form from the slot's state of ``layer`` where the dispatch resumes a
        sequence, from zeros where it starts one — so nothing of the slot's
        previous tenant survives admission — leaving in the slot what the
        last real token leaves."""
        positions, resume = c.positions + c.start[:, None], c.start[0] > 0
        S, z = st
        q, k, v, log_g = (
            t[0] for t in retention.project(bp, x, positions, cfg)
        )
        valid = jnp.arange(x.shape[1], dtype=jnp.int32) <= c.last_pos[0]
        # int32 by hand: with x64 on, a Python 0 beside them is an int64
        at = (jnp.asarray(layer, jnp.int32), c.slot[0].astype(jnp.int32))
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
        x = _attn_out(bp, x, y.astype(cfg.dtype)[None], cfg)
        return x, kp, vp, (S, z), None


class _Mixer(_Part):
    """A state-space mixer beside ``gqa`` attention (``models/ssm.py``:
    Falcon-H1's Mamba-2) on the same input: no pages, a state a slot ``(S
    float32, tail)``, donated.  A step steps a live slot's state in one
    pass of ``parallel/ssm.py``'s kernel where it fits (``ssm.step`` where
    not); a prefill runs SSD's chunked form and OVERWRITES the slot's
    state and tail.  Its halves return ``(m, st')``, ``m`` added to
    attention's output."""

    donated = True

    def state(self, cfg, slots, dtype):
        return ssm.init_state(cfg, slots, dtype)

    def kernels(self, pool, B):
        return {"decode_ssm_kernel_steps": int(ssm_kernel_fits(pool.cfg))}

    def step(self, bp, x, c, cfg, st, layer):
        return ssm.mix_step(
            bp, x, st, c.tables[:, 0] > 0, layer, cfg, ssm_kernel_fits(cfg)
        )

    def prefill(self, bp, x, c, cfg, st, layer):
        return ssm.mix_prefill(bp, x, st, layer, c.slot, c.last_pos, cfg)


_KINDS = {
    "gqa": _Gqa(), "cca": _Cca(), "mla": _Mla(), "retention": _Retention(),
}
_MIXER = _Mixer()


def _parts(cfg):
    """The parts of ``cfg``'s block: ``(attention, mixer)``, the mixer None
    where the spec has none.  The one place the serving path asks a block
    its attention kind; at most one part keeps a state, as a mixer runs
    beside ``gqa`` alone."""
    return (
        _KINDS[cfg.block.attention],
        None if cfg.block.mixer is None else _MIXER,
    )


# the decode-kernel counters every block's step adds to, 0 if it lacks theirs
_KERNEL_STEPS = ("decode_kernel_steps", "decode_latent_kernel_steps",
                 "decode_ssm_kernel_steps")


def prefill_tokens(cfg) -> Optional[int]:
    """The most prompt tokens a prefill dispatch of ``cfg``'s block takes, a
    later one resuming from the state left in the slot (None: all)."""
    return _parts(cfg)[0].prefill_tokens


def kernel_steps(pool, B: int) -> Dict[str, int]:
    """What a one-token step of ``B`` rows on ``pool`` adds to the
    decode-kernel counters: 1 where the block's parts trace it a kernel."""
    steps = dict.fromkeys(_KERNEL_STEPS, 0)
    for part in _parts(pool.cfg):
        if part is not None:
            steps.update(part.kernels(pool, B))
    return steps


def _paged_block(c, cfg, bp, x, kp, vp, st, layer, route=None, site=None):
    """One decoder block of a decode step, chunk ``c``, against ``layer`` of
    the stacked pools and state: attention by its kind (:func:`_parts`),
    the mixer beside it where the spec has one, the feed-forward
    (``route``: :func:`_feed_forward`).  ``(x', kp', vp', st', routed)``."""
    attention, mixer = _parts(cfg)
    x_in = x
    # scope names are metadata: a profiler session groups the device
    # operations of a step under attention / page_write / paged_kernel
    # (or page_gather, on the general path; window_write / window_kernel
    # for a window layer; latent_write / latent_kernel for a latent block)
    with jax.named_scope("attention"):
        x, kp, vp, st = attention.step(bp, x, c, cfg, kp, vp, st, layer, site)
    if mixer is not None:
        with jax.named_scope("mixer"):
            m, st = mixer.step(bp, x_in, c, cfg, st, layer)
        x = x + m
    x, routed = _feed_forward(bp, x, cfg, layer, route)
    return x, kp, vp, st, routed


def _prefill_block(c, cfg, bp, x, kp, vp, st, layer, route=None,
                   site=None):
    """:func:`_paged_block` for ONE sequence whose chunk starts it (a
    retention block's may resume it), each part's ``prefill``: what the
    chunk leaves in the slot's state OVERWRITES it, so nothing of the
    slot's previous tenant survives admission."""
    attention, mixer = _parts(cfg)
    x_in = x
    with jax.named_scope("attention"):
        x, kp, vp, st, leave = attention.prefill(
            bp, x, c, cfg, kp, vp, st, layer, site
        )
    if mixer is not None:
        with jax.named_scope("mixer"):
            m, st = mixer.prefill(bp, x_in, c, cfg, st, layer)
        x = x + m
    x, routed = _feed_forward(bp, x, cfg, layer, route)
    if leave is not None:
        st = leave(st, layer)
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
    :func:`apply_paged`.  ``state`` is the slot state of the rows (a
    ``cca`` block's [n_layers, B, width], a retention block's ``(S, z)``,
    a mixer's ``(S, tail)``), ``stats`` is :func:`_routing` over the rows
    that hold a sequence (a reserved page: ``tables[:, 0]``)."""
    B, L = tokens.shape
    positions = indices[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    x = _embed(params, tokens, cfg)
    live = None
    if cfg.block.routes:
        live = jnp.broadcast_to(tables[:, :1] > 0, (B, L))

    block = functools.partial(_paged_block, _Chunk(positions, tables), cfg)
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
    :func:`paged_prefill`.  The slot state of ``slot`` [1] is OVERWRITTEN
    with what the prompt's last real position leaves, so nothing of the
    slot's previous tenant survives admission; tokens past ``last_pos``
    are padding, routed to no expert and counted nowhere.  A ``retention``
    block's chunk may also CONTINUE its sequence, from position ``start``
    [1] and the slot's state: a chunk at ``start`` 0 starts from zeros."""
    B, L = toks.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = _embed(params, toks, cfg)
    live = None
    if cfg.block.routes:
        live = positions <= last_pos[:, None]

    block = functools.partial(
        _prefill_block, _Chunk(positions, table, slot, last_pos, start), cfg
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
# state (5 MB at ZAYA1's widths) is carried by the scan but NOT donated:
# the benchmark's fault test (``perfbench/tests/test_correct_zaya.py``)
# hands back the state it passed, to see that a stale one is caught.  A
# retention block's (5.45 GB at 20 slots of 8 layers) and a mixer's (2.16
# GB at 128 slots of Falcon-H1's 4 layers) go as ``retention``, donated.
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
    if not _parts(cfg)[0].turned:
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
    block has no pages to return: its tokens and its state.  This module
    alone knows the convention: :func:`dispatch_args` and
    :func:`dispatch_results` are the scheduler's side of it."""
    if cfg.block.stateless:
        return tokens, k_pages, v_pages
    if not holds_pages(cfg):
        return tokens, state
    return tokens, k_pages, v_pages, state, stats


def dispatch_args(pool, state, slot=None, start=None):
    """What a serving executable on ``pool`` is handed after the config,
    of the slot state held and a prefill's ``slot`` and ``start``: ``(args,
    kwargs)``.  The dense block takes none; a donated state goes as
    ``retention``, with a retention block's ``slot`` and ``start`` by name;
    any other block takes its state (None if donated) and ``slot`` in
    place."""
    cfg = pool.cfg
    if cfg.block.stateless:
        return (), {}
    at = () if slot is None else (np.array([slot], np.int32),)
    if not holds_pages(cfg):
        return (), dict(retention=state, **(
            {"slot": at[0], "start": start} if at else {}
        ))
    if pool.donates_state:
        return (None, *at), {"retention": state}
    return (state, *at), {}


def dispatch_results(cfg, out):
    """What a serving executable returned, whatever the block: ``(tokens,
    (k_pages, v_pages), state, stats)``, None where it returns no such
    thing."""
    if cfg.block.stateless:
        out = (*out, None, None)
    elif not holds_pages(cfg):
        out = (out[0], None, None, out[1], None)
    tokens, kp, vp, state, stats = out
    return tokens, (kp, vp), state, stats


@functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=_DONATED
)
def paged_decode_step(params, toks, tables, indices, k_pages, v_pages, cfg,
                      state=None, retention=None):
    """One greedy decode step for the whole slot batch: toks [B] ->
    next tokens [B].  Fixed [max_slots] shapes — the ONE executable the
    scheduler reuses for every step of every request population (idle
    slots decode garbage into the trash page that nobody reads).  The
    pools, and a state passed as ``retention``, are donated: the call
    consumes them and returns them updated in place.  What a block takes
    beside the pools is :func:`dispatch_args`'s, what it returns
    :func:`_results`'s: ``(next, k_pages', v_pages')`` for the dense
    block; a ``retention`` block takes no pools (None), and ``tables`` [B,
    1] that say which rows hold a sequence (> 0)."""
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
    contiguous ``generate`` samples from ``logits[:, -1]`` — and what
    else the block returns, as :func:`paged_decode_step` does; a block
    that is not the dense one also takes the sequence's ``slot`` [1].  One
    executable per prompt bucket (the ladder bounds the grid).  A
    ``retention`` block takes no pools and no table (None), and the
    position ``start`` [1] the chunk continues its sequence from (0: it
    starts one)."""
    logits, k_pages, v_pages, state, stats = _prefill_forward(
        params, toks, table, last_pos, k_pages, v_pages, cfg,
        state if retention is None else retention, slot, start,
    )
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _results(tok0, k_pages, v_pages, state, stats, cfg)
