"""Paged decode attention as a Pallas TPU kernel.

A one-token decode step attends over what each sequence HOLDS: its K and
V live in fixed-size pages of a shared pool (``models/kv_pager.py``),
scattered wherever the free list put them, and the row's page table says
where.  The XLA path gathers every page of every row's CAPACITY into a
contiguous copy and masks most of it out; this kernel reads the pages in
place — page by page through the table row, up to the row's frontier and
no further — with the online-softmax recurrence of ``parallel/flash.py``:
nothing of shape ``[B, max_pages * P, kvh, Dh]`` is written and no scores
over the capacity exist.

Layout it dictates: a layer's pool is ``[kvh, n_pages, P, Dh]``, so one
page of one head is ``[P, Dh]`` — with ``P`` a whole number of sublane
tiles (16 rows of bf16) a run of native tiles, 4 KB at P=16/Dh=128 — and
``pool.at[layer, :, page]`` is one strided DMA that brings a page of
EVERY kv head.  The kernel is handed the pools of ALL layers, stacked
``[n_layers, kvh, n_pages, P, Dh]``, with the layer to read: the stack
stays in HBM where it lies and no layer's pool is sliced out of it for
the call (PR 33).

Shape of the kernel: ONE program, no grid.  ``q`` [B, kvh, g, Dh] and the
output sit whole in VMEM (a decode step's are a few hundred KB), tables
and lengths in SMEM, the pools stay in HBM.  Rows are walked in order;
a row's keys come in compute blocks of ``pages_per_block`` pages (128
keys) through a ring of ``_RING`` buffers: while block ``n`` is
multiplied the copies of the next ``_RING - 1`` blocks — the row's own,
then the NEXT rows' first ones — are in flight, so only the first DMAs of
a call are exposed.  Scores, softmax statistics and the accumulator are
f32; the probabilities are rounded to the pool's dtype for the second
product, as ``transformer._cache_attention`` rounds them: same
mathematics, another order of summation.

The same walk serves a latent block (``models/mla.py``): ONE pool of
rows ``[n_layers, 1, n_pages, P, width]`` whose values are the keys'
first ``values`` columns, so a page is one copy into one ring and the
value product reads the buffer the scores read (:func:`latent_attention`,
named ``tfs_latent_attention``).

Off-TPU the kernel runs in Pallas interpret mode (``flash``'s rule and
its one WARNING).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _NEG_INF, _resolve_interpret

# the trace reduction and the docs find the kernels by these names
KERNEL_NAME = "tfs_paged_attention"
LATENT_KERNEL_NAME = "tfs_latent_attention"

# keys per compute block: one lane-width of scores
_BLOCK_KEYS = 128
# buffers in the ring: _RING - 1 blocks' copies are in flight while one is
# multiplied
_RING = 8
# what the kernel may ask of VMEM: the smallest default scoped limit of the
# chips served (16 MiB), with room left for the compiler's own
VMEM_BUDGET_BYTES = 12 * 2**20


def pages_per_block(P: int) -> int:
    """Pages a compute block holds: ``_BLOCK_KEYS`` keys' worth."""
    return max(1, _BLOCK_KEYS // int(P))


def vmem_bytes(B: int, h: int, kvh: int, dh: int, P: int, dtype) -> int:
    """VMEM the kernel asks for: K's and V's rings of buffers, each a
    compute block of every kv head, and ``q`` with the output (a group's
    rows padded to a sublane tile)."""
    item = jnp.dtype(dtype).itemsize
    buffers = 2 * _RING * kvh * pages_per_block(P) * P * dh * item
    rows = -(-(h // kvh) // 8) * 8
    return buffers + 2 * B * kvh * rows * dh * 4


def latent_vmem_bytes(B: int, h: int, width: int, values: int, P: int,
                      dtype) -> int:
    """VMEM :func:`latent_attention` asks for: ONE ring of row blocks, and
    ``q`` (``width`` wide) with the output (``values`` wide), both in
    ``dtype``, the heads padded to its sublane tile."""
    item = jnp.dtype(dtype).itemsize
    buffers = _RING * pages_per_block(P) * P * width * item
    sublanes = 32 // item
    rows = -(-h // sublanes) * sublanes
    return buffers + B * rows * (width + values) * item


def _paged_kernel(
    layer_ref,
    lengths_ref,
    tables_ref,
    q_ref,
    *refs,
    scale: float,
    max_pages: int,
    window: int = 0,
    values: int = 0,
):
    """``refs``: the pools, the output, their rings and the semaphores —
    ``k_hbm, v_hbm, o_ref, k_buf, v_buf, sems``, or with ``values`` > 0
    (static) one pool whose values are its keys' first ``values`` columns,
    ``k_hbm, o_ref, k_buf, sems``."""
    if values:
        k_hbm, o_ref, k_buf, sems = refs
        kv = ((k_hbm, k_buf),)
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
        kv = ((k_hbm, k_buf), (v_hbm, v_buf))
    B, kvh, g, dh = q_ref.shape
    ring, _, ppb, P, _ = k_buf.shape
    T = ppb * P
    # loop bounds and counters are int32 by hand: with x64 on, a Python
    # bound makes an int64 index, which Mosaic has no use for
    zero, ring_ = jnp.int32(0), jnp.int32(ring)
    layer = layer_ref[0]

    def first_page(b):
        """The row's first page: that of its window's first key."""
        return jax.lax.div(
            jnp.maximum(lengths_ref[b] - window, 0), jnp.int32(P)
        )

    def last_page(b):
        return jax.lax.div(lengths_ref[b] - 1, jnp.int32(P))

    def n_blocks(b):
        if window:
            return jax.lax.div(
                last_page(b) - first_page(b) + ppb, jnp.int32(ppb)
            )
        return jax.lax.div(lengths_ref[b] + (T - 1), jnp.int32(T))

    def fetch(b, i, slot):
        """Start the page copies of row ``b``'s block ``i`` into buffer
        ``slot`` — a page of every kv head a copy, K's before V's (one
        pool's alone where the values are the keys' columns) — and
        return the block after it: the row's next, or the next row's
        first.  Table slots past the row's width repeat its last page
        (masked like everything past the frontier); past the last row
        the walk stays on it, copies the kernel's end drains — always
        issued, so they schedule beside the products, not behind a
        branch.  Under a window the row's pages are a ring: the block
        counts from the window's first page, page ``p`` lies in table
        slot ``p % max_pages``, and the walk past the row's last page
        repeats that page."""
        if window:
            first, last = first_page(b), last_page(b)
            pages = [
                tables_ref[b * max_pages + jax.lax.rem(
                    jnp.minimum(first + i * ppb + j, last), jnp.int32(max_pages)
                )]
                for j in range(ppb)
            ]
        else:
            pages = [
                tables_ref[b * max_pages + jnp.minimum(i * ppb + j, max_pages - 1)]
                for j in range(ppb)
            ]
        for x, (hbm, buf) in enumerate(kv):
            for j, page in enumerate(pages):
                pltpu.make_async_copy(
                    hbm.at[layer, :, page], buf.at[slot, :, j],
                    sems.at[x, slot],
                ).start()
        last = i + 1 >= n_blocks(b)
        return (
            jnp.where(last, jnp.minimum(b + 1, B - 1), b),
            jnp.where(last, 0, i + 1),
        )

    def arrived(x, slot):
        """Wait for the ``ppb`` copies into K's (0) or V's (1) ``slot``;
        a wait knows its semaphore and its size, not its source."""
        hbm, buf = kv[x]
        for j in range(ppb):
            pltpu.make_async_copy(
                hbm.at[0, :, 0], buf.at[slot, :, j], sems.at[x, slot]
            ).wait()

    def row(b, carry):
        length = lengths_ref[b]
        q = q_ref[b]  # [kvh, g, dh]
        if window:  # the walk starts at the window's first page
            start = first_page(b) * P

        def block(i, carry):
            m, l, acc, n, fb, fi = carry
            # keep ring - 1 blocks in flight: the one that takes the
            # buffer the previous iteration has done with
            fb, fi = fetch(fb, fi, jax.lax.rem(n + ring - 1, ring_))
            slot = jax.lax.rem(n, ring_)
            arrived(0, slot)
            k = k_buf[slot].reshape(kvh, T, dh)
            s = jnp.einsum(
                "kgd,ktd->kgt", q, k, preferred_element_type=jnp.float32
            ) * np.float32(scale)
            pos = i * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            if window:
                pos = start + pos
                s = jnp.where((pos < length) & (pos >= length - window), s, _NEG_INF)
            else:
                s = jnp.where(pos < length, s, _NEG_INF)
            # every block walked holds a key under the frontier (length
            # >= 1), so m_new is finite and exp(-inf - m_new) is 0
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            if values:  # the buffer the scores read: no second copy
                v = k[..., :values]
            else:
                arrived(1, slot)
                v = v_buf[slot].reshape(kvh, T, dh)
            acc = acc * alpha + jnp.einsum(
                "kgt,ktd->kgd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc, n + 1, fb, fi

        _, l, acc, *carry = jax.lax.fori_loop(
            zero, n_blocks(b), block,
            (
                jnp.full((kvh, g, 1), _NEG_INF, jnp.float32),
                jnp.zeros((kvh, g, 1), jnp.float32),
                jnp.zeros((kvh, g, values or dh), jnp.float32),
                *carry,
            ),
        )
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return tuple(carry)

    fb, fi = jax.lax.fori_loop(
        zero, ring_ - 1, lambda d, at: fetch(*at, d), (zero, zero)
    )
    n, _, _ = jax.lax.fori_loop(zero, jnp.int32(B), row, (zero, fb, fi))

    def drain(d, _):
        slot = jax.lax.rem(n + d, ring_)
        for x in range(len(kv)):
            arrived(x, slot)

    jax.lax.fori_loop(zero, ring_ - 1, drain, None)


def paged_attention(
    q,
    k_pages,
    v_pages,
    tables,
    lengths,
    layer,
    interpret: Optional[bool] = None,
    window: int = 0,
):
    """softmax(q K^T / sqrt(d)) V of one query position a row over the
    keys the row holds in its pages of ``layer``.

    q: [B, h, Dh]; k_pages/v_pages: the stacked pools [n_layers, kvh,
    n_pages, P, Dh] with ``h % kvh == 0``, of which the kernel reads
    ``layer`` (an int32 scalar, traced or not) and nothing else — a
    caller with one layer's pool passes ``pool[None]`` and 0; tables:
    int32 [B, max_pages], physical page of each of the row's page slots;
    lengths: int32 [B], keys the row attends (positions ``0 .. length -
    1``), AT LEAST 1 — an idle row reads its table's first page like any
    other.  Returns [B, h, Dh] in ``q.dtype``.  Keys past a row's
    frontier — the tail of its last page, pages it never reserved — get
    exact zero weight; they must be finite, as zero times them is
    added.

    ``window`` > 0 (static): the row attends only to its last ``window``
    keys, positions ``length - window .. length - 1``, and its table is a
    ring: logical page ``p`` of the sequence lies in table slot ``p %
    max_pages``.  The walk starts at the window's first page; keys of that
    page before the window get exact zero weight."""
    return _walk(
        q, (k_pages, v_pages), tables, lengths, layer, interpret, KERNEL_NAME,
        scale=1.0 / np.sqrt(q.shape[2]), window=window,
    )


def latent_attention(
    q,
    pages,
    tables,
    lengths,
    layer,
    values: int,
    scale: float,
    interpret: Optional[bool] = None,
):
    """softmax(scale q R^T) R[:, :values] of one query position a row over
    the rows ``R`` it holds in its pages of ``layer``: a latent block's
    absorbed decode attention (``mla.absorb``), every head against the one
    pool.

    q: [B, h, width]; pages: the stacked pool [n_layers, 1, n_pages, P,
    width], read at ``layer`` alone; tables, lengths and ``layer`` as
    :func:`paged_attention`'s.  ``values`` and ``scale`` are static.
    Returns [B, h, values] in ``q.dtype``.  A page is one copy and the
    value product reads the buffer the scores read."""
    return _walk(
        q, (pages,), tables, lengths, layer, interpret, LATENT_KERNEL_NAME,
        scale=scale, values=values,
    )


def _walk(q, pools, tables, lengths, layer, interpret, name, **static):
    """The one ``pallas_call`` of both kernels: ``q`` [B, h, dh] whole in
    VMEM as ``kvh`` groups of heads, each of ``pools`` read in HBM through
    a ring of its own.  Returns [B, h, values or dh]."""
    B, h, dh = q.shape
    _, kvh, _, P, _ = pools[0].shape
    g = h // kvh
    dv = static.get("values") or dh
    buf = pltpu.VMEM((_RING, kvh, pages_per_block(P), P, dh), pools[0].dtype)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, max_pages=tables.shape[1], **static
        ),
        in_specs=[smem, smem, smem, vmem] + [hbm] * len(pools),
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((B, kvh, g, dv), q.dtype),
        scratch_shapes=[buf] * len(pools)
        + [pltpu.SemaphoreType.DMA((len(pools), _RING))],
        interpret=_resolve_interpret(interpret),
        name=name,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32).reshape(-1),
        q.reshape(B, kvh, g, dh),
        *pools,
    ).reshape(B, h, dv)
