"""Flash attention as a Pallas TPU kernel.

The transformer's attention is the FLOPs *and* HBM hot spot: the reference
XLA path (``parallel/ring.py::full_attention``) materialises the [B, H, L, L]
score matrix in HBM — O(L^2) bytes of traffic.  This kernel computes the
same softmax(QK^T)V with the online-softmax recurrence, streaming K/V blocks
through VMEM and keeping the running (max, denom, accumulator) state on-chip:
O(L) HBM traffic, MXU matmuls, f32 accumulation.

Scope: the single-sequence-shard case (``sp == 1`` — positions are the
row-major ``arange``).  Sequence-sharded attention is ``ring_attention``
(``parallel/ring.py``), which hosts this kernel's recurrence as its local
step (``flash_ring_step``).  The backward pass is ALSO Pallas (round 3): the
standard flash backward — two kernels (dQ over K blocks; dK/dV over Q
blocks) recomputing probability blocks from the forward's saved per-row
logsumexp — so training holds O(L) HBM end to end.

Off-TPU (the CPU test mesh) the kernels run in Pallas interpret mode, so the
same code paths are exercised everywhere — never silently: the first
interpreted call logs a WARNING naming the backend, because on a host whose
TPU failed to initialise interpret mode is reference code standing in for the
kernel.  Pass ``interpret=False`` to demand the Mosaic compile.

Measured on an earlier shared v5e (B=2 H=8 Dh=128 bf16, fwd+bwd, vs the XLA
reference path): parity at L<=4096, 4.4x faster at L=8192, and at L=16384 the
XLA backward OOMs (24.5G for the [L, L] scores) while flash runs in 392 ms.
``attn_impl="auto"`` dispatches on that crossover
(``TransformerConfig.flash_min_len``); full table in docs/PERF.md.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec as P

from .. import envutil

_log = logging.getLogger("tensorframes_tpu.flash")

_NEG_INF = float("-inf")


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means "interpret exactly when the backend is not a TPU" —
    and says so once, at WARNING, when that turns the kernels into
    interpreted reference code."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    envutil.warn_once(
        _log, "flash.interpret",
        "Pallas flash kernels are running in INTERPRET mode: the jax "
        "backend is %r, not 'tpu' (pass interpret=False to require the "
        "compiled kernel)",
        backend,
    )
    return True


def _per_shard(fn, in_kinds: str, out_kinds: str, B: int, H: int, KVH: int):
    """``fn`` made safe to lower under a device mesh.

    A Mosaic kernel cannot be partitioned automatically: where a
    ``pallas_call`` lowers, every axis of the ambient mesh must be Manual
    (jax raises "Mosaic kernels cannot be automatically partitioned"
    otherwise — which XLA:CPU's interpret mode never does, so only a run
    on several real chips shows it).  Attention is independent per batch
    row and per head, so the axes still free here become manual with the
    batch split over ``dp``/``ep`` and the heads over ``tp`` — the layout
    the transformer already gives q/k/v — and replicated where they do not
    divide.  Inside ring attention's ``sp``-manual region this nests; it
    is never differentiated, because every caller sits inside a
    ``custom_vjp``.

    Kinds, one letter per argument/result: ``q`` [B, L, H, Dh], ``k``
    [B, L, KVH, Dh], ``s`` per-row statistics [B, H, L], ``r`` replicated.
    """
    mesh = jax.sharding.get_abstract_mesh()
    free = [
        n
        for n, t in zip(mesh.axis_names, mesh.axis_types)
        if t != AxisType.Manual
    ]
    if not free:
        return fn
    batch, split = [], 1
    for a in ("dp", "ep"):
        if a in free and B % (split * mesh.shape[a]) == 0:
            batch.append(a)
            split *= mesh.shape[a]
    b = tuple(batch) or None
    tp = mesh.shape["tp"] if "tp" in free else 1
    h = "tp" if H % tp == 0 and KVH % tp == 0 and tp > 1 else None
    heads = P(b, None, h, None)
    spec = {"q": heads, "k": heads, "s": P(b, h, None), "r": P()}
    outs = tuple(spec[c] for c in out_kinds)
    return jax.shard_map(
        fn,
        in_specs=tuple(spec[c] for c in in_kinds),
        out_specs=outs if len(outs) > 1 else outs[0],
        # every axis, the already-manual ones included: the lowering checks
        # the innermost region's own axis set, not the union of the nest
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_k: int,
    window: int = 0,
):
    # padded QUERY rows are never masked here: their garbage outputs are
    # sliced off by the [:Lq] in _flash_fwd_impl, so only keys need seq_k
    qi = pl.program_id(1)
    j = pl.program_id(2)
    # with a window the key axis of the grid counts from the q block's
    # first key block (``_window_first``), not from 0
    ki = j if not window else _window_first(qi, block_q, block_k, window) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal block skip: a k block strictly above the diagonal contributes
    # nothing to this q block — skip its matmuls entirely (~2x fewer FLOPs
    # and VMEM loads at long L)
    needed = True
    if causal:
        needed = (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(needed)
    def _compute():
        q = q_ref[0]  # [block_q, dh]
        k = k_ref[0]  # [block_k, dh]
        v = v_ref[0]

        s = (
            jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            * np.float32(scale)
        )  # [block_q, block_k] f32

        q_idx = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_idx < seq_k  # padded keys contribute nothing
        if causal:
            mask &= q_idx >= k_idx
        if window:
            mask &= q_idx - k_idx < window
        s_masked = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:]  # [block_q, 1]
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, s_masked.max(axis=-1, keepdims=True))
        # -inf-safe online softmax: rows with no unmasked key yet keep
        # m=-inf and contribute zeros (exp(-inf - 0) == 0), never NaNs
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s_masked - m_safe)  # masked: exp(-inf - finite) == 0
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

        m_scr[:] = m_new
        l_scr[:] = l_new
        acc_scr[:] = acc

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l_fin = l_scr[:]
        denom = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # logsumexp per row — the backward's softmax residual (all-masked
        # rows keep -inf; the backward masks them out explicitly)
        if lse_ref is not None:
            lse_ref[0] = m_scr[:] + jnp.log(denom)


def _flash_forward_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                          **kw):
    """:func:`_flash_kernel` with no logsumexp output, for a forward nothing
    differentiates: a ``[rows, 1]`` float32 output is laid out a lane tile
    wide, 128 times its size (384 MiB at 48 heads of 16,384 queries)."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr, acc_scr, **kw)


def _pad_to(x, length, axis):
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _blocking(Lq, Lk, block_q, block_k):
    bq = min(block_q, max(8, Lq))
    bk = min(block_k, max(8, Lk))
    return bq, bk, -(-Lq // bq) * bq, -(-Lk // bk) * bk


def _to_bh(x, L_p):
    """[B, L, H, D] -> [B*H, L_padded, D]."""
    B, L, H, Dh = x.shape
    x = jnp.swapaxes(x, 1, 2).reshape(B * H, L, Dh)
    return _pad_to(x, L_p, axis=1)


def _kv_head_map(H: int, KVH: int):
    """Grid row (batch*H + h) -> K/V array row (batch*KVH + h//g): GQA K/V
    stay kv-width in HBM and every query head of a group reads the SAME
    block — no materialised repeat, h/kvh x less K/V HBM traffic."""
    if H % KVH:
        # a non-divisible count would wrap the map into the NEXT batch's
        # kv rows — silent cross-batch corruption; fail loudly instead
        raise ValueError(
            f"flash attention needs n_heads divisible by n_kv_heads; "
            f"got H={H}, KVH={KVH}"
        )
    g = H // KVH
    return lambda b: (b // H) * KVH + (b % H) // g


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret, window=0,
                    name=None):
    """Returns ``(out [B, Lq, H, Dh], lse [B, H, Lq_p])``.  k/v may be
    GQA-grouped [B, Lk, KVH, Dh] with H % KVH == 0.  A ``name``d kernel (the
    serving prefill's) computes no ``lse`` and returns ``out`` alone."""
    local = functools.partial(
        _flash_fwd_local, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_resolve_interpret(interpret), window=window, name=name,
    )
    return _per_shard(
        local, "qkk", "qs" if name is None else "q", q.shape[0], q.shape[2],
        k.shape[2],
    )(q, k, v)


def _window_first(qi, block_q: int, block_k: int, window: int):
    """The first key block a causal window of ``window`` keys reaches from
    q block ``qi``: the block of the key ``qi * block_q - window + 1``."""
    return jax.lax.div(
        jnp.maximum(qi * block_q - (window - 1), 0), jnp.int32(block_k)
    )


def _flash_fwd_local(q, k, v, *, causal, block_q, block_k, interpret,
                     window=0, name=None):
    B, Lq, H, Dh = q.shape
    Lk, KVH = k.shape[1], k.shape[2]
    kv_of = _kv_head_map(H, KVH)
    scale = 1.0 / np.sqrt(Dh)
    bq, bk, Lq_p, Lk_p = _blocking(Lq, Lk, block_q, block_k)

    qb, kb, vb = _to_bh(q, Lq_p), _to_bh(k, Lk_p), _to_bh(v, Lk_p)
    grid = (B * H, Lq_p // bq, Lk_p // bk)
    kv_block = lambda b, i, j: (kv_of(b), j, 0)  # noqa: E731
    if window:
        # a q block's keys are the ``bq + window - 1`` before its last
        # query: the grid walks the blocks they touch, from the first
        # (``_window_first``), and no other.  A step past the q block's
        # last key block maps to that block again, which the pipeline then
        # does not copy a second time, and skips its products
        nk = Lk_p // bk
        grid = grid[:2] + (min(nk, -(-(bq + window - 1) // bk) + 1),)

        def kv_block(b, i, j):
            last = jnp.minimum(((i + 1) * bq - 1) // bk, nk - 1)
            return (kv_of(b), jnp.minimum(_window_first(i, bq, bk, window) + j, last), 0)

    out_specs = [
        pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Lq_p, Dh), q.dtype),
        jax.ShapeDtypeStruct((B * H, Lq_p, 1), jnp.float32),
    ]
    kernel = _flash_kernel
    if name is not None:  # a forward alone: no logsumexp
        out_specs, out_shape, kernel = out_specs[:1], out_shape[:1], _flash_forward_kernel
    outs = pl.pallas_call(
        functools.partial(
            kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_k=bk,
            seq_k=Lk,
            window=window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dh), kv_block),
            pl.BlockSpec((1, bk, Dh), kv_block),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running row max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, Dh), jnp.float32),  # f32 output accumulator
        ],
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(qb, kb, vb)

    out = jnp.swapaxes(outs[0][:, :Lq].reshape(B, H, Lq, Dh), 1, 2)
    if name is not None:
        return out
    return out, outs[1].reshape(B, H, Lq_p)


# ---------------------------------------------------------------------------
# ring-attention local step (carry-in/carry-out online softmax)
# ---------------------------------------------------------------------------


def _ring_step_kernel(
    qo_ref,
    ko_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    o_out,
    m_out,
    l_out,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _load_carry():
        m_scr[:] = m_ref[0]
        l_scr[:] = l_ref[0]
        acc_scr[:] = o_ref[0]

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = (
        jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        * np.float32(scale)
    )
    if causal:
        q_idx = qo_ref[0, 0] + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_idx = ko_ref[0, 0] + kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_idx >= k_idx, s, _NEG_INF)

    m_prev = m_scr[:]
    l_prev = l_scr[:]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    m_scr[:] = m_new
    l_scr[:] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )

    @pl.when(kj == pl.num_programs(2) - 1)
    def _store_carry():
        o_out[0] = acc_scr[:]
        m_out[0] = m_scr[:]
        l_out[0] = l_scr[:]


def chunk_supported(c: int) -> bool:
    """Whether a per-device chunk length can be Pallas-tiled on TPU."""
    return any(c % b == 0 for b in (128, 64, 32, 16, 8))


def _chunk_block(c: int) -> int:
    for b in (128, 64, 32, 16, 8):
        if c % b == 0:
            return b
    # a non-8-multiple block shape fails Mosaic tiling on real TPUs (CPU
    # interpret mode would silently accept it — ADVICE r2); fail loudly so
    # callers route such shapes to the xla impl instead
    raise ValueError(
        f"flash_ring_step needs a per-device chunk length divisible by 8 "
        f"for TPU tiling; got C={c} — use attn impl 'xla' for this shape"
    )


def flash_ring_step(
    q, k, v, o, m, l, q_off, k_off,
    causal: bool = True,
    interpret: Optional[bool] = None,
):
    """One ring-attention step as a Pallas kernel: fold the K/V chunk at
    global offset ``k_off`` into the running online-softmax carry.

    The XLA step (``ring.py::_online_softmax_step``) materialises the
    [B, H, C, C] score block in HBM every ring hop; this kernel streams it
    through VMEM — O(C) HBM traffic per hop, the flash recurrence with the
    (o numerator f32, m row-max, l denominator) carry travelling between
    hops instead of living in scratch.

    q: [B, C, H, Dh]; k/v: [B, C, KVH, Dh] (GQA kv heads stay grouped —
    the kernel's index maps share blocks, so the ring never materialises
    an h-wide K/V per hop); o: [B, C, H, Dh] f32; m/l: [B, H, C] f32;
    ``q_off``/``k_off``: traced int32 global positions of the chunks.
    Returns the updated (o, m, l).
    """
    local = functools.partial(
        _ring_step_local, causal=causal,
        interpret=_resolve_interpret(interpret),
    )
    return _per_shard(
        local, "qkkqssrr", "qss", q.shape[0], q.shape[2], k.shape[2]
    )(q, k, v, o, m, l, q_off, k_off)


def _ring_step_local(q, k, v, o, m, l, q_off, k_off, *, causal, interpret):
    B, C, H, Dh = q.shape
    KVH = k.shape[2]
    kv_of = _kv_head_map(H, KVH)
    scale = 1.0 / np.sqrt(Dh)
    bq = _chunk_block(C)
    bk = bq

    def to_bh(x):  # [B, C, h, D] -> [B*h, C, D]
        return jnp.swapaxes(x, 1, 2).reshape(B * x.shape[2], C, x.shape[-1])

    qb, kb, vb, ob = to_bh(q), to_bh(k), to_bh(v), to_bh(o)
    # m/l travel as [BH, C, 1]: TPU block tiling needs the last two dims to
    # divide (8, 128) or equal the array dims — a trailing 1 satisfies that
    # and matches the kernel's (bq, 1) scratch layout exactly
    mb = m.reshape(B * H, C, 1)
    lb = l.reshape(B * H, C, 1)
    qo = jnp.reshape(jnp.asarray(q_off, jnp.int32), (1, 1))
    ko = jnp.reshape(jnp.asarray(k_off, jnp.int32), (1, 1))

    grid = (B * H, C // bq, C // bk)
    smem = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0), memory_space=pltpu.SMEM)
    carry_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    o_new, m_new, l_new = pl.pallas_call(
        functools.partial(
            _ring_step_kernel,
            scale=scale,
            causal=causal,
            block_q=bq,
            block_k=bk,
        ),
        grid=grid,
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (kv_of(b), j, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (kv_of(b), j, 0)),
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            carry_spec,
            carry_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            carry_spec,
            carry_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, C, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B * H, C, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, C, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qo, ko, qb, kb, vb, ob, mb, lb)

    o_out = jnp.swapaxes(o_new.reshape(B, H, C, Dh), 1, 2)
    return o_out, m_new.reshape(B, H, C), l_new.reshape(B, H, C)


# ---------------------------------------------------------------------------
# backward: the standard flash recomputation from saved lse (two kernels —
# dQ accumulates over K blocks; dK/dV accumulate over Q blocks)
# ---------------------------------------------------------------------------


def _bwd_mask_and_p(
    q, k, lse, qi, ki, block_q, block_k, scale, causal, seq_q, seq_k
):
    """Recompute the probability block P = exp(S - lse) with padding and
    causal masks applied (shared by both backward kernels)."""
    s = (
        jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        * np.float32(scale)
    )
    q_idx = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_idx = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = (q_idx < seq_q) & (k_idx < seq_k)
    if causal:
        mask &= q_idx >= k_idx
    # all-masked rows carry lse = -inf; zero them via the mask, never
    # through exp(finite - (-inf)) = inf
    lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)  # [bq, bk] f32
    return p


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_k, seq_q, seq_k,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = True
    if causal:
        needed = (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        p = _bwd_mask_and_p(
            q, k, lse_ref[0], qi, ki, block_q, block_k, scale, causal,
            seq_q, seq_k,
        )
        dp = jnp.dot(
            do.astype(v.dtype), v.T, preferred_element_type=jnp.float32
        )
        ds = p * (dp - dd_ref[0])  # [bq, bk] f32
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        ) * np.float32(scale)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _store():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, block_q, block_k, seq_q, seq_k, n_q_blocks,
):
    ki = pl.program_id(1)  # k blocks are the outer loop here
    # the inner axis enumerates (query head of the GQA group, q block):
    # one kv head's dK/dV accumulate over ALL its query heads in VMEM,
    # so grouped grads need no cross-block reduction
    t = pl.program_id(2)
    qi = t % n_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        needed = (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(needed)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        p = _bwd_mask_and_p(
            q, k, lse_ref[0], qi, ki, block_q, block_k, scale, causal,
            seq_q, seq_k,
        )
        dv_scr[:] = dv_scr[:] + jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(
            do.astype(v.dtype), v.T, preferred_element_type=jnp.float32
        )
        ds = p * (dp - dd_ref[0])
        dk_scr[:] = dk_scr[:] + jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        ) * np.float32(scale)

    @pl.when(t == pl.num_programs(2) - 1)
    def _store():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_impl(
    q, k, v, out, lse, g, causal, block_q, block_k, interpret
):
    local = functools.partial(
        _flash_bwd_local, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_resolve_interpret(interpret),
    )
    return _per_shard(
        local, "qkkqsq", "qkk", q.shape[0], q.shape[2], k.shape[2]
    )(q, k, v, out, lse, g)


def _flash_bwd_local(
    q, k, v, out, lse, g, *, causal, block_q, block_k, interpret
):
    B, Lq, H, Dh = q.shape
    Lk, KVH = k.shape[1], k.shape[2]
    grp = H // KVH
    kv_of = _kv_head_map(H, KVH)
    scale = 1.0 / np.sqrt(Dh)
    bq, bk, Lq_p, Lk_p = _blocking(Lq, Lk, block_q, block_k)
    nq = Lq_p // bq

    qb, kb, vb = _to_bh(q, Lq_p), _to_bh(k, Lk_p), _to_bh(v, Lk_p)
    dob = _to_bh(g, Lq_p)
    # D = rowsum(dO * O): O(L*Dh) elementwise, f32 — cheap outside pallas
    dd = (
        dob.astype(jnp.float32) * _to_bh(out, Lq_p).astype(jnp.float32)
    ).sum(-1, keepdims=True)
    lse = lse.reshape(B * H, Lq_p, 1)

    kw = dict(
        scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=Lq, seq_k=Lk,
    )
    row_spec = pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0))
    col_spec = pl.BlockSpec((1, bk, Dh), lambda b, i, j: (kv_of(b), j, 0))
    row1_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    # dQ: q blocks outer, k blocks inner
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kw),
        grid=(B * H, Lq_p // bq, Lk_p // bk),
        in_specs=[row_spec, col_spec, col_spec, row_spec, row1_spec,
                  row1_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq_p, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dh), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, dd)

    # dK/dV: grid rows are KV heads; the inner axis runs (group head,
    # q block) so one kv head's dK/dV accumulate over all its query heads
    # in scratch — GQA grads come out kv-width with no extra reduction
    def q_row(b, j, t):
        return ((b // KVH) * H + (b % KVH) * grp + t // nq, t % nq, 0)

    row_spec2 = pl.BlockSpec((1, bq, Dh), q_row)
    col_spec2 = pl.BlockSpec((1, bk, Dh), lambda b, j, t: (b, j, 0))
    row1_spec2 = pl.BlockSpec((1, bq, 1), q_row)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q_blocks=nq, **kw),
        grid=(B * KVH, Lk_p // bk, grp * nq),
        in_specs=[row_spec2, col_spec2, col_spec2, row_spec2, row1_spec2,
                  row1_spec2],
        out_specs=[col_spec2, col_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((B * KVH, Lk_p, Dh), k.dtype),
            jax.ShapeDtypeStruct((B * KVH, Lk_p, Dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, Dh), jnp.float32),
            pltpu.VMEM((bk, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, dd)

    def from_bh(x, L, heads):
        return jnp.swapaxes(x[:, :L].reshape(B, heads, L, Dh), 1, 2)

    return from_bh(dq, Lq, H), from_bh(dk, Lk, KVH), from_bh(dv, Lk, KVH)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """softmax(QK^T / sqrt(d)) V with online softmax in a Pallas kernel.

    q: [B, Lq, H, Dh]; k/v: [B, Lk, KVH, Dh] with H % KVH == 0 — GQA
    K/V stay kv-width in HBM: every query head of a group reads the same
    K/V blocks via the grid index map (no materialised repeat, h/kvh x
    less K/V HBM traffic), and dK/dV accumulate per kv head inside the
    backward kernel, coming out kv-width.  Causal masking uses row-major
    positions (``arange``) — the sp == 1 case; use ``ring_attention`` for
    sequence-sharded inputs.

    Both passes are Pallas kernels with O(L) HBM traffic: the backward
    recomputes probability blocks from the saved per-row logsumexp (the
    standard flash backward) instead of materialising the [L, L] score
    matrix.
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out


# the trace reduction finds the serving prefill's kernel by this name
PREFILL_KERNEL_NAME = "tfs_flash_prefill"


def flash_prefill(q, k, v, window=0, block=512, interpret=None):
    """The forward of causal :func:`flash_attention` alone, for a serving
    prefill that starts its sequence: q [1, L, H, Dh], k/v [1, L, KVH, Dh]
    at row-major positions.  ``window`` > 0 (static) is a sliding window: a
    query at t sees the keys in (t - window, t], and the grid walks only the
    key blocks a q block's window touches, so the work grows with ``L *
    window`` and not ``L^2``.  Blocks of ``block`` queries and keys; the
    kernel is named ``PREFILL_KERNEL_NAME``."""
    if window < 0:
        raise ValueError(f"window {window}: a number of keys, or 0 for none")
    return _flash_fwd_impl(
        q, k, v, True, block, block, interpret, window, PREFILL_KERNEL_NAME
    )


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd_impl(
        q, k, v, out, lse, g, causal, block_q, block_k, interpret
    )


flash_attention.defvjp(_fwd, _bwd)
