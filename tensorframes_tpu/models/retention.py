"""Power retention (arXiv:2507.04239, "Scaling Context Requires Rethinking
Attention"), the attention sublayer of the Brumby block, in the forms
serving needs.

A head's attention weight is a POWER of the score, ``(q.k / sqrt(dh))**p``
with p = 2, decayed by a learned gate, and not its exponential.  A square
of an inner product is an inner product of squares: with the symmetric
power embedding ``phi(u) = (c_ab u_a u_b) for a <= b`` (``c_aa`` = 1,
``c_ab`` = sqrt 2), ``phi(u).phi(w) = (u.w)**2``.  So what softmax
attention keeps as a cache that grows with the context, this layer keeps
as a STATE of fixed size, per KV head ``S`` in ``R^{D x dh}`` and a
normaliser ``z`` in ``R^D``, ``D = dh (dh + 1) / 2`` (8,256 at heads of
128):

    S_t = g_t S_{t-1} + phi(k~_t) v_t^T        z_t = g_t z_{t-1} + phi(k~_t)
    y_t = phi(q~_t)^T S_t / (phi(q~_t)^T z_t + eps)

with ``g_t = sigmoid(gate_t)`` one a KV head and ``u~ = u / dh**(1/4)``.
In attention form, which this module computes only inside a chunk:
``A_ts = exp(sum_{r=s+1..t} log g_r) (q_t.k_s / sqrt(dh))**2`` for s <= t,
``y_t = sum_s A_ts v_s / (sum_s A_ts + eps)``.

The ORDER of phi's entries is free, and chosen for the chip: by offset,
``phi(u)[o, a] = c[o, a] u[a] u[(a + o) % dh]`` for ``o = 0 .. dh/2``.
Offset 0 is the diagonal, offsets ``1 .. dh/2 - 1`` hold every unordered
pair ``{a, a + o}`` once, and offset ``dh/2`` holds each of its pairs
twice, so only its first half counts (``c`` is 0 on the second).  That is
the symmetric form, ``D`` entries on ``(dh/2 + 1) x dh`` stored places
(8,320 at 128: 64 are never written and stay 0), and every row of it is a
lane rotation of ``u`` times ``u``: the decode kernel
(``parallel/retention.py``) builds it in registers, a vector at a time,
and the state's minor axis is whole lane tiles.  Stored: ``S`` as ``[..,
kvh, dh/2 + 1 (o), dh (v), dh (a)]`` and ``z`` as ``[.., kvh, dh/2 + 1,
dh]``, float32.

* :func:`step` is the recurrent form, one token a row (the ``jnp`` form of
  the kernel's step: what runs where the kernel does not fit, and what
  the tests hold the kernel against), :func:`read_out` its second half,
  which is also how the benchmark reads a served state with the
  reference's queries;
* :func:`chunked` is the paper's chunked form for one sequence: inside a
  chunk the attention form with the decay mask, across chunks the state.
  It starts from any state, so a prompt longer than a dispatch resumes
  from what the dispatch before left.

Float32 throughout: phi, the state, the normaliser, the read-out and its
division (products of float32 operands at ``HIGHEST``); q, k and v come
in the compute dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tfm

EPS = 1e-6  # beside the normaliser's read-out
# tokens a chunk of :func:`chunked` holds.  Across chunks every token reads
# the state through phi(q) (1.33 MB a token a layer in float32) and a chunk
# moves the state once (68 MB a layer); inside a chunk the work is c x c.
# At 256 the state's traffic is a fifth of phi's, the chunk's own transient
# (phi(q): 341 MB) fits beside weights and state, and the c x c part is a
# twentieth of the rest
CHUNK = 256
# tokens a prefill dispatch holds at most: a longer prompt goes in several,
# each resuming from the state the one before left (a constant of the
# block: the largest executable, and the longest stall of the decode lane)
PREFILL_TOKENS = 2048

_HI = jax.lax.Precision.HIGHEST


def offsets(dh: int) -> int:
    return dh // 2 + 1


def phi_dim(dh: int) -> int:
    """D: the entries of the symmetric form."""
    return dh * (dh + 1) // 2


def coef(dh: int) -> np.ndarray:
    """``c`` float32 [offsets, dh]: 1 on the diagonal, sqrt 2 on the pairs,
    0 on the second half of the last offset (its pairs a second time)."""
    c = np.full((offsets(dh), dh), np.sqrt(2.0), np.float32)
    c[0] = 1.0
    c[-1, dh // 2:] = 0.0
    return c


def state_shapes(cfg, slots: int):
    """``(S, z)`` shapes of ``slots`` sequences, all layers."""
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    return (
        (cfg.n_layers, int(slots), kvh, offsets(dh), dh, dh),
        (cfg.n_layers, int(slots), kvh, offsets(dh), dh),
    )


def init_state(cfg, slots: int):
    """The state of ``slots`` sequences, all layers, zeros in float32:
    also the state of a sequence before its position 0."""
    return tuple(jnp.zeros(s, jnp.float32) for s in state_shapes(cfg, slots))


def state_bytes_per_slot(cfg) -> int:
    """Bytes one sequence's state takes, all layers, as stored."""
    return sum(4 * int(np.prod(s)) for s in state_shapes(cfg, 1))


def scaled(u):
    """``u~ = u / dh**(1/4)`` in float32, so that ``phi(q~).phi(k~)`` is
    ``(q.k / sqrt(dh))**2``."""
    return u.astype(jnp.float32) * np.float32(u.shape[-1] ** -0.25)


@jax.named_scope("retention_expand")
def expand(u):
    """phi(u): [..., dh] float32 -> [..., offsets, dh], by offset."""
    dh = u.shape[-1]
    uu = jnp.concatenate([u, u], -1)
    rolled = jnp.stack(
        [uu[..., o:o + dh] for o in range(offsets(dh))], axis=-2
    )
    return coef(dh) * u[..., None, :] * rolled


def project(bp, x, positions, cfg):
    """``h = RMSNorm(x)`` -> ``(q [B, L, h, dh], k, v [B, L, kvh, dh], log g
    [B, L, kvh] float32)``: the dense block's projections with Qwen3's
    per-head RMSNorm on q and k before the rotation, and the gate, one a
    KV head, ``log sigmoid(h W_g + b_g)`` in float32."""
    B, L, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt, eps = cfg.dtype, cfg.block.norm_eps
    y = tfm._rms_norm(x, bp["ln1"], eps)
    q = tfm.linear(y, bp["wq"], dt).reshape(B, L, h, dh)
    k = tfm.linear(y, bp["wk"], dt).reshape(B, L, kvh, dh)
    v = tfm.linear(y, bp["wv"], dt).reshape(B, L, kvh, dh)
    q = tfm._rope(tfm._rms_norm(q, bp["q_norm"], eps), positions, cfg.rope_theta)
    k = tfm._rope(tfm._rms_norm(k, bp["k_norm"], eps), positions, cfg.rope_theta)
    with jax.named_scope("retention_gate"):
        gate = jnp.einsum(
            "bld,dk->blk", y, tfm.weight(bp["wg"], dt),
            preferred_element_type=jnp.float32,
        ) + bp["bg"].astype(jnp.float32)
        log_g = jax.nn.log_sigmoid(gate)
    return q, k, v, log_g


def read_out(q, S, z):
    """What the queries q [B, h, dh] read out of the rows' state S [B, kvh,
    O, dh, dh] and z [B, kvh, O, dh]: ``phi(q~)^T S / (phi(q~)^T z + eps)``,
    [B, h, dh] float32, a KV head's state read by its group of query heads."""
    B, h, dh = q.shape
    kvh = S.shape[1]
    pq = expand(scaled(q)).reshape(B, kvh, h // kvh, -1, dh)
    num = jnp.einsum("bkgoa,bkova->bkgv", pq, S, precision=_HI)
    den = jnp.einsum("bkgoa,bkoa->bkg", pq, z, precision=_HI)
    return (num / (den[..., None] + EPS)).reshape(B, h, dh)


def step(q, k, v, log_g, S, z, live):
    """The recurrent form, one token a row: q [B, h, dh], k, v [B, kvh, dh],
    log_g [B, kvh], the rows' state S [B, kvh, O, dh, dh] and z [B, kvh, O,
    dh], live [B] bool.  Returns ``(y [B, h, dh] float32, S', z')``; a row that
    is not live keeps its state and reads zeros."""
    pk = expand(scaled(k))  # [B, kvh, O, dh]
    g = jnp.exp(log_g)[..., None, None]
    v = v.astype(jnp.float32)
    S1 = g[..., None] * S + pk[:, :, :, None, :] * v[:, :, None, :, None]
    z1 = g * z + pk
    y = read_out(q, S1, z1)
    keep = live[:, None, None, None]
    return (
        jnp.where(live[:, None, None], y, 0.0),
        jnp.where(keep[..., None], S1, S),
        jnp.where(keep, z1, z),
    )


@jax.named_scope("retention_chunk")
def chunked(q, k, v, log_g, S, z, valid, chunk: int = CHUNK):
    """The chunked form for ONE sequence of L tokens from the state ``(S
    [kvh, O, dh, dh], z [kvh, O, dh])``: q [L, h, dh], k, v [L, kvh, dh], log_g
    [L, kvh], valid [L] bool (padding after the last real token: it
    neither decays the state nor enters it).  L is a multiple of the chunk
    or shorter than one.  Returns ``(y [L, h, dh] float32, S', z')``, the
    state as the last valid token leaves it."""
    L, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    c = min(int(chunk), L)
    n = L // c
    scale = np.float32(1.0 / np.sqrt(dh))
    causal = jnp.tril(jnp.ones((c, c), bool))

    def one(carry, xs):
        S, z = carry
        q, k, v, log_g, valid = xs
        a = jnp.cumsum(jnp.where(valid[:, None], log_g, 0.0), axis=0)  # [c, kvh]
        # inside the chunk: the attention form under the decay mask
        s = jnp.einsum(
            "tkgd,skd->kgts", q.reshape(c, kvh, g, dh), k,
            preferred_element_type=jnp.float32,
        ) * scale
        decay = a.T[:, :, None] - a.T[:, None, :]  # [kvh, t, s]
        mask = causal & valid[None, :]
        A = jnp.square(s) * jnp.exp(jnp.where(mask, decay, -jnp.inf))[:, None]
        num = jnp.einsum("kgts,skv->tkgv", A, v.astype(jnp.float32), precision=_HI)
        den = jnp.sum(A, axis=-1).transpose(2, 0, 1)  # [t, kvh, g]
        # across chunks: the state as the chunk found it, decayed to t
        pq = expand(scaled(q)).reshape(c, kvh, g, -1, dh)
        into = jnp.exp(a)[:, :, None]  # [t, kvh, 1]
        num = num + into[..., None] * jnp.einsum(
            "tkgoa,kova->tkgv", pq, S, precision=_HI
        )
        den = den + into * jnp.einsum("tkgoa,koa->tkg", pq, z, precision=_HI)
        y = num / (den[..., None] + EPS)
        # the state the chunk leaves: decayed through it, plus its tokens
        pk = expand(scaled(k))  # [c, kvh, O, dh]
        w = jnp.where(valid[:, None], jnp.exp(a[-1][None] - a), 0.0)  # [s, kvh]
        through = jnp.exp(a[-1])[:, None, None]
        S = through[..., None] * S + jnp.einsum(
            "skv,skoa->kova", w[..., None] * v.astype(jnp.float32), pk,
            precision=_HI,
        )
        z = through * z + jnp.einsum("sk,skoa->koa", w, pk, precision=_HI)
        return (S, z), y.reshape(c, h, dh)

    xs = tuple(
        t.reshape((n, c) + t.shape[1:]) for t in (q, k, v, log_g, valid)
    )
    (S, z), y = jax.lax.scan(one, (S, z), xs)
    return y.reshape(L, h, dh), S, z
