"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434), the
attention sublayer of an ``attention="mla"`` block, in the two forms
serving needs.

Keys and values are not cached by head.  A token leaves ONE row behind,
``[c ; k_r]``: its key-value latent ``c = RMSNorm(h W_kva[:, :kv_rank])``
and the rotated part of its key, ``k_r = rope(h W_kva[:, kv_rank:])``,
which all heads share — ``page_width`` = ``kv_rank + rope_dim`` values a
token a layer (576 at the published widths, where K and V of its 64 heads
would be 20,480).  A page stores the row on whole lane tiles
(:func:`row_width`, 640 there, the tail zeros): the chip's tiled layout
pads a row-major row of 576 to that anyway, and given a ragged minor
dimension its compiler instead lays the pool out pages-minor and copies
the whole of it around every step (a compile for a described v5e shows
it, ``tests/test_paged_compile.py``).  A head's keys and values are linear
in the latent,
``[k_n(j) ; v(j)] = c W_kvb(j)``, so attention can be computed either way
round:

* :func:`attend_expanded` (a prefill, whose only keys are its own chunk's)
  expands the chunk's latents to per-head ``k_n`` and ``v`` and attends as
  any multi-head attention does, the rotated part added to the scores;
* :func:`attend_absorbed` (a decode step, against the rows a cache holds)
  moves ``W_kvb`` to the query's side: ``q_lat(j) = q_n(j) W_kvb^K(j)^T``,
  ``score = [q_lat(j) ; q_r(j)] . [c ; k_r]`` over a row as it lies,
  ``o_lat(j) = sum_s p c(s)`` over the row's first ``kv_rank`` values, and
  only then ``out(j) = o_lat(j) W_kvb^V(j)``.  Nothing of the cache's
  extent is ever expanded by head: the step reads 576 values a token held
  and multiplies them with all 64 heads' queries at once.  On the serving
  path a step whose shapes fit does the middle part — scores, softmax and
  the weighted sum — in the Pallas kernel ``tfs_latent_attention``
  (``parallel/paged_attention.py``), reading each row's pages where they
  lie, between :func:`absorb` and :func:`up`.

The same mathematics, summed in another order; both forms read the rows
rounded to the page dtype, as the pages hold them.  The scores' scale is
``(nope_dim + rope_dim) ** -0.5``, times YaRN's ``m ** 2`` where the spec
says so.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tfm


def page_width(cfg) -> int:
    """Values a token leaves in a layer's page: latent and rotated key."""
    return cfg.block.latent.kv_rank + cfg.block.latent.rope_dim


def row_width(cfg) -> int:
    """What a page stores of a token: :func:`page_width` on whole lane
    tiles of 128, zeros past the rotated key."""
    return -(-page_width(cfg) // 128) * 128


def softmax_scale(cfg) -> float:
    lat, yarn = cfg.block.latent, cfg.block.yarn
    scale = (lat.nope_dim + lat.rope_dim) ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= tfm.yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return float(scale)


def _rotate(x, positions, cfg):
    """RoPE over the whole of ``x``'s last axis by the spec's table; under
    YaRN cos and sin carry ``mscale / mscale_all_dim``'s ratio (1 where
    the two are equal, as published)."""
    yarn = cfg.block.yarn
    x = tfm._rope(
        x, positions, tfm.rope_table(cfg.rope_theta, x.shape[-1], yarn)
    )
    if yarn is not None:
        m = tfm.yarn_mscale(yarn.factor, yarn.mscale) / tfm.yarn_mscale(
            yarn.factor, yarn.mscale_all_dim
        )
        if m != 1.0:
            x = x * jnp.asarray(m, x.dtype)
    return x


def project(bp, x, positions, cfg):
    """``h = RMSNorm(x)`` down to the two latents and up to the queries:
    ``(q_n [B, L, H, nope], q_r [B, L, H, rope], row [B, L, 1,
    row_width])`` — ``row`` is what the page holds, the normed latent,
    the rotated key part and zeros to the tile's end, laid out as one KV
    head."""
    B, L, _ = x.shape
    lat, dt, eps = cfg.block.latent, cfg.dtype, cfg.block.norm_eps
    with jax.named_scope("mla_down"):
        h = tfm._rms_norm(x, bp["ln1"], eps)
        c_q = tfm._rms_norm(h @ tfm.weight(bp["wq_a"], dt), bp["q_ln"], eps)
        ckr = h @ tfm.weight(bp["wkv_a"], dt)
        c = tfm._rms_norm(ckr[..., : lat.kv_rank], bp["kv_ln"], eps)
        k_r = _rotate(ckr[..., None, lat.kv_rank:], positions, cfg)
        pad = jnp.zeros(
            (B, L, 1, row_width(cfg) - page_width(cfg)), k_r.dtype
        )
        row = jnp.concatenate([c[:, :, None], k_r, pad], -1)
    q = (c_q @ tfm.weight(bp["wq_b"], dt)).reshape(
        B, L, cfg.n_heads, lat.nope_dim + lat.rope_dim
    )
    q_r = _rotate(q[..., lat.nope_dim:], positions, cfg)
    return q[..., : lat.nope_dim], q_r, row


def _up_weights(bp, cfg):
    """``W_kvb`` by head: ``(W^K [kv_rank, H, nope], W^V [kv_rank, H,
    v])``."""
    lat = cfg.block.latent
    w = tfm.weight(bp["wkv_b"], cfg.dtype).reshape(
        lat.kv_rank, cfg.n_heads, lat.nope_dim + lat.v_dim
    )
    return w[..., : lat.nope_dim], w[..., lat.nope_dim:]


def _softmax(s, positions, dtype):
    """Causal softmax over keys at positions ``arange(S)``; a key past a
    query's position has exact zero weight.  s: [B, H, L, S] f32."""
    k_pos = jnp.arange(s.shape[-1], dtype=jnp.int32)
    mask = positions[:, None, :, None] >= k_pos[None, None, None, :]
    return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1).astype(dtype)


def attend_expanded(bp, q_n, q_r, rows, positions, cfg):
    """Attention of a chunk that starts its sequence over the chunk's own
    rows ``[B, L, row_width]``, expanded by head: ``[B, L, H * v]``."""
    lat, dt = cfg.block.latent, cfg.dtype
    B, L = rows.shape[:2]
    c = rows[..., : lat.kv_rank]
    k_r = rows[..., lat.kv_rank: page_width(cfg)]
    with jax.named_scope("mla_up"):
        w_k, w_v = _up_weights(bp, cfg)
        k_n = jnp.einsum("bsc,chn->bshn", c, w_k)
        v = jnp.einsum("bsc,chv->bshv", c, w_v)
    s = (
        jnp.einsum("blhn,bshn->bhls", q_n, k_n,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("blhr,bsr->bhls", q_r, k_r,
                     preferred_element_type=jnp.float32)
    ) * np.float32(softmax_scale(cfg))
    p = _softmax(s, positions, dt)
    att = jnp.einsum(
        "bhls,bshv->blhv", p, v, preferred_element_type=jnp.float32
    ).astype(dt)
    return att.reshape(B, L, cfg.n_heads * lat.v_dim)


def absorb(bp, q_n, q_r, width, cfg):
    """``W_kvb``'s key half moved to the query's side: ``[q_lat ; q_r ;
    0]`` ``[B, L, H, width]``, scored against a cached row of ``width``
    values as it lies."""
    w_k, _ = _up_weights(bp, cfg)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("blhn,chn->blhc", q_n, w_k)
        pad = jnp.zeros(q_r.shape[:-1] + (width - page_width(cfg),), q_r.dtype)
        return jnp.concatenate([q_lat, q_r, pad], -1)


def up(bp, o_lat, cfg):
    """``W_kvb``'s value half on the way out: the heads' weighted sums of
    latents ``o_lat`` [B, L, H, kv_rank] to ``[B, L, H * v]``."""
    B, L = o_lat.shape[:2]
    _, w_v = _up_weights(bp, cfg)
    with jax.named_scope("mla_up"):
        att = jnp.einsum("blhc,chv->blhv", o_lat, w_v)
    return att.reshape(B, L, cfg.n_heads * cfg.block.latent.v_dim)


def attend_absorbed(bp, q_n, q_r, rows, positions, cfg):
    """Attention over a cache's rows ``[B, S, row_width]`` as they lie,
    ``W_kvb`` absorbed into the query and applied to the output: ``[B, L,
    H * v]``.  Rows past a query's position have exact zero weight."""
    dt = cfg.dtype
    q = absorb(bp, q_n, q_r, rows.shape[-1], cfg)
    with jax.named_scope("mla_absorb"):
        s = jnp.einsum(
            "blhw,bsw->bhls", q, rows, preferred_element_type=jnp.float32
        ) * np.float32(softmax_scale(cfg))
        p = _softmax(s, positions, dt)
        # over the whole row, the columns past the latent dropped after:
        # slicing the latent out of the cache first would copy it
        o_lat = jnp.einsum(
            "bhls,bsw->blhw", p, rows, preferred_element_type=jnp.float32
        )[..., : cfg.block.latent.kv_rank].astype(dt)
    return up(bp, o_lat, cfg)
