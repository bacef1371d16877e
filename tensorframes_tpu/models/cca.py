"""Compressed convolutional attention (CCA, arXiv:2510.04476), the
attention sublayer of the ZAYA1 block, in the two forms serving needs.

All of attention runs in the compressed width: ``u = RMSNorm(x)`` is
projected to ``h`` query heads and ``kvh`` key heads of ``dh`` (together
``C = (h + kvh) * dh`` channels, far narrower than ``d_model``), and two
causal convolutions of kernel 2 over the sequence mix each position with
the one before it, zero history before position 0:

* depthwise, ``c1[t] = a0 * c[t-1] + a1 * c[t] + b1`` per channel;
* grouped by head, ``c2[t](h) = c1[t-1](h) A0(h) + c1[t](h) A1(h) + b2(h)``.

A q-k mean is added back (``mq(h) = (q~(h) + k~(h // g)) / 2`` and, for a
key head, the mean of ``mq`` over its ``g`` query heads), the value's
second half of heads is the projection of the *previous* token (value
shift), q and k are normalised per head to length ``sqrt(dh)`` (k times a
learned temperature per key head), and RoPE turns the spec's
``rotary_share`` of each head.  What comes out is an ordinary
``(q, k, v)`` for grouped-query attention, so pages hold K and V exactly
as they do for the dense block, post-convolution, normalised and rotated.

What is new for a server is the decode step: position ``t`` needs
``c[t-1]``, ``c1[t-1]`` and ``u[t-1] Wv2``, which no page holds.  That is
the per-sequence **convolution state**, ``state_width(cfg)`` values a
layer, fixed in size whatever the sequence length:

* :func:`qkv_sequence` is the whole-sequence form (a prefill, positions
  from 0): it also returns the state every position would leave behind,
  from which the caller takes the row at the prompt's last real position;
* :func:`qkv_step` is the step form: one token a row, the previous
  position's state in, this position's state out.

Both share :func:`_project`, :func:`_convolve` and :func:`_finish`, so the
step is the sequence form's arithmetic on other operands, not a copy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tfm

_NORM_EPS = 1e-6  # under the root of a head's squared length


def channels(cfg) -> int:
    return (cfg.n_heads + cfg.n_kv_heads) * cfg.head_dim


def shifted_width(cfg) -> int:
    """Width of the value's shifted half: the later half of the KV heads."""
    return (cfg.n_kv_heads - cfg.n_kv_heads // 2) * cfg.head_dim


def state_width(cfg) -> int:
    """``c[t-1]``, ``c1[t-1]`` and ``u[t-1] Wv2``, side by side."""
    return 2 * channels(cfg) + shifted_width(cfg)


def init_state(cfg, slots: int, dtype=None) -> jnp.ndarray:
    """The convolution state of ``slots`` sequences, all layers: zeros
    ``[n_layers, slots, state_width]``, which is also the history of a
    sequence before its position 0."""
    return jnp.zeros((cfg.n_layers, int(slots), state_width(cfg)),
                     dtype or cfg.dtype)


def _project(bp, x, cfg):
    """``u = RMSNorm(x)`` -> the compressed channels ``c = [u Wq ; u Wk]``
    and the two halves of the value, ``u Wv1`` (this token's) and
    ``u Wv2`` (what the NEXT position will use)."""
    dt = cfg.dtype
    u = tfm._rms_norm(x, bp["ln1"], cfg.block.norm_eps)
    c = jnp.concatenate(
        [u @ tfm.weight(bp["wq"], dt), u @ tfm.weight(bp["wk"], dt)], -1
    )
    return c, u @ tfm.weight(bp["wv1"], dt), u @ tfm.weight(bp["wv2"], dt)


@jax.named_scope("cca_conv")
def _convolve(bp, c, c_prev, c1_of_prev, cfg):
    """The two kernel-2 convolutions at the positions of ``c`` [B, L, C],
    given the previous position's channels ``c_prev`` and a function that
    turns this call's ``c1`` into the previous position's ``c1``.
    Returns ``(c1, c2)``."""
    dt = cfg.dtype
    H, dh = cfg.n_heads + cfg.n_kv_heads, cfg.head_dim
    w0, w1 = tfm.weight(bp["conv0_w"], dt), tfm.weight(bp["conv1_w"], dt)
    c1 = w0[0] * c_prev + w0[1] * c + tfm.weight(bp["conv0_b"], dt)
    c1_prev = c1_of_prev(c1)
    heads = lambda z: z.reshape(z.shape[:2] + (H, dh))
    c2 = (
        jnp.einsum("blhi,hio->blho", heads(c1_prev), w1[0])
        + jnp.einsum("blhi,hio->blho", heads(c1), w1[1])
        + tfm.weight(bp["conv1_b"], dt)
    )
    return c1, c2


def _finish(bp, c, c2, v1, v2_prev, positions, cfg):
    """q-k mean, normalisation, key temperature, RoPE and the value's two
    halves -> ``(q [B, L, h, dh], k [B, L, kvh, dh], v [B, L, kvh, dh])``."""
    B, L = c.shape[:2]
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    ch = c.reshape(B, L, h + kvh, dh)
    mq = (ch[:, :, :h] + jnp.repeat(ch[:, :, h:], g, axis=2)) * 0.5
    mk = jnp.mean(mq.reshape(B, L, kvh, g, dh), axis=3)
    q, k = c2[:, :, :h] + mq, c2[:, :, h:] + mk

    def unit(z):  # to length sqrt(dh), in f32
        z32 = z.astype(jnp.float32)
        n = jax.lax.rsqrt(jnp.sum(z32 * z32, -1, keepdims=True) + _NORM_EPS)
        return z32 * n * np.float32(np.sqrt(dh))

    q = unit(q).astype(cfg.dtype)
    k = (unit(k) * bp["k_temp"].astype(jnp.float32)[:, None]).astype(cfg.dtype)
    share = cfg.block.rotary_share
    q = tfm._rope(q, positions, cfg.rope_theta, share)
    k = tfm._rope(k, positions, cfg.rope_theta, share)
    v = jnp.concatenate([v1, v2_prev], -1).reshape(B, L, kvh, dh)
    return q, k, v


def _shift(z):
    """``z[t-1]`` along the sequence axis, zeros before position 0."""
    return jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def qkv_sequence(bp, x, positions, cfg):
    """Whole-sequence form for rows that START at position 0: x [B, L, D]
    -> ``(q, k, v, tail)``.  ``tail`` [B, L, state_width] holds, at every
    position, the state a step at the NEXT position would need; a prefill
    keeps the row at its prompt's last real position."""
    c, v1, v2 = _project(bp, x, cfg)
    c1, c2 = _convolve(bp, c, _shift(c), _shift, cfg)
    q, k, v = _finish(bp, c, c2, v1, _shift(v2), positions, cfg)
    return q, k, v, jnp.concatenate([c, c1, v2], -1)


def qkv_step(bp, x, positions, state, cfg):
    """Step form: x [B, 1, D] continues each row at ``positions`` [B, 1];
    ``state`` [B, state_width] is what the row's previous position left.
    Returns ``(q, k, v, state')``."""
    C = channels(cfg)
    with jax.named_scope("conv_state"):
        prev = state.astype(cfg.dtype)[:, None]
        c_prev, c1_prev, v2_prev = prev[..., :C], prev[..., C:2 * C], prev[..., 2 * C:]
    c, v1, v2 = _project(bp, x, cfg)
    c1, c2 = _convolve(bp, c, c_prev, lambda _: c1_prev, cfg)
    q, k, v = _finish(bp, c, c2, v1, v2_prev, positions, cfg)
    with jax.named_scope("conv_state"):
        state = jnp.concatenate([c, c1, v2], -1)[:, 0].astype(state.dtype)
    return q, k, v, state
