"""Mamba-2's mixer (arXiv:2405.21060, "Transformers are SSMs"), as Falcon-H1
runs it beside attention in every block (arXiv:2507.22448), in the forms
serving needs.

From the block's normed input ``h`` (the same as attention's), with the
sizes of ``cfg.block.mixer`` (:class:`~.transformer.SSMSpec`) and the
multipliers of ``cfg.block.multipliers``:

    [z | x | B | C | dt] = (ssm_in * h) W_in * segment multipliers
    xBC = silu(conv1d(xBC) + b)      causal, depthwise, kernel d_conv
    dt = softplus(dt + dt_bias)      A = -exp(A_log)     one a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T           a head: [P, N]
    y_t = S_t C_t + D x_t
    out = ssm_out * W_out RMSNorm_groups(y * silu(z))

B and C are shared by the heads of a group (head ``i`` reads group ``i //
(heads / groups)``).  What a sequence carries from one position to the
next is a STATE of fixed size a layer: ``S`` for every head, float32 (4 MB
at Falcon-H1-34B's widths), and the convolution's last ``d_conv - 1``
inputs (the TAIL, 30 KB in the compute dtype).  The pool holds both for
every decode slot beside its pages (``kv_pager.PagePool``): ``S``
[n_layers, slots, heads, P, N] and the tail [n_layers, slots, d_conv - 1,
conv_dim], channels minor so that a row is whole lane tiles.

* :func:`mix_step` is the decode form, one token a row: the tail and the
  state of ``layer`` stepped where they lie, by the kernel
  (``parallel/ssm.py``) or by :func:`step`;
* :func:`mix_prefill` is the prefill form for ONE sequence from position
  0: the convolution over the whole prompt, SSD's chunked form
  (:func:`chunked`) from a zero state, and the slot's state and tail
  OVERWRITTEN with what the prompt's last real token leaves, so nothing
  of a slot's previous tenant survives admission.  Padding past the last
  real token enters nothing: its dt is 0, so it neither decays the state
  nor adds to it.

Float32: dt, softplus, exp(dt A), the state, the read-out and D's skip,
the convolution's sums and the gated norm's statistics (products of
float32 operands at ``HIGHEST``).  Weights and activations (z, x, B, C,
the tail) are in the compute dtype.  Device operations carry the scopes
``ssm_in``, ``ssm_conv``, ``ssm_step`` (a decode step's state: the kernel
``tfs_ssm_step``), ``ssd_chunk`` (a prefill's), ``ssm_norm`` and
``ssm_out``, under the block's ``mixer``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tfm
from ..parallel import ssm as ssm_kernel

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def state_shapes(cfg, slots: int):
    """``(S, tail)`` shapes of ``slots`` sequences, all layers."""
    m = cfg.block.mixer
    return (
        (cfg.n_layers, int(slots), m.heads, m.head_dim, m.d_state),
        (cfg.n_layers, int(slots), m.d_conv - 1, m.conv_dim),
    )


def init_state(cfg, slots: int, dtype):
    """The state of ``slots`` sequences, all layers, zeros: ``S`` in float32
    and the tail in ``dtype``."""
    s, t = state_shapes(cfg, slots)
    return jnp.zeros(s, _F32), jnp.zeros(t, dtype)


@jax.named_scope("ssm_in")
def project(bp, x, cfg):
    """``[.., D]`` -> ``(z [.., d_ssm], xBC [.., conv_dim])`` in the compute
    dtype and dt before its bias ``[.., heads]`` in float32: the block's
    ``ln1`` norm (attention's, the same ops), ``ssm_in``, the projection
    with float32 accumulation and the five segment multipliers."""
    m = cfg.block.mixer
    mult = cfg.block.multipliers
    dt = cfg.dtype
    y = tfm.times(tfm._rms_norm(x, bp["ln1"], cfg.block.norm_eps), mult.ssm_in)
    p = jnp.einsum(
        "...d,de->...e", y, tfm.weight(bp["ssm_in"], dt),
        preferred_element_type=_F32,
    )
    gn = m.groups * m.d_state
    cuts = np.cumsum([m.d_ssm, m.d_ssm, gn, gn])
    z, xs, b, c, dtr = (
        tfm.times(seg, s)
        for seg, s in zip(jnp.split(p, cuts, axis=-1), mult.ssm_segments)
    )
    return z.astype(dt), jnp.concatenate([xs, b, c], -1).astype(dt), dtr


def _silu_conv(bp, window, dtype):
    """``silu(sum_k w[k] window[.., k, :] + b)``: window [.., d_conv, C]."""
    w = bp["conv_w"].astype(_F32)
    out = jnp.sum(window.astype(_F32) * w, axis=-2) + bp["conv_b"].astype(_F32)
    return jax.nn.silu(out).astype(dtype)


@jax.named_scope("ssm_conv")
def conv_step(bp, xbc, tail):
    """This position's xBC [R, C] after the tail [R, d_conv - 1, C] of the
    positions before: ``(silu(conv) [R, C], tail')``."""
    window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
    return _silu_conv(bp, window, xbc.dtype), window[:, 1:]


@jax.named_scope("ssm_conv")
def conv_sequence(bp, xbc, last_pos):
    """A sequence's xBC [L, C] from position 0 (zeros before it): ``(silu(
    conv) [L, C], tail [d_conv - 1, C])``, the tail the inputs at positions
    ``last_pos - d_conv + 2 .. last_pos``."""
    L = xbc.shape[0]
    K = bp["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    window = jnp.stack([padded[k:k + L] for k in range(K)], axis=1)
    tail = jax.lax.dynamic_slice_in_dim(
        padded, jnp.asarray(last_pos, jnp.int32) + 1, K - 1, axis=0
    )
    return _silu_conv(bp, window, xbc.dtype), tail


def split(xbc, cfg):
    """xBC [.., conv_dim] -> ``(x [.., heads, P], B [.., groups, N], C)``."""
    m = cfg.block.mixer
    lead = xbc.shape[:-1]
    gn = m.groups * m.d_state
    x, b, c = jnp.split(xbc, [m.d_ssm, m.d_ssm + gn], axis=-1)
    return (
        x.reshape(lead + (m.heads, m.head_dim)),
        b.reshape(lead + (m.groups, m.d_state)),
        c.reshape(lead + (m.groups, m.d_state)),
    )


def discretize(bp, dtr):
    """``(dt = softplus(dt + dt_bias), A = -exp(A_log))``, float32."""
    return (
        jax.nn.softplus(dtr + bp["dt_bias"].astype(_F32)),
        -jnp.exp(bp["A_log"].astype(_F32)),
    )


def _by_head(u, heads):
    """A group's rows [.., G, N] for each of its heads: [.., heads, N]."""
    return jnp.repeat(u, heads // u.shape[-2], axis=-2)


def step(x, B, C, dt, A, D, S, live):
    """The recurrent form, one token a row: x [R, H, P], B and C [R, G, N],
    dt [R, H], A and D [H], the rows' state S [R, H, P, N] float32, live
    [R] bool.  Returns ``(y [R, H, P] float32, S')``, y with D's skip; a
    row that is not live keeps its state and reads zeros."""
    H = x.shape[1]
    x = x.astype(_F32)
    Bh, Ch = (_by_head(u.astype(_F32), H) for u in (B, C))
    S1 = (
        jnp.exp(dt * A)[..., None, None] * S
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    )
    y = jnp.einsum("rhpn,rhn->rhp", S1, Ch, precision=_HI) + D[:, None] * x
    return (
        jnp.where(live[:, None, None], y, 0.0),
        jnp.where(live[:, None, None, None], S1, S),
    )


@jax.named_scope("ssd_chunk")
def chunked(x, B, C, dt, A, S, chunk: int):
    """SSD's chunked form for ONE sequence of L tokens from the state ``S``
    [H, P, N]: x [L, H, P], B and C [L, G, N], dt [L, H] (0 where a token
    is padding: it then neither decays the state nor enters it), A [H].
    Inside a chunk the quadratic form, ``y_t = sum_{s <= t} (C_t . B_s)
    exp(sum_{r=s+1..t} dt_r A) dt_s x_s``; across chunks the state.  L
    need not be a multiple of the chunk.  Returns ``(y [L, H, P] float32,
    without D's skip, S')``, the state the last token leaves."""
    L, H, P = x.shape
    G, N = B.shape[1:]
    J = H // G
    c = min(int(chunk), L)
    pad = -L % c
    x, B, C, dt = (
        jnp.pad(u.astype(_F32), ((0, pad),) + ((0, 0),) * (u.ndim - 1))
        for u in (x, B, C, dt)
    )
    n = (L + pad) // c
    x = x.reshape(n, c, G, J, P)
    B, C = B.reshape(n, c, G, N), C.reshape(n, c, G, N)
    dt = dt.reshape(n, c, G, J)
    xdt = x * dt[..., None]
    a = jnp.cumsum(dt * A.reshape(G, J), axis=1)  # [n, c, G, J], <= 0
    at = a.transpose(0, 2, 3, 1)  # [n, G, J, c]
    causal = jnp.tril(jnp.ones((c, c), bool))
    # inside a chunk: scores C_t . B_s under the decay from s to t
    cb = jnp.einsum("ntge,nsge->ngts", C, B, precision=_HI)
    decay = jnp.exp(jnp.where(causal, at[..., :, None] - at[..., None, :], -jnp.inf))
    y = jnp.einsum("ngts,ngjts,nsgjp->ntgjp", cb, decay, xdt, precision=_HI)
    # what each chunk adds to the state, decayed to its end
    to_end = jnp.exp(at[..., -1:] - at).transpose(0, 3, 1, 2)  # [n, c, G, J]
    adds = jnp.einsum(
        "nsgjp,nsge->ngjpe", xdt * to_end[..., None], B, precision=_HI
    )
    through = jnp.exp(at[..., -1])  # [n, G, J]

    def carry(s, xs):
        through, add = xs
        return through[..., None, None] * s + add, s

    S, before = jax.lax.scan(carry, S.reshape(G, J, P, N), (through, adds))
    # across chunks: the state as the chunk found it, decayed to t
    y = y + jnp.einsum(
        "ntge,ngjpe->ntgjp", C, before, precision=_HI
    ) * jnp.exp(a)[..., None]
    return y.reshape(n * c, H, P)[:L], S.reshape(H, P, N)


@jax.named_scope("ssm_norm")
def gated_norm(bp, y, z, cfg):
    """``RMSNorm_groups(y * silu(z)) * gain``: y [.., d_ssm] float32, the
    statistics of each of ``groups`` groups in float32; the compute dtype
    out."""
    m = cfg.block.mixer
    g = y * jax.nn.silu(z.astype(_F32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (m.groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.block.norm_eps)
    return (g.reshape(shape) * bp["ssm_norm"].astype(_F32)).astype(cfg.dtype)


@jax.named_scope("ssm_out")
def out(bp, y, cfg):
    mult = cfg.block.multipliers
    return tfm.times(y @ tfm.weight(bp["ssm_out"], cfg.dtype), mult.ssm_out)


def mix_step(bp, x, st, live, layer, cfg, kernel: bool):
    """The mixer for ONE token a row, x [R, 1, D], against ``layer`` of the
    stacked state ``st = (S, tail)``: ``(m [R, 1, D], st')``.  A live row's
    state is decayed, takes the token and is read out, in one pass where
    ``kernel``; a row that holds no sequence keeps its state."""
    S, tail = st
    R = x.shape[0]
    z, xbc, dtr = project(bp, x[:, 0], cfg)
    xbc, rows = conv_step(bp, xbc, tail[layer])
    tail = tail.at[layer].set(rows)
    xs, b, c = split(xbc, cfg)
    dt, A = discretize(bp, dtr)
    D = bp["D"].astype(_F32)
    with jax.named_scope("ssm_step"):
        if kernel:
            y, S = ssm_kernel.ssm_step(
                xs.astype(_F32), b.astype(_F32), c.astype(_F32), dt, A, D,
                S, live, layer,
            )
        else:
            y, s1 = step(xs, b, c, dt, A, D, S[layer], live)
            S = S.at[layer].set(s1)
    y = gated_norm(bp, y.reshape(R, -1), z, cfg)
    return out(bp, y, cfg)[:, None], (S, tail)


def mix_prefill(bp, x, st, layer, slot, last_pos, cfg):
    """The mixer for ONE sequence from position 0, x [1, L, D] (positions
    past ``last_pos`` [1] are padding), against ``st = (S, tail)``: ``(m [1,
    L, D], st')``, the state and tail of ``slot`` [1] in ``layer``
    OVERWRITTEN with what position ``last_pos`` leaves."""
    S, tail = st
    m = cfg.block.mixer
    L = x.shape[1]
    last = last_pos[0].astype(jnp.int32)
    z, xbc, dtr = project(bp, x[0], cfg)
    xbc, rows = conv_sequence(bp, xbc, last)
    xs, b, c = split(xbc, cfg)
    dt, A = discretize(bp, dtr)
    dt = jnp.where((jnp.arange(L) <= last)[:, None], dt, 0.0)
    y, s1 = chunked(
        xs, b, c, dt, A, jnp.zeros(S.shape[2:], _F32), m.chunk
    )
    y = y + bp["D"].astype(_F32)[:, None] * xs.astype(_F32)
    # int32 by hand: with x64 on, a Python 0 beside them is an int64
    at = (jnp.asarray(layer, jnp.int32), slot[0].astype(jnp.int32))
    zero = jnp.int32(0)
    S = jax.lax.dynamic_update_slice(S, s1[None, None], at + (zero,) * 3)
    tail = jax.lax.dynamic_update_slice(
        tail, rows[None, None].astype(tail.dtype), at + (zero,) * 2
    )
    y = gated_norm(bp, y.reshape(L, -1), z, cfg)
    return out(bp, y, cfg)[None], (S, tail)
