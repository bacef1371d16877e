"""Mixture-of-experts FFN with expert parallelism (the ``ep`` mesh axis).

Net-new relative to the reference (which has no models in-repo — SURVEY.md
§2.7: its only parallelism is Spark partition data-parallelism).  A complete
modern flagship-model family needs sparse scaling, and its TPU-native shape
is the GShard/Switch design rather than any ragged/dynamic dispatch:

* **Static-shape capacity routing.**  Every group of ``S`` tokens owns a
  fixed per-expert buffer of ``C = ceil(S * top_k * capacity_factor / E)``
  slots; tokens beyond an expert's capacity are dropped (their combine
  weight is zero, so the residual stream passes them through unchanged).
  Dispatch and combine are dense one-hot tensors ``[G, S, E, C]`` consumed
  by einsums — everything is a matmul on the MXU, no sorts, no ragged
  shapes, one compiled executable for every step.

* **Expert parallelism as a sharding constraint.**  Expert weights carry
  ``P("ep", ...)`` on their expert axis and the dispatched activations
  ``[E, G, C, D]`` are constrained to the same; with groups sharded over
  ``(dp, ep, sp)`` GSPMD lowers the layout change into the classic
  all-to-all over the ``ep`` axis.  No hand-written collectives — the same
  code runs unsharded on one chip.

* **tp composes inside each expert**: gate/up projections are
  column-sharded over ``tp`` and the down projection row-sharded, exactly
  like the dense SwiGLU, so one psum per MoE layer is inserted by GSPMD.

* **Groups are (batch x sp-chunk).**  Routing positions come from a cumsum
  over the group's token axis; making each sequence-parallel chunk its own
  group keeps that cumsum device-local under an ``sp`` mesh.

The auxiliary load-balance loss is the Switch formulation
``E * sum_e f_e * P_e`` (``f_e`` = fraction of tokens whose top-1 choice is
expert ``e``, ``P_e`` = mean router probability), returned as an f32 scalar
per layer and summed by the caller (``transformer.apply_blocks``).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def capacity(
    group_size: int, top_k: int, n_experts: int, factor: float
) -> int:
    """Per-expert slot count for one routing group — static at trace time.

    Never below 1, never above ``group_size`` (a token occupies at most one
    slot per expert across all ranks: rank ``r+1`` re-routes over the
    experts rank ``<= r`` did not pick)."""
    c = math.ceil(group_size * top_k * factor / n_experts)
    return max(1, min(group_size, c))


def gate(
    probs: jnp.ndarray, top_k: int, cap: int, valid=None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k capacity gating.

    ``probs`` [G, S, E] f32 (softmaxed router output) ->
    ``(dispatch [G, S, E, C], combine [G, S, E, C], aux [])``, all f32.
    ``valid`` [G, S] (optional) marks real tokens: padding (packed
    batches, ``data.pack_examples`` segment 0) neither claims capacity
    slots nor contributes to the load-balance statistics — otherwise pad
    garbage could evict real tokens and bias the aux loss.

    Slot assignment is rank-major then token-major (all rank-0 choices
    claim slots before any rank-1 choice, each in token order) — the
    GShard priority rule, so earlier ranks never lose capacity to later
    ones.  Combine weights follow the two standard routers: top-1 uses
    the raw gate probability (Switch — the router must receive task-loss
    gradient through the gate, which a renormalised p/p == 1 constant
    would kill); top-k>1 renormalises over the k picks *before* capacity
    dropping (GShard/Mixtral).  A dropped pick contributes zero, leaving
    the token's residual partially (or fully) un-updated rather than
    re-scaled.
    """
    G, S, E = probs.shape
    if valid is not None:
        vmask = valid.astype(probs.dtype)[..., None]  # [G, S, 1]
    picks = []  # (onehot [G,S,E], prob [G,S]) per rank
    masked = probs
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)
        oh = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        if valid is not None:
            oh = oh * vmask  # pad picks vanish: no slot, no weight
        picks.append((oh, jnp.sum(masked * oh, axis=-1)))
        # exclude the pick with a negative sentinel, not *0: a saturated
        # f32 softmax can underflow every other expert to exactly 0.0,
        # and argmax over an all-zero row would re-pick expert 0,
        # burning one of its capacity slots on a zero-weight duplicate
        masked = jnp.where(oh > 0, jnp.float32(-1.0), masked)
    if top_k == 1:
        denom = jnp.ones_like(picks[0][1])
    else:
        denom = jnp.maximum(sum(p for _, p in picks), 1e-9)

    dispatch = jnp.zeros((G, S, E, cap), probs.dtype)
    combine = jnp.zeros((G, S, E, cap), probs.dtype)
    used = jnp.zeros((G, 1, E), probs.dtype)  # slots taken by earlier ranks
    for oh, p in picks:
        # position of each token within its chosen expert's buffer:
        # earlier tokens of this rank + everything earlier ranks used
        pos = jnp.cumsum(oh, axis=1) - oh + used
        used = used + jnp.sum(oh, axis=1, keepdims=True)
        slot = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)  # [G, S]
        keep = oh * (pos < cap)  # [G, S, E]
        slot_oh = jax.nn.one_hot(slot, cap, dtype=probs.dtype)  # [G, S, C]
        contrib = keep[..., None] * slot_oh[:, :, None, :]
        dispatch = dispatch + contrib
        combine = combine + (p / denom)[..., None, None] * contrib

    # Switch load-balance loss on the PRE-capacity assignment (drops are a
    # capacity artefact; the router should be pushed toward balance, not
    # toward whatever the drops left behind); statistics over REAL tokens
    if valid is not None:
        n = jnp.maximum(jnp.sum(vmask), 1.0)
        f = jnp.sum(picks[0][0], axis=(0, 1)) / n
        p_mean = jnp.sum(probs * vmask, axis=(0, 1)) / n
    else:
        f = jnp.mean(picks[0][0], axis=(0, 1))  # top-1 fraction per expert
        p_mean = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p_mean)
    return dispatch, combine, aux


def _sp_groups(L: int) -> int:
    """How many sp chunks the sequence axis splits into under the ambient
    mesh (1 when no mesh / no divisible non-Manual ``sp`` axis)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or "sp" not in mesh.axis_names:
        return 1
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    if types["sp"] == jax.sharding.AxisType.Manual:
        return 1  # inside a shard_map: L is already the local chunk
    sp = mesh.shape["sp"]
    return sp if sp > 1 and L % sp == 0 else 1


def _route(bp, y: jnp.ndarray, cfg, segments=None):
    """The routing prologue shared by the executed layer (``moe_mlp``) and
    the diagnostics (``routing_stats``) — ONE definition so observability
    can never silently diverge from what the model runs.

    ``y`` [B, L, D] -> ``(yg [G, S, D], probs, dispatch, combine, aux,
    cap)`` with groups = (batch x sp-chunk)."""
    B, L, D = y.shape
    E = bp["router"].shape[-1]
    sp = _sp_groups(L)
    G, S = B * sp, L // sp
    yg = y.reshape(G, S, D)
    logits = jnp.einsum(
        "gsd,de->gse",
        yg.astype(jnp.float32),
        bp["router"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(logits, axis=-1)
    cap = capacity(S, cfg.moe_top_k, E, cfg.moe_capacity_factor)
    valid = None
    if segments is not None:
        valid = segments.reshape(G, S) > 0
    dispatch, combine, aux = gate(probs, cfg.moe_top_k, cap, valid)
    return yg, probs, dispatch, combine, aux, cap


def routing_stats(bp, y: jnp.ndarray, cfg, segments=None) -> dict:
    """Routing diagnostics for one batch of activations — the MoE
    observability surface (``observability.py`` spans time verbs; this
    inspects *where tokens go*).  Runs the SAME ``_route`` as the layer.
    Returns host-side floats:

    * ``load``: per-expert fraction of all (token, rank) assignments;
    * ``prob``: per-expert mean router probability;
    * ``drop_fraction``: assignments lost to capacity;
    * ``aux``: the load-balance loss this routing would contribute.
    """
    yg, probs, dispatch, _, aux, cap = _route(bp, y, cfg, segments)
    G, S, _ = yg.shape
    assigned = float(jnp.sum(dispatch))
    total = (
        int(jnp.sum(segments > 0)) if segments is not None else G * S
    ) * cfg.moe_top_k
    load = jnp.sum(dispatch, axis=(0, 1, 3)) / max(assigned, 1.0)
    return {
        "load": np.asarray(load, dtype=np.float64),
        "prob": np.asarray(jnp.mean(probs, axis=(0, 1)), dtype=np.float64),
        # an all-padding batch has zero routable slots: report drop 0
        # (nothing to drop), never divide by zero (ADVICE r3)
        "drop_fraction": (1.0 - assigned / total) if total else 0.0,
        "capacity": cap,
        "aux": float(aux),
    }


def layer_routing_stats(
    params, tokens: jnp.ndarray, cfg, layer: int = 0,
    positions=None, segments=None,
) -> dict:
    """``routing_stats`` on the ACTUAL MLP input of block ``layer`` for a
    token batch: runs the forward through blocks ``0..layer-1`` and block
    ``layer``'s attention half, then probes its router — the activations
    are exactly what training routed, not an embedding-space proxy.
    Pass ``positions``/``segments`` for packed batches so the replay (and
    the pad exclusion) matches packed training."""
    from . import transformer as tfm

    B, L = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    x = tfm.embed_lookup(params["embed"], tokens, cfg.dtype)
    blocks = params["blocks"]
    for i in range(layer):
        bp_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
        x, _ = tfm._block(bp_i, x, positions, cfg, None, segments)
    bp = jax.tree_util.tree_map(lambda a: a[layer], blocks)
    x, _ = tfm._attn_residual(bp, x, positions, cfg, None, segments)
    y = tfm._rms_norm(x, bp["ln2"])
    return routing_stats(bp, y, cfg, segments)


def moe_mlp(
    bp, y: jnp.ndarray, cfg, segments=None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The MoE replacement for the dense SwiGLU block.

    ``y`` [B, L, D] (post-RMSNorm activations) -> ``(out [B, L, D],
    aux [])``.  ``bp`` holds ``router`` [D, E], ``we_gate``/``we_up``
    [E, D, F], ``we_down`` [E, F, D].
    """
    from .transformer import shard, weight

    B, L, D = y.shape
    dt = cfg.dtype
    yg, _probs, dispatch, combine, aux, _cap = _route(bp, y, cfg, segments)

    # groups -> per-expert buffers: the E axis picks up the ep sharding the
    # G axis loses — GSPMD's cue for the dispatch all-to-all
    ex_in = jnp.einsum(
        "gsec,gsd->egcd", dispatch.astype(dt), yg.astype(dt),
        preferred_element_type=jnp.float32,
    ).astype(dt)
    ex_in = shard(ex_in, "ep", ("dp", "sp"), None, None)

    h_gate = jnp.einsum(
        "egcd,edf->egcf", ex_in, weight(bp["we_gate"], dt),
        preferred_element_type=jnp.float32,
    ).astype(dt)
    h_up = jnp.einsum(
        "egcd,edf->egcf", ex_in, weight(bp["we_up"], dt),
        preferred_element_type=jnp.float32,
    ).astype(dt)
    h = shard(jax.nn.silu(h_gate) * h_up, "ep", ("dp", "sp"), None, "tp")
    ex_out = jnp.einsum(
        "egcf,efd->egcd", h, weight(bp["we_down"], dt),
        preferred_element_type=jnp.float32,
    ).astype(dt)
    ex_out = shard(ex_out, "ep", ("dp", "sp"), None, None)

    # combine: back to token-major layout (the reverse all-to-all)
    out = jnp.einsum(
        "gsec,egcd->gsd", combine.astype(dt), ex_out,
        preferred_element_type=jnp.float32,
    ).astype(dt)
    out = out.reshape(B, L, D)
    return shard(out, ("dp", "ep"), "sp", None), aux


# ---------------------------------------------------------------------------
# dropless experts (the serving path's layers): top-1 behind an MLP router,
# and top-k behind a sigmoid router beside a shared expert
# ---------------------------------------------------------------------------
#
# The capacity path above gives every expert ``ceil(1.25 S / E)`` places a
# group and drops the rest: right for training under ``ep`` (static shapes,
# one all-to-all), wrong for serving, where the published model drops
# nothing, a group may be one decode slot, and the pad tokens of a prefill
# bucket would claim places.  Here tokens are sorted by their expert and the
# three expert products are grouped matrix products (``jax.lax.ragged_dot``:
# on a TPU a Mosaic kernel that visits only the groups that got rows, so an
# expert nobody chose is neither multiplied nor read).  Tokens that are not
# live (bucket padding, idle decode slots) go to no expert at all.


def router_top1(bp, y: jnp.ndarray, r_prev: jnp.ndarray, live: jnp.ndarray,
                eps: float):
    """The ZAYA1 router (arXiv:2511.17127) on tokens ``y`` [T, D].

    ``r = y Wr + gamma * r_prev`` (depth averaging: ``r_prev`` [T, R] is
    the same tokens' ``r`` of the layer below, zeros at the first layer);
    scores ``W3 gelu(W2 gelu(W1 RMSNorm(r)))``; ``p = softmax(scores)``;
    the expert is ``argmax(p + bias)`` and the gate is its own ``p``,
    unnormalised (top-1).  All in float32 at full matmul precision (the
    MXU's default would round ``r`` to bfloat16 and move near-ties).
    Returns ``(expert [T] int32, gate [T] f32, r [T, R] f32)``; a token
    that is not ``live`` gets expert ``E``, which sorts last and belongs
    to no group."""
    f32 = lambda name: bp[name].astype(jnp.float32)
    mm = lambda a, w: jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)
    r = mm(y.astype(jnp.float32), f32("router_in")) + f32("router_gamma") * r_prev
    z = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + eps)
    z = jax.nn.gelu(mm(z * f32("router_ln"), f32("router_w1")))
    z = jax.nn.gelu(mm(z, f32("router_w2")))
    p = jax.nn.softmax(mm(z, f32("router_w3")), axis=-1)
    E = p.shape[-1]
    expert = jnp.argmax(p + f32("router_bias"), axis=-1).astype(jnp.int32)
    gate_p = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
    return jnp.where(live, expert, E), gate_p, r


def stack_experts(blocks, cfg):
    """Split a stacked block tree for a layer scan: ``(rest, experts)``.
    ``experts`` holds ``we_gate`` / ``we_up`` / ``we_down`` of ALL layers as
    one ``[n_layers * E, ...]`` array each, in the compute dtype, to be
    closed over by the scan's body; ``rest`` is what the scan slices a
    layer at a time.  A grouped product wants its weights as one buffer:
    sliced by the scan, a layer's 16 experts are COPIED out of the stack
    every step (3 x 134 MB a layer at ZAYA1's widths); addressed as groups
    ``layer * E ..`` of the whole stack they are read in place."""
    from .transformer import weight

    names = ("we_gate", "we_up", "we_down")
    rest = {k: v for k, v in blocks.items() if k not in names}
    experts = {
        k: weight(blocks[k], cfg.dtype).reshape((-1,) + blocks[k].shape[2:])
        for k in names
    }
    return rest, experts


# the token-expert pairs one sorted grouped product takes at once: a
# prefill with more goes a run of tokens at a time (``_grouped_experts``),
# since each of the products holds its float32 rows whole (768 MiB at 65,536
# pairs of 3,072: a 16,384-token prefill at top-4)
PAIRS_AT_ONCE = 1 << 14


def _grouped_experts(yt, picks, gates, experts, layer, held, dt):
    """:func:`_grouped_once` over the tokens, in runs of tokens of at most
    ``PAIRS_AT_ONCE`` pairs where there are more (each run sorted and
    multiplied on its own; a token's output is the same): ``(out [T, D],
    sizes [held])``."""
    T, k = picks.shape
    runs = -(-T * k // PAIRS_AT_ONCE)
    if runs == 1 or T % runs:
        return _grouped_once(yt, picks, gates, experts, layer, held, dt)
    out, sizes = jax.lax.map(
        lambda run: _grouped_once(*run, experts, layer, held, dt),
        (yt.reshape(runs, T // runs, -1), picks.reshape(runs, T // runs, k),
         gates.reshape(runs, T // runs, k)),
    )
    return out.reshape(T, -1), sizes.sum(axis=0, dtype=sizes.dtype)


def _grouped_once(yt, picks, gates, experts, layer, held, dt):
    """The sorted grouped products both expert layers end in.  ``yt`` [T,
    D] tokens; ``picks`` [T, k] int32, each pair's expert among the
    ``held`` this program holds (``held`` itself for a pair that goes to
    no group: a token that is not live, or an expert held elsewhere);
    ``gates`` [T, k] f32.  The expert weights are the groups ``layer *
    held ..`` of ``experts``, the stack of all layers
    (:func:`stack_experts`).  Returns ``(out [T, D], sizes [held])``:
    ``sum_k gates[t, k] * Wdown(e)(silu(y Wgate(e)) * y Wup(e))`` over a
    token's pairs that have a group, exact zeros for a token with none,
    and the pairs each held expert got."""
    from .transformer import weight

    T, k = picks.shape
    flat = picks.reshape(T * k)
    with jax.named_scope("moe/sort"):
        order = jnp.argsort(flat)  # stable: ties keep token order
        sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
        rows = order if k == 1 else order // k  # the token of a sorted pair
        xs = yt[rows]
        # every other layer's groups are empty
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((experts["we_gate"].shape[0],), jnp.int32),
            sizes, (layer * held,),
        )
    with jax.named_scope("moe/experts"):
        grouped = lambda a, w: jax.lax.ragged_dot(
            a, weight(w, dt), groups, preferred_element_type=jnp.float32
        )
        hidden = jax.nn.silu(grouped(xs, experts["we_gate"])) * grouped(xs, experts["we_up"])
        ys = grouped(hidden.astype(dt), experts["we_down"])
    with jax.named_scope("moe/combine"):
        # rows past the groups (pairs routed nowhere) hold nothing defined
        keep = (flat < held)[order]
        ys = jnp.where(keep[:, None], ys * gates.reshape(T * k)[order][:, None], 0.0)
        if k == 1:  # a row a token: put each back where it came from
            out = jnp.zeros((T, yt.shape[1]), dt).at[order].set(ys.astype(dt))
        else:  # a token's pairs add up, in float32
            out = jnp.zeros((T, yt.shape[1]), jnp.float32).at[rows].add(ys).astype(dt)
    return out, sizes


def experts_top1(bp, y: jnp.ndarray, r_prev: jnp.ndarray, live: jnp.ndarray,
                 cfg, experts, layer):
    """The dropless expert layer: ``y`` [B, L, D] (post-RMSNorm) ->
    ``(out [B, L, D], r [B, L, R] f32, counts [E] int32, chosen [B, L]
    int32)``.  ``bp`` holds the layer's ``router_*``; the expert weights
    are the groups of layer ``layer`` in ``experts``, the stack of all
    layers (:func:`stack_experts`).

    ``out`` is ``p[e] * Wdown(e)(silu(y Wgate(e)) * y Wup(e))`` for every
    live token at any load, exact zeros elsewhere; ``r`` is the router's
    carry for the layer above; ``counts`` the live tokens each expert got;
    ``chosen`` each token's expert (``E`` for a token that is not live)."""
    B, L, D = y.shape
    T = B * L
    yt, live = y.reshape(T, D), live.reshape(T)
    with jax.named_scope("moe/router"):
        expert, gate_p, r = router_top1(
            bp, yt, r_prev.reshape(T, -1), live, cfg.block.norm_eps
        )
    out, sizes = _grouped_experts(
        yt, expert[:, None], gate_p[:, None], experts, layer,
        cfg.moe_experts, cfg.dtype,
    )
    return out.reshape(B, L, D), r.reshape(B, L, -1), sizes, expert.reshape(B, L)


def router_sigmoid(bp, y: jnp.ndarray, live: jnp.ndarray, k: int,
                   scale: float, bias=None):
    """The DeepSeek-V3 style router without its group limit on tokens
    ``y`` [T, D]: ``s = sigmoid(y Wr)`` over all ``E`` routed experts; the
    picks are the ``k`` largest ``s`` (the lower index on a tie), or of
    ``s + bias`` where a selection ``bias`` [E] is given, which steers the
    picks and not their weights; the weights are ``s[picks] / (sum
    s[picks] + 1e-20) * scale``.  In float32 at full matmul precision, as
    :func:`router_top1`.  Returns ``(picks [T, k] int32, weights [T, k]
    f32)``; a token that is not ``live`` gets expert ``E`` ``k`` times."""
    s = jax.nn.sigmoid(jnp.matmul(
        y.astype(jnp.float32), bp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    if bias is None:
        top, picks = jax.lax.top_k(s, k)
    else:
        _, picks = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(s, picks, axis=-1)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.where(live[:, None], picks.astype(jnp.int32), s.shape[-1]), w


def experts_topk(bp, y: jnp.ndarray, live: jnp.ndarray, cfg, experts, layer):
    """The expert layer of shared and routed experts, for the share of the
    routed ones this program holds (``cfg.block.experts_share``): ``y``
    [B, L, D] (post-RMSNorm) -> ``(out [B, L, D], counts [held] int32,
    chosen [B, L, k] int32)``.  ``bp`` holds the layer's ``router`` [D, E]
    and the shared expert's ``ws_*``; the held experts' weights are the
    groups of layer ``layer`` in ``experts`` (:func:`stack_experts`).

    Every live token is routed over ALL ``E`` experts and its ``k``
    weights are normalised over all its picks; ``out`` is the shared
    expert (every token's, counted once whoever holds what) plus the
    weighted outputs of the picks that fall on the held experts — what the
    holders of the other experts would add is left out, and the sum over
    all holders is the whole layer.  ``counts`` are the pairs each held
    expert got; ``chosen`` every token's picks in the router's numbering
    (``E`` for a token that is not live)."""
    from .transformer import swiglu

    B, L, D = y.shape
    T = B * L
    yt = y.reshape(T, D)
    held, index = cfg.experts_held, cfg.block.experts_share[0]
    with jax.named_scope("moe/router"):
        bias = (bp["expert_bias"],) if cfg.block.selection_bias else ()
        picks, w = router_sigmoid(
            bp, yt, live.reshape(T), cfg.moe_top_k, cfg.block.routed_scale,
            *bias,
        )
        local = picks - index * held
        local = jnp.where((local >= 0) & (local < held), local, held)
    out, sizes = _grouped_experts(
        yt, local, w, experts, layer, held, cfg.dtype
    )
    out = out.reshape(B, L, D)
    if cfg.block.shared_experts:
        with jax.named_scope("moe/shared"):
            out = out + swiglu(
                y, bp["ws_gate"], bp["ws_up"], bp["ws_down"], cfg.dtype
            )
    return out, sizes, picks.reshape(B, L, -1)
