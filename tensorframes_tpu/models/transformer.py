"""Decoder-only transformer LM — the framework's flagship model family.

The reference's model story is frozen-graph *scoring* of conv nets
(``/root/reference/src/main/python/tensorframes_snippets/read_image.py:108-167``);
it has no in-repo model definitions, no attention, and no training loop
(SURVEY.md §2.7).  The TPU-native build makes the modern equivalent
first-class: a decoder-only transformer whose forward/training step shards
over the standard 5-axis mesh (``parallel.mesh.training_mesh``):

* ``dp`` — batch data parallelism;
* ``ep`` — expert parallelism: ``moe_experts > 0`` swaps each block's dense
  SwiGLU for a mixture of experts (``models/moe.py``) whose expert axis is
  sharded over ``ep``; the batch also shards over ``(dp, ep)`` outside the
  expert computation, so ep costs nothing for dense configs;
* ``tp`` — Megatron-style tensor parallelism: QKV/gate/up projections are
  column-sharded ``P(None, "tp")``, output/down projections row-sharded
  ``P("tp", None)``, so each block needs exactly one all-reduce per
  sub-layer (inserted by GSPMD from the sharding constraints);
* ``sp`` — sequence/context parallelism: activations are sharded along the
  sequence axis ``P("dp", "sp", None)``; attention over the distributed
  sequence runs as ring attention (``parallel.ring``) with K/V blocks
  rotating over the ``sp`` ring via ``ppermute``;
* ``pp`` — pipeline stages (``train.py`` stacks blocks per stage and
  schedules microbatches over the ``pp`` axis).

All matmuls run in bf16 on the MXU with f32 accumulation
(``preferred_element_type``); params are kept in f32.  Sharding is expressed
as *constraints* (``with_sharding_constraint``) against the ambient mesh, so
the same code runs unsharded on one chip and GSPMD-partitioned on a pod —
constraints over axes absent from the ambient mesh are dropped.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


class QTensor(NamedTuple):
    """An int8-quantized weight: ``q`` int8 values + broadcastable f32
    ``scale`` (per output channel / embedding row — ``models/quant.py``).
    A NamedTuple, so param trees holding these remain ordinary pytrees."""

    q: jnp.ndarray
    scale: jnp.ndarray


class OutIn(NamedTuple):
    """A projection weight held turned, ``[..., out, in]``: the contraction
    axis minor, which is how the chip's dot reads a decode step's weight
    where it lies (``kv_pager.serving_params``; held ``[in, out]`` every
    layer of every step copied its slice to this layout first).  A type of
    its own, so that :func:`weight` and :func:`linear` tell the two
    orientations apart where neither a key nor a shape can (``wq`` is
    square in Mistral and Brumby)."""

    w: jnp.ndarray


def weight(w: "QTensor | OutIn | jnp.ndarray", dt) -> jnp.ndarray:
    """Weight accessor: dequantise a QTensor to ``dt`` (XLA fuses the
    int8->dt multiply into the consuming matmul's operand read), turn an
    :class:`OutIn` back to ``[in, out]``, or cast a plain array."""
    if isinstance(w, QTensor):
        return w.q.astype(dt) * w.scale.astype(dt)
    if isinstance(w, OutIn):
        return jnp.swapaxes(w.w, -1, -2).astype(dt)
    return w.astype(dt)


def linear(y: jnp.ndarray, w: "QTensor | OutIn | jnp.ndarray", dt):
    """``y @ W`` for a weight held ``[in, out]``, or as :class:`OutIn`,
    whose minor axis the product contracts where it lies."""
    if isinstance(w, OutIn):
        return jnp.einsum("...d,kd->...k", y, w.w.astype(dt))
    return y @ weight(w, dt)


def embed_lookup(emb: "QTensor | jnp.ndarray", tokens, dt) -> jnp.ndarray:
    """Token-row gather that never materialises a dequantised [V, D]
    table: int8 rows gather first, then scale by the gathered per-row
    scales."""
    if isinstance(emb, QTensor):
        return emb.q[tokens].astype(dt) * emb.scale[tokens].astype(dt)
    return emb.astype(dt)[tokens]


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The widths of latent attention (MLA, ``models/mla.py``): the
    query's and the key-value's low ranks, and a head's three sizes —
    what of a query or key is not rotated, what is, and a value."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's rescaling of the rotary frequencies (:func:`rope_table`)
    and of the attention scores (:func:`yarn_mscale`)."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """The sizes of a Mamba-2 mixer (``models/ssm.py``) that runs beside a
    block's attention on the same normed input: ``heads`` heads of
    ``head_dim`` (``d_ssm`` in all), ``groups`` groups of heads that share
    B and C of ``d_state`` each, a causal depthwise convolution of
    ``d_conv`` over x, B and C, and SSD's ``chunk`` for a prefill.  The
    gated RMSNorm after the gate normalises ``groups`` groups of
    ``d_ssm / groups``."""

    heads: int
    head_dim: int
    groups: int
    d_state: int
    d_conv: int = 4
    chunk: int = 128

    @property
    def d_ssm(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """x, B and C side by side: what the convolution mixes."""
        return self.d_ssm + 2 * self.groups * self.d_state


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Falcon-H1's named scalars (its config's ``*_multiplier`` keys), each
    applied once where that model applies it: on the embedding, on the
    attention's input and keys and on its output, on the mixer's input,
    on the five segments of its projection (z, x, B, C, dt) and on its
    output, inside the SwiGLU's gate and on its output, and on the
    logits.  A multiplier of 1 (every one, by default) traces no
    operation."""

    embedding: float = 1.0
    attention_in: float = 1.0
    key: float = 1.0
    attention_out: float = 1.0
    ssm_in: float = 1.0
    ssm_segments: Tuple[float, ...] = (1.0,) * 5
    ssm_out: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0
    head: float = 1.0


def times(x, s):
    """``x * s`` for a multiplier ``s``, and ``x`` itself where it is 1."""
    return x if s == 1.0 else x * s


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What one decoder block is made of (ROADMAP D8): the kinds of its
    two sublayers and the numbers they share.  A model with another
    block states it here, not in a further flag on
    :class:`TransformerConfig`; the default is the dense GQA + SwiGLU
    block every earlier configuration runs, op for op."""

    # "gqa": grouped-query attention over pages of K and V;
    # "cca": compressed convolutional attention (``models/cca.py``), whose
    #   decode step also needs a fixed-size per-sequence convolution state;
    # "mla": latent attention (``models/mla.py``, sizes in ``latent``):
    #   ONE pool of pages, a token's normed latent and its rotated key
    #   part side by side, which a decode step attends over as they lie;
    # "retention": power retention (``models/retention.py``): NO pages, a
    #   float32 state of fixed size a sequence a layer, with per-head
    #   RMSNorm on q and k and a gate a KV head
    attention: str = "gqa"
    # "swiglu": the dense SwiGLU (or the capacity-routed ``moe.moe_mlp``
    #   when ``moe_experts`` > 0, the training path);
    # "experts_top1": the dropless top-1 expert layer behind an MLP
    #   router (``moe.experts_top1``): ``moe_experts`` experts of width
    #   ``moe_d_ff``, router width ``router_hidden``;
    # "experts_topk": ``moe_top_k`` of ``moe_experts`` a token behind a
    #   sigmoid router, beside ``shared_experts`` that every token takes
    #   (``moe.experts_topk``), of which this program holds the share
    #   ``experts_share``
    ffn: str = "swiglu"
    norm_eps: float = 1e-6
    rotary_share: float = 1.0  # share of a head's dimensions RoPE rotates
    head_dim: Optional[int] = None  # None: d_model // n_heads
    router_hidden: int = 0
    # the first ``dense_layers`` layers take the dense SwiGLU (width
    # ``d_ff``) whatever ``ffn`` says of the rest: a run of its own in the
    # layer scan, with its own stack of parameters (``dense_blocks``)
    dense_layers: int = 0
    shared_experts: int = 0  # each of width ``moe_d_ff``
    routed_scale: float = 1.0  # on the normalised weights of the picks
    # (index, of): the routed experts are divided over ``of`` holders and
    # this one holds run ``index`` of them, ``moe_experts // of`` experts;
    # it routes over all and computes what its own give
    experts_share: Tuple[int, int] = (0, 1)
    latent: Optional[LatentSpec] = None
    yarn: Optional[Yarn] = None
    # a Mamba-2 mixer beside a "gqa" attention, on the same normed input,
    # its output added to the residual with attention's (Falcon-H1): the
    # pool then holds pages AND a state a slot
    mixer: Optional[SSMSpec] = None
    multipliers: Multipliers = Multipliers()
    # the attention of each layer, by index: "full" or "window" (empty:
    # every layer full).  A window layer's query at t sees the keys in
    # (t - window, t], and its pages are a ring in a pool of its own
    # (``kv_pager``); a full layer's see every key up to t.  Among window
    # layers a full layer takes no rotation (NoPE); window layers are rotated
    layer_types: Tuple[str, ...] = ()
    window: int = 0
    # per-head RMSNorm of q and k before rotation (gains ``q_norm``,
    # ``k_norm`` [Dh]), on a "gqa" block
    qk_norm: bool = False
    # attention's output times sigmoid(RMSNorm(x) W_g), elementwise, before
    # W_o (``w_attn_gate`` [D, h * Dh])
    attn_gate: bool = False
    # RMSNorm on each sublayer's output before it joins the residual
    # (``ln_post_attn``, ``ln_post_mlp``)
    sandwich: bool = False
    # a per-expert bias (``expert_bias`` [E]) added to the sigmoid scores
    # of an "experts_topk" router: it steers the picks, not their weights
    selection_bias: bool = False

    def __post_init__(self):
        if self.attention not in ("gqa", "cca", "mla", "retention"):
            raise ValueError(
                f"attention {self.attention!r}: 'gqa', 'cca', 'mla' or "
                f"'retention'"
            )
        if self.ffn not in ("swiglu", "experts_top1", "experts_topk"):
            raise ValueError(
                f"ffn {self.ffn!r}: 'swiglu', 'experts_top1' or 'experts_topk'"
            )
        if (self.attention == "mla") != (self.latent is not None):
            raise ValueError("attention 'mla' and `latent` go together")
        if self.mixer is not None and self.attention != "gqa":
            raise ValueError("a mixer runs beside attention 'gqa' only")
        index, of = self.experts_share
        if not 0 <= index < of:
            raise ValueError(f"experts_share {self.experts_share}: (index, of)")
        if set(self.layer_types) - {"full", "window"}:
            raise ValueError(f"layer_types {self.layer_types}: 'full' or 'window'")
        if ("window" in self.layer_types) != (self.window > 0):
            raise ValueError("window layers and a window size go together")
        if self.layer_types and not self.window:
            raise ValueError("a pattern of layer_types holds window layers")
        if (self.layer_types or self.qk_norm) and (
            self.attention != "gqa" or self.mixer is not None
        ):
            raise ValueError(
                "layer_types and qk_norm are of a 'gqa' block without a mixer"
            )
        if self.selection_bias and self.ffn != "experts_topk":
            raise ValueError("a selection bias steers an 'experts_topk' router")

    @property
    def stateless(self) -> bool:
        """The dense block: pages are its only per-sequence state and it
        routes nothing, so its executables take and return no more."""
        return (
            self.attention == "gqa" and self.ffn == "swiglu"
            and self.mixer is None and self.multipliers == Multipliers()
            and not (self.layer_types or self.qk_norm or self.attn_gate
                     or self.sandwich)
        )

    def kind_of(self, layer: int) -> Optional[str]:
        """Layer ``layer``'s attention, "full" or "window"; None where the
        spec states no pattern."""
        return self.layer_types[layer] if self.layer_types else None

    @property
    def routes(self) -> bool:
        """Whether the layers after the leading dense ones send tokens to
        experts through a router whose picks the executables report."""
        return self.ffn in ("experts_top1", "experts_topk")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: int = 8  # < n_heads => grouped-query attention
    d_ff: int = 2048  # SwiGLU hidden size
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    # "auto" (length-dispatched full/flash) | "full" | "flash" (Pallas,
    # sp=1) | "ring" (sp-distributed) | "ring_flash" (ring with the Pallas
    # local step)
    attn_impl: str = "full"
    # "auto" picks flash at L >= this (the measured v5e crossover vs the
    # fused XLA path, docs/PERF.md); full below it or with custom positions
    flash_min_len: int = 8192
    remat: bool = False  # legacy alias for remat_policy="full"
    # rematerialisation policy for the decoder blocks (VERDICT r3 weak #1 —
    # all-or-nothing remat left a known train-step win on the table):
    #   "none" — save everything (fastest when it fits);
    #   "full" — checkpoint whole blocks, recompute all activations in the
    #            backward (O(sqrt) live memory, ~1/3 extra FLOPs);
    #   "dots" — selective: save matmul/projection outputs, recompute
    #            cheap elementwise + the [L, L]-shaped attention einsums
    #            (jax.checkpoint_policies.dots_with_no_batch_dims_saveable);
    #   "attn" — selective the other way round: save every block activation
    #            EXCEPT the attention core (scores -> f32 softmax -> @v),
    #            which recomputes from the saved q/k/v in the backward.
    #            The [B, h, L, L] f32 probabilities — the tensors that make
    #            "none" OOM — never survive the forward, while the matmul
    #            backward runs entirely from saved activations;
    #   "selective" — block-level checkpoint that saves ONLY the named
    #            activations (norm outputs, post-RoPE q/k/v, attention
    #            output, gate*up) — ~350MB/layer at the bench shapes
    #            instead of "attn"'s ~900MB — and recomputes the rest.
    #            The backward redoes two FFN matmuls + the attention core
    #            per block instead of the whole forward (docs/PERF.md has
    #            the measured policy x batch matrix on the v5e).
    remat_policy: str = "none"
    # mixture of experts (models/moe.py): > 0 replaces every block's dense
    # SwiGLU with moe_experts expert FFNs, sharded over the mesh's "ep" axis
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01  # load-balance loss weight (Switch)
    moe_d_ff: Optional[int] = None  # per-expert hidden size (default d_ff)
    # chunked cross-entropy (loss_fn): > 0 computes the loss over length-
    # chunks of this size so the [B, L, V] f32 logits (plus their softmax
    # intermediates) never materialise — the logits of one [B, chunk]
    # slice exist at a time, recomputed in the backward (jax.checkpoint).
    # 0 = classic full-logits loss.  Must divide the training L.
    ce_chunk: int = 0
    block: BlockSpec = BlockSpec()

    def __post_init__(self):
        if self.remat_policy not in (
            "none", "full", "dots", "attn", "selective",
        ):
            raise ValueError(
                f"remat_policy {self.remat_policy!r}: use 'none', 'full', "
                f"'dots', 'attn' or 'selective'"
            )
        if self.block.head_dim is None and self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        if self.block.ffn == "experts_top1" and not (
            self.moe_experts and self.block.router_hidden
        ):
            raise ValueError(
                "ffn 'experts_top1' needs moe_experts and block.router_hidden"
            )
        if self.block.ffn == "experts_topk" and (
            not self.moe_experts
            or self.moe_experts % self.block.experts_share[1]
        ):
            raise ValueError(
                "ffn 'experts_topk' needs moe_experts, a multiple of the "
                "holders in block.experts_share"
            )
        if not 0 <= self.block.dense_layers <= self.n_layers:
            raise ValueError("block.dense_layers must lie within n_layers")
        if self.block.layer_types and len(self.block.layer_types) != self.n_layers:
            raise ValueError("block.layer_types names every layer")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.moe_experts and self.moe_top_k > self.moe_experts:
            raise ValueError(
                f"moe_top_k {self.moe_top_k} > moe_experts {self.moe_experts}"
            )

    @property
    def head_dim(self) -> int:
        if self.block.latent is not None:
            # a query head, a value head and a page's row are three sizes
            raise ValueError(
                "a latent block has no one head size: see cfg.block.latent"
            )
        return self.block.head_dim or self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        """The routed experts whose weights this program holds."""
        return self.moe_experts // self.block.experts_share[1]


def shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Constrain ``x``'s sharding against the ambient mesh.

    Axes named in ``spec`` but absent from the ambient mesh are dropped, so
    model code states its ideal layout once and degrades gracefully on
    smaller meshes (or none).  Entries may be ``None``, an axis name, or a
    tuple of axis names.
    """
    if len(spec) > x.ndim:
        raise ValueError(
            f"shard: {len(spec)} spec entries for a rank-{x.ndim} array"
        )
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    # axes already bound as Manual (we are inside a shard_map over them,
    # e.g. the pipeline stage body) cannot be constrained again — drop them
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    names = {
        n
        for n in mesh.axis_names
        if types[n] != jax.sharding.AxisType.Manual
    }

    def keep(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = []
        split = 1
        for a in axes:
            # an axis also drops when the dim cannot split evenly over it
            # (e.g. ragged sequence lengths under an sp mesh): constraints
            # degrade to a coarser sharding instead of erroring
            if a in names and dim % (split * mesh.shape[a]) == 0:
                kept.append(a)
                split *= mesh.shape[a]
        if not kept:
            return None
        return tuple(kept) if isinstance(entry, (tuple, list)) else kept[0]

    return jax.lax.with_sharding_constraint(
        x, P(*(keep(e, d) for e, d in zip(spec, x.shape)))
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Parameter pytree.  Layout (per block): fused qkv? no — separate
    wq/wk/wv so tp sharding of GQA kv heads stays independent."""
    d, h, kvh, dh, f = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
    )
    pd = cfg.param_dtype
    k_embed, k_blocks, k_head = jax.random.split(rng, 3)

    def dense(key, fan_in, shape):
        return (
            jax.random.normal(key, shape, pd) * np.sqrt(1.0 / fan_in)
        ).astype(pd)

    def block_params(key) -> Params:
        ks = jax.random.split(key, 8)
        bp = {
            "ln1": jnp.ones((d,), pd),
            "wq": dense(ks[0], d, (d, h * dh)),
            "wk": dense(ks[1], d, (d, kvh * dh)),
            "wv": dense(ks[2], d, (d, kvh * dh)),
            "wo": dense(ks[3], h * dh, (h * dh, d)),
            "ln2": jnp.ones((d,), pd),
        }
        if cfg.moe_experts:
            E, fe = cfg.moe_experts, cfg.moe_d_ff or f
            ek = jax.random.split(ks[4], 3 * E)

            def experts(keys, fan_in, shape):
                return jnp.stack([dense(kk, fan_in, shape) for kk in keys])

            bp["router"] = dense(ks[7], d, (d, E))
            bp["we_gate"] = experts(ek[:E], d, (d, fe))
            bp["we_up"] = experts(ek[E : 2 * E], d, (d, fe))
            bp["we_down"] = experts(ek[2 * E :], fe, (fe, d))
        else:
            bp["w_gate"] = dense(ks[4], d, (d, f))
            bp["w_up"] = dense(ks[5], d, (d, f))
            bp["w_down"] = dense(ks[6], f, (f, d))
        return bp

    # blocks are STACKED on a lead [n_layers, ...] axis: scanned in apply()
    # (one trace for all layers) and shardable over "pp" by the pipeline
    # schedule in train.py
    blocks = jax.vmap(block_params)(jax.random.split(k_blocks, cfg.n_layers))
    return {
        "embed": dense(k_embed, d, (cfg.vocab_size, d)),
        "blocks": blocks,
        "ln_f": jnp.ones((d,), pd),
        "lm_head": dense(k_head, d, (d, cfg.vocab_size)),
    }


# Canonical per-param layout for one decoder block, WITHOUT the stacked
# [n_layers, ...] lead axis.  Shared by shard_params and the pipeline's
# stage regrouping (train._stage_params), so pp restacking preserves the
# tp/ep layout instead of dropping it.
_BLOCK_SPECS = {
    "ln1": (None,),
    "wq": (None, "tp"),
    "wk": (None, "tp"),
    "wv": (None, "tp"),
    "wo": ("tp", None),
    "ln2": (None,),
    "w_gate": (None, "tp"),
    "w_up": (None, "tp"),
    "w_down": ("tp", None),
    # MoE (models/moe.py): expert axis over ep, expert FFNs tp-sharded
    # like the dense ones; the router is small and replicated
    "router": (None, None),
    "we_gate": ("ep", None, "tp"),
    "we_up": ("ep", None, "tp"),
    "we_down": ("ep", "tp", None),
}


def block_spec(name: str, lead_dims: int = 1) -> tuple:
    """Sharding spec for a stacked block param (``lead_dims`` unsharded
    lead axes — 1 for the [n_layers] stack, 2 for [stages, lps])."""
    return (None,) * lead_dims + _BLOCK_SPECS[name]


def shard_params(params: Params) -> Params:
    """Apply the canonical tp/ep layout constraints to a param pytree
    (no-op without an ambient mesh).  The pipeline layer adds the ``pp``
    lead-axis sharding on top (``train.py``).  Quantized (QTensor) leaves
    pass through unsharded — they are a single-chip/replicated inference
    artifact (``models/quant.py``)."""

    def s_(v, *spec):
        return v if isinstance(v, QTensor) else shard(v, *spec)

    p = dict(params)
    p["embed"] = s_(params["embed"], "tp", None)
    p["lm_head"] = s_(params["lm_head"], None, "tp")
    p["blocks"] = {
        k: s_(v, *block_spec(k)) for k, v in params["blocks"].items()
    }
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _saved(x: jnp.ndarray) -> jnp.ndarray:
    """Tag an activation as saveable under remat_policy="selective"
    (``jax.checkpoint_policies.save_only_these_names``); a no-op tag under
    every other policy."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, "tfs_saved")


def _rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype)


def rope_table(theta: float, dims: int, yarn: Optional[Yarn] = None):
    """The ``dims // 2`` rotary frequencies as a float32 table: ``f_i =
    theta ** (-2i / dims)``, and under YaRN (arXiv:2309.00071, as
    DeepSeek-V2 applies it) blended with ``f_i / factor`` by a ramp over
    the dimensions whose wavelength the original context holds between
    ``beta_fast`` and ``beta_slow`` times: the fast ones keep their
    frequency, the slow ones are interpolated."""
    half = dims // 2
    f = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    if yarn is not None and yarn.factor != 1:

        def corr(turns):  # the dimension that makes ``turns`` turns
            return dims * np.log(
                yarn.original_max / (2 * np.pi * turns)
            ) / (2 * np.log(theta))

        low = max(int(np.floor(corr(yarn.beta_fast))), 0)
        high = min(int(np.ceil(corr(yarn.beta_slow))), dims - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
        f = (1 - ramp) * f + ramp * f / yarn.factor
    return f.astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def _rope(
    x: jnp.ndarray, positions: jnp.ndarray, freqs, share: float = 1.0
):
    """Rotary embedding.  x: [B, L, H, Dh]; positions: [B, L] (absolute).
    ``freqs`` is the table of ``Dh // 2`` frequencies
    (:func:`rope_table`), or the base ``theta`` of the plain one.
    ``share`` < 1 rotates the first ``share * Dh`` dimensions of each
    head and passes the rest through (partial rotary)."""
    if share < 1.0:
        rot = int(x.shape[-1] * share)
        return jnp.concatenate(
            [_rope(x[..., :rot], positions, freqs), x[..., rot:]], -1
        )
    dh = x.shape[-1]
    if np.ndim(freqs) == 0:
        freqs = freqs ** (
            -jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2)
        )
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, L, Dh/2]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def head(params: Params, x: jnp.ndarray, cfg, lead: str = "bl"):
    """The output head on final-norm hidden states, f32 logits.  A param
    tree without ``lm_head`` is a tied model: the head reads ``embed``
    ``[V, D]`` as it lies, contracting its second axis, and no transposed
    copy of it is ever held."""
    if "lm_head" in params:
        w, eq = params["lm_head"], f"{lead}d,dv->{lead}v"
    else:
        w, eq = params["embed"], f"{lead}d,vd->{lead}v"
    return times(
        jnp.einsum(
            eq, x, weight(w, cfg.dtype), preferred_element_type=jnp.float32
        ),
        cfg.block.multipliers.head,
    )


# attention numerics live in parallel.ring (full_attention is the shared
# non-ring kernel; ring_attention the sp-distributed one)


def _block(
    bp: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: TransformerConfig,
    kv=None,
    segments=None,
):
    """One decoder block.  x: [B, L, D] (L may be the sp-local chunk when
    ring attention is on — positions carry the global offsets).

    ``kv``: optional ``(cache_k, cache_v, index)`` for incremental
    decoding — caches are [B, S, kvh, Dh]; this chunk's (post-RoPE,
    pre-GQA-repeat) k/v are written at ``index`` and attention runs over
    the whole cache (slots past the written frontier carry positions
    later than every query, so the causal mask hides them — no extra
    validity mask needed).

    Returns ``(x', aux)`` — ``aux`` is the block's MoE load-balance loss
    (f32 scalar, 0 for dense blocks) — or ``(x', (ck, cv), aux)`` when
    caching."""
    x, cache = _attn_residual(bp, x, positions, cfg, kv, segments)
    # -- MLP: dense SwiGLU or mixture of experts ----------------------------
    x, aux = _mlp_residual(bp, x, cfg, segments)
    if kv is not None:
        return x, cache, aux
    return x, aux


def _attn_qkv(bp, x, positions, cfg, rotate=True):
    """The projection half of attention shared by every cache layout:
    rms_norm -> q/k/v projections -> (per-head q/k RMSNorm) -> RoPE ->
    layout shards.  Returns ``(q [B, L, h, Dh], k [B, L, kvh, Dh], v [B,
    L, kvh, Dh])``.  Split out (round 22) so the paged KV cache
    (``models/kv_pager.py``) runs the EXACT ops of the contiguous path —
    bit-identity between the two cache layouts is by construction, not by
    parallel maintenance.  ``rotate`` False (static) leaves q and k
    unrotated: a full layer of a stack with ``layer_types``."""
    B, L, D = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    mult = cfg.block.multipliers
    y = _saved(_rms_norm(x, bp["ln1"], cfg.block.norm_eps))
    y = times(y, mult.attention_in)
    q = linear(y, bp["wq"], dt).reshape(B, L, h, dh)
    k = times(linear(y, bp["wk"], dt).reshape(B, L, kvh, dh), mult.key)
    v = linear(y, bp["wv"], dt).reshape(B, L, kvh, dh)
    if cfg.block.qk_norm:
        q = _rms_norm(q, bp["q_norm"], cfg.block.norm_eps)
        k = _rms_norm(k, bp["k_norm"], cfg.block.norm_eps)
    if rotate:
        q = _rope(q, positions, cfg.rope_theta, cfg.block.rotary_share)
        k = _rope(k, positions, cfg.rope_theta, cfg.block.rotary_share)
    q = _saved(shard(q, ("dp", "ep"), "sp", "tp", None))
    k = _saved(shard(k, ("dp", "ep"), "sp", "tp", None))
    v = _saved(shard(v, ("dp", "ep"), "sp", "tp", None))
    return q, k, v


def attn_gate(bp, x, cfg):
    """The output gate of a spec with ``attn_gate``: ``sigmoid(RMSNorm(x)
    W_g)`` [B, L, h * Dh] in the compute dtype, from the block's input
    ``x`` normed as :func:`_attn_qkv` norms it (the same operations, which
    XLA computes once)."""
    y = _rms_norm(x, bp["ln1"], cfg.block.norm_eps)
    return jax.nn.sigmoid(linear(y, bp["w_attn_gate"], cfg.dtype))


@jax.named_scope("mlp")
def _mlp_residual(bp, x, cfg, segments=None):
    """The MLP half of a block: x -> x + FF(rms_norm(x)).  Returns
    ``(x', aux)`` — aux is the MoE load-balance loss (0 for dense).
    Split out of ``_block`` (round 22) so the paged decode block
    (``models/kv_pager.py``) composes the same halves in the same
    order."""
    y = _saved(_rms_norm(x, bp["ln2"], cfg.block.norm_eps))
    if cfg.moe_experts and cfg.block.ffn == "swiglu":
        from .moe import moe_mlp

        ff_out, aux = moe_mlp(bp, y, cfg, segments)
        x = x + ff_out
    else:
        mult = cfg.block.multipliers
        ff_out = swiglu(
            y, bp["w_gate"], bp["w_up"], bp["w_down"], cfg.dtype,
            (mult.mlp_gate, mult.mlp_down),
        )
        if cfg.block.sandwich:
            ff_out = _rms_norm(ff_out, bp["ln_post_mlp"], cfg.block.norm_eps)
        x = x + ff_out
        aux = jnp.zeros((), jnp.float32)
    return x, aux


def swiglu(y, w_gate, w_up, w_down, dt, scales=(1.0, 1.0)):
    """``W_down(silu(y W_gate) * y W_up)``: the dense feed-forward, and an
    expert layer's shared expert.  ``scales``: multipliers inside the
    gate's silu and on the output (Falcon-H1's ``mlp_multipliers``)."""
    gate = jax.nn.silu(times(y @ weight(w_gate, dt), scales[0]))
    up = y @ weight(w_up, dt)
    ff = _saved(shard(gate * up, ("dp", "ep"), "sp", "tp"))
    return times(
        shard(ff @ weight(w_down, dt), ("dp", "ep"), "sp", None), scales[1]
    )


@jax.named_scope("attention")
def _attn_residual(bp, x, positions, cfg, kv=None, segments=None):
    """The attention half of a block: x -> x + Wo(attn(...)).  Returns
    ``(x', cache)`` (cache None outside decode).  Split out of ``_block``
    so diagnostics (``moe.layer_routing_stats``) can reproduce the EXACT
    activations the MLP half routes."""
    B, L, D = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    q, k, v = _attn_qkv(bp, x, positions, cfg)
    if kv is not None:
        ck, cv, idx = kv
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), idx, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), idx, 1)
        att = _cache_attention(q, ck.astype(dt), cv.astype(dt), positions)
    elif cfg.attn_impl in ("ring", "ring_flash"):
        from ..parallel.ring import ring_attention

        # GQA kv heads stay grouped: the ring rotates kv-width blocks
        # (h/kvh x less ICI traffic) and widens per fold step locally
        att = ring_attention(
            q, k, v, causal=True,
            impl="flash" if cfg.attn_impl == "ring_flash" else "xla",
        )
    elif cfg.attn_impl == "flash":
        # Pallas online-softmax kernel (O(L) HBM traffic); row-major causal
        # positions — the sp == 1 operating point (parallel/flash.py).
        # GQA k/v pass at kv width: the kernel's index maps share blocks
        from ..parallel.flash import flash_attention

        att = flash_attention(q, k, v, True)
    else:
        from ..parallel.ring import full_attention

        if kvh != h:
            k = jnp.repeat(k, h // kvh, axis=2)
            v = jnp.repeat(v, h // kvh, axis=2)

        def attn_core(q_, k_, v_):
            return full_attention(
                q_, k_, v_, True, positions, positions, segments, segments
            )

        if cfg.remat_policy == "attn":
            # recompute scores/softmax from the saved q/k/v in the
            # backward; the f32 [B, h, L, L] probabilities never persist
            attn_core = jax.checkpoint(attn_core)
        att = _saved(attn_core(q, k, v))
    att = att.reshape(B, L, h * dh)
    x = x + shard(att @ weight(bp["wo"], dt), ("dp", "ep"), "sp", None)
    return x, ((ck, cv) if kv is not None else None)


def _cache_attention(q, ck, cv, positions_q, window=0, k_positions=None):
    """Attention over a KV cache with GROUPED kv heads: q [B, L, h, Dh],
    ck/cv [B, S, kvh, Dh].  The h/kvh query groups index the shared kv
    head directly — the cache is never materialised h-wide (decode reads
    scale with n_kv_heads, the point of GQA).  Numerics mirror
    ``full_attention`` (f32 softmax, f32-accumulated matmuls); unwritten
    cache slots are hidden by the causal mask (their arange positions
    exceed every query position).  ``window`` > 0 (static) also hides the
    keys at or before ``t - window`` from a query at ``t``;
    ``k_positions`` [B, S] gives each row's keys their positions where
    they are not ``arange(S)`` (a window layer's ring of pages)."""
    B, L, h, dh = q.shape
    S, kvh = ck.shape[1], ck.shape[2]
    g = h // kvh
    scale = np.float32(1.0 / np.sqrt(dh))
    qg = q.reshape(B, L, kvh, g, dh)
    s = jnp.einsum(
        "blkgd,bskd->bkgls", qg, ck, preferred_element_type=jnp.float32
    ) * scale
    k_pos = jnp.arange(S, dtype=jnp.int32)
    q_pos = positions_q[:, None, None, :, None]
    if k_positions is None:
        k_pos = k_pos[None, None, None, None, :]
    else:
        k_pos = k_positions[:, None, None, None, :]
    mask = q_pos >= k_pos
    if window:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    att = jnp.einsum(
        "bkgls,bskd->blkgd", p, cv, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return att.reshape(B, L, h, dh)


def apply_blocks(
    blocks: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: TransformerConfig,
    segments=None,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Scan the stacked block params over x — one trace for all layers.

    Returns ``(x, aux)``: aux is the summed per-layer MoE load-balance
    loss (f32 scalar, 0 for dense models) — the ``blocks_runner``
    contract shared with ``train.pipelined_blocks``."""
    body = _block
    policy = cfg.remat_policy
    if policy == "none" and cfg.remat:
        policy = "full"  # legacy flag
    if policy == "full":
        body = jax.checkpoint(body, static_argnums=(3,))
    elif policy == "dots":
        body = jax.checkpoint(
            body,
            static_argnums=(3,),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif policy == "selective":
        body = jax.checkpoint(
            body,
            static_argnums=(3,),
            policy=jax.checkpoint_policies.save_only_these_names(
                "tfs_saved"
            ),
        )

    def step(carry, bp):
        x, aux = carry
        x, a = body(bp, x, positions, cfg, None, segments)
        return (x, aux + a), None

    (out, aux), _ = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.float32)), blocks
    )
    return out, aux


def apply(
    params: Params,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    positions: Optional[jnp.ndarray] = None,
    blocks_runner=None,
    return_hidden: bool = False,
    return_aux: bool = False,
    segment_ids: Optional[jnp.ndarray] = None,
) -> "jnp.ndarray | tuple[jnp.ndarray, ...]":
    """tokens [B, L] int32 -> logits [B, L, V] (f32).

    ``blocks_runner(blocks, x, positions, cfg, segments=None) -> (x,
    aux)`` overrides how the decoder stack runs (default sequential
    ``apply_blocks``; the training layer passes the GPipe pipeline,
    ``train.pipelined_blocks``).
    ``return_hidden=True`` also returns the final-norm hidden states
    [B, L, D] (the embedding surface for scoring programs);
    ``return_aux=True`` appends the MoE load-balance aux loss (f32
    scalar, 0 for dense models).  Extras are appended in
    (hidden, aux) order.

    ``segment_ids`` [B, L] enables packed-sequence training
    (``data.pack_examples``): attention stays within each segment (id 0 =
    padding); pass the matching restart ``positions``.  Packed batches
    require the full-attention path (the Pallas/ring kernels mask by
    row-major chunk offsets)."""
    B, L = tokens.shape
    if segment_ids is not None and cfg.attn_impl in (
        "flash", "ring", "ring_flash",
    ):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} cannot honour segment_ids "
            f"(packed sequences need the explicit mask); use "
            f"attn_impl='full' or 'auto'"
        )
    if segment_ids is not None and positions is None:
        raise ValueError(
            "segment_ids without restart positions: RoPE would rotate "
            "later segments from a continuous arange and logits would "
            "silently differ from the per-example forward — pass the "
            "positions from data.pack_examples/lm_split_packed"
        )
    if cfg.attn_impl == "auto":
        # kernel choice by mesh + length (VERDICT r2 weak #2).  Under an
        # ambient mesh with a real sp axis the sequence arrives sharded, so
        # attention must be the ring (with the Pallas local step when the
        # per-device chunk tiles and is long enough to win).  Unsharded:
        # below the crossover the fused XLA path wins; at long L flash's
        # O(L) HBM traffic does.  Custom positions force the XLA paths
        # (the Pallas kernels mask with row-major arange).
        mesh = jax.sharding.get_abstract_mesh()
        sp = mesh.shape["sp"] if "sp" in mesh.axis_names else 1
        if sp > 1:
            from ..parallel.flash import chunk_supported

            if positions is not None or segment_ids is not None or L % sp:
                # ring masking derives global offsets from chunk indices
                # (row-major) and its shard_map needs L divisible by sp;
                # custom positions / ragged lengths take the explicit
                # GSPMD-sharded path — correct, if chattier
                resolved = "full"
            elif L >= cfg.flash_min_len and chunk_supported(L // sp):
                resolved = "ring_flash"
            else:
                resolved = "ring"
        else:
            use_flash = (
                positions is None
                and segment_ids is None
                and L >= cfg.flash_min_len
            )
            resolved = "flash" if use_flash else "full"
        cfg = dataclasses.replace(cfg, attn_impl=resolved)
    if positions is not None and cfg.attn_impl in (
        "flash",
        "ring",
        "ring_flash",
    ):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} masks with row-major positions "
            f"derived from chunk offsets and cannot honour custom "
            f"`positions` (tokens would attend across position resets); "
            f"pass positions=None or use attn_impl='full'/'auto'"
        )
    if cfg.remat_policy == "attn" and cfg.attn_impl != "full":
        raise ValueError(
            f"remat_policy='attn' checkpoints the full-attention core and "
            f"has no effect under attn_impl={cfg.attn_impl!r} (flash/ring "
            f"never materialise the [L, L] probabilities in the first "
            f"place) — use remat_policy='none'/'full'/'selective' there."
        )
    if not cfg.block.stateless:
        raise NotImplementedError(
            f"block {cfg.block.attention}/{cfg.block.ffn} runs on the paged "
            f"serving path (models/kv_pager.py) only: the whole-batch forward "
            f"and training carry neither its convolution state nor its "
            f"router's layer-to-layer carry"
        )
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    if blocks_runner is None:
        blocks_runner = apply_blocks
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    x = shard(x, ("dp", "ep"), "sp", None)
    x, aux = blocks_runner(params["blocks"], x, positions, cfg, segment_ids)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["ln_f"], cfg.block.norm_eps)
        logits = head(params, x, cfg)
    logits = shard(logits, ("dp", "ep"), "sp", "tp")
    out = (logits,)
    if return_hidden:
        out += (x,)
    if return_aux:
        out += (aux,)
    return out if len(out) > 1 else logits


def nll_sum_and_count(
    logits: jnp.ndarray, targets: jnp.ndarray
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Summed masked NLL + valid-target count (-1 = ignore) — the single
    home of the masking numerics shared by :func:`cross_entropy`, the
    chunked loss, and the 1F1B head (sums combine exactly across chunks
    and microbatches; divide once, globally)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = targets >= 0
    safe = jnp.where(valid, targets, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid), jnp.sum(valid)


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy over valid targets (-1 = ignore)."""
    s, c = nll_sum_and_count(logits, targets)
    return s / jnp.maximum(c, 1)


def cross_entropy_chunked(
    hidden: jnp.ndarray,
    lm_head: "QTensor | jnp.ndarray",
    targets: jnp.ndarray,
    chunk: int,
    dtype,
) -> jnp.ndarray:
    """``cross_entropy(hidden @ lm_head, targets)`` without ever holding
    the full [B, L, V] f32 logits: a ``lax.scan`` over length-chunks
    computes one [B, chunk, V] logits slice at a time, and
    ``jax.checkpoint`` on the chunk body recomputes the slice in the
    backward instead of saving it.  Row-wise softmax makes this exactly
    the un-chunked loss (same f32 numerics, same valid-mask mean)."""
    B, L, D = hidden.shape
    if L % chunk:
        raise ValueError(
            f"ce_chunk {chunk} must divide the sequence length {L}"
        )
    n = L // chunk
    w = weight(lm_head, dtype)
    hs = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        h, t = xs
        logits = jnp.einsum(
            "bcd,dv->bcv", h, w, preferred_element_type=jnp.float32
        )
        ns, nc = nll_sum_and_count(logits, t)
        s, c = carry
        return (
            s + ns.astype(jnp.float32),
            c + nc.astype(jnp.int32),
        ), None

    (s, c), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hs, ts),
    )
    return s / jnp.maximum(c, 1)


def loss_fn(
    params: Params,
    tokens: jnp.ndarray,
    targets: jnp.ndarray,
    cfg: TransformerConfig,
    blocks_runner=None,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (+ weighted MoE load-balance aux when
    the config is sparse).  targets [B, L] int32 (-1 = ignore); pass
    ``positions``/``segment_ids`` from ``data.lm_split_packed`` for
    packed batches (cross-segment targets arrive pre-masked as -1).

    With ``cfg.ce_chunk > 0`` the loss is computed chunk-wise from the
    final hidden states (the un-chunked logits are dead code and XLA
    eliminates them) — identical numerics, O(L/chunk) less live memory."""
    if cfg.ce_chunk:
        _, hidden, aux = apply(
            params, tokens, cfg, positions=positions,
            blocks_runner=blocks_runner, return_hidden=True,
            return_aux=True, segment_ids=segment_ids,
        )
        loss = cross_entropy_chunked(
            hidden, params["lm_head"], targets, cfg.ce_chunk, cfg.dtype
        )
    else:
        logits, aux = apply(
            params, tokens, cfg, positions=positions,
            blocks_runner=blocks_runner, return_aux=True,
            segment_ids=segment_ids,
        )
        loss = cross_entropy(logits, targets)
    if cfg.moe_experts:
        loss = loss + jnp.float32(cfg.moe_aux_coef) * aux
    return loss
