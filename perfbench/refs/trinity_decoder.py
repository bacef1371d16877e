"""Plain reference for the Trinity-Large-Preview configuration
(`trinity_large_l5_ep8`, HF `afmoe`): window and full attention layers in one
stack, each with per-head RMSNorm on q and k and a sigmoid output gate,
sandwich norms around both sublayers, leading dense SwiGLU layers, then
expert layers of a shared expert beside `num_experts_per_tok` routed ones
behind a sigmoid router whose picks a per-expert bias steers; the embedding
scaled by sqrt(hidden) (`mup_enabled`); an untied head.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one whole sequence at a time: no
cache, no pages, no kernel, no sorting (every held expert is applied to every
token under a mask), no batching.  Attention is computed a block of
`QUERY_BLOCK` queries at a time against every key, under the causal mask and,
on a window layer, the window's; a layer's weights become float32 where they
are used, so that a 15,104-token sequence at the published widths fits beside
the bfloat16 weights.  Imports nothing of the program.  With `x` the residual
and `u = RMSNorm(x)` (eps `rms_norm_eps`, statistics in float32):

  input       x = sqrt(hidden) E[token]
  attention   q = RMSNorm_head(u W_q) (48 heads of 128), k = RMSNorm_head(u W_k)
              (8 KV heads), v = u W_v;  on a window layer q and k are rotated
              (RoPE, theta `rope_theta`, half-split pairing) and a query at t
              sees the keys in (t - window, t]; a full layer rotates nothing
              and sees every key <= t;  o = softmax(q k^T / sqrt(128)) v (GQA)
              a = W_o (o * sigmoid(u W_g));  x <- x + RMSNorm_post_attn(a)
  dense       y = RMSNorm_pre_mlp(x);  x <- x + RMSNorm_post_mlp(W_down(silu(y W_gate) * y W_up))
  experts     s = sigmoid(y W_r) over ALL routed experts; picks = the k largest
              s + b (b the selection bias); w = s[picks] / (sum s[picks] +
              1e-20) * route_scale;  x <- x + RMSNorm_post_mlp(E_shared(y) +
              sum over the picks HELD here of w E_e(y))
  output      logits = W_head RMSNorm(x)

The share.  `expert_share` `{index, of}`: `num_experts` experts are held here,
global ids `index * num_experts ..`, of `of * num_experts` the router scores;
routing and weights are over all of them, the picks on absent experts add
nothing, here and in the program alike.  `{0, 1}` is the uncut layer.

The cut.  `layers` lists the published layers this configuration holds; their
kinds are the published `layer_types` at those indices, and the leading dense
ones are those below `num_dense_layers`.

Variants (`variant`, for the mutation checks of `correct`): "past_window"
(window layers attend to every earlier key), "rotate_full" (the full layers
rotated too), "no_gate", "no_bias" (picks from s alone) and "bf16_attention"
(scores, softmax and the value product in bfloat16).  Precision "int8" is the
control (`fake_int8`).

The weight tree is the layout the program consumes: `embed`, `lm_head`,
`ln_f`, `dense_blocks` and `blocks` stacked on a leading layer axis; the
benchmark makes it on the device from the seed and hands the same arrays to
both sides.
"""

import functools
import math

import numpy as np

QUERY_BLOCK = 256  # queries a block of attention holds, against every key
HEAD_BLOCKS = 8  # the head's columns, in float32 this many at a time
QK_GAIN = 1.6  # the q and k norms' gains: see make_weights
BIAS_SCALE = 0.02  # the selection bias: see make_weights
VARIANTS = ("past_window", "rotate_full", "no_gate", "no_bias", "bf16_attention")


def kinds(cfg):
    """The held layers' attention, "window" or "full", in layer order."""
    return tuple("window" if cfg["layer_types"][i] == "sliding_attention" else "full"
                 for i in cfg["layers"])


def dims(cfg):
    """Short names for the sizes of the config dict."""
    share = cfg["expert_share"]
    h, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return dict(d=cfg["hidden_size"], h=h, kvh=kvh, dh=dh, hd=h * dh, kd=kvh * dh,
                fd=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
                fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
                e=cfg["num_experts"], e_all=cfg["num_experts"] * share["of"],
                first=cfg["num_experts"] * share["index"], k=cfg["num_experts_per_tok"],
                n=len(cfg["layers"]), n0=sum(1 for i in cfg["layers"] if i < cfg["num_dense_layers"]),
                v=cfg["vocab_size"], w=cfg["sliding_window"])


def attention_shapes(s):
    """name -> (kind, shape, fan_in): see `make_weights` for the kinds."""
    return {
        "ln1": ("gain", (s["d"],), None), "ln_post_attn": ("gain", (s["d"],), None),
        "ln2": ("gain", (s["d"],), None), "ln_post_mlp": ("gain", (s["d"],), None),
        "wq": ("normal", (s["d"], s["hd"]), s["d"]), "wk": ("normal", (s["d"], s["kd"]), s["d"]),
        "wv": ("normal", (s["d"], s["kd"]), s["d"]), "wo": ("normal", (s["hd"], s["d"]), s["hd"]),
        "q_norm": ("qk_gain", (s["dh"],), None), "k_norm": ("qk_gain", (s["dh"],), None),
        "w_attn_gate": ("normal", (s["d"], s["hd"]), s["d"]),
    }


def dense_shapes(s):
    return {**attention_shapes(s),
            "w_gate": ("normal", (s["d"], s["fd"]), s["d"]), "w_up": ("normal", (s["d"], s["fd"]), s["d"]),
            "w_down": ("normal", (s["fd"], s["d"]), s["fd"])}


def expert_shapes(s):
    return {**attention_shapes(s),
            "router": ("normal", (s["d"], s["e_all"]), s["d"]),
            "expert_bias": ("bias", (s["e_all"],), None),
            "ws_gate": ("normal", (s["d"], s["fs"]), s["d"]), "ws_up": ("normal", (s["d"], s["fs"]), s["d"]),
            "ws_down": ("normal", (s["fs"], s["d"]), s["fs"]),
            "we_gate": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
            "we_up": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
            "we_down": ("normal", (s["e"], s["f"], s["d"]), s["f"])}


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in one jitted call, in `dtype`, a
    layer at a time so that no float32 copy of a stacked tensor exists.

    Matrices (`normal`) are N(0, 1 / fan_in) and gains 1 + 0.1 N(0, 1).
    Random weights stand in for a trained model where a run's numbers
    depend on it:

    * the embedding is N(0, 1 / hidden): times sqrt(hidden) the residual
      starts at unit scale, as muP intends, and each sublayer, normed after
      itself, adds about as much again, so no sublayer is drowned;
    * the q and k norms' gains are QK_GAIN (1 + 0.1 N): with q and k of unit
      RMS a head's scores have a spread of about QK_GAIN^2 = 2.6, and the
      softmax is far from uniform (over 4,096 keys the best takes some 30%
      of the weight).  So where a query looks decides its output: keys past
      the window, or a rotation where there is none, move the logits by
      far more than rounding does;
    * the router is N(0, 1 / hidden): its 256 logits are about N(0, 1) and
      the best four scores lie near sigmoid(2.5 .. 3), a few thousandths
      apart, which float32 resolves; the selection bias is BIAS_SCALE x
      N(0, 1), several times those spacings, so it changes about half of a
      token's picks and a router that leaves it out is caught.
    """
    import jax
    import jax.numpy as jnp

    s = dims(cfg)

    def draw(k, kind, shape, fan):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "normal":
            x = z / np.sqrt(fan)
        elif kind == "bias":
            x = BIAS_SCALE * z
        else:
            x = (QK_GAIN if kind == "qk_gain" else 1.0) * (1.0 + 0.1 * z)
        return x.astype(dtype)

    def layer(shapes):
        def one(key):
            ks = jax.random.split(key, len(shapes))
            return {name: draw(k, *spec) for k, (name, spec) in zip(ks, shapes.items())}
        return one

    @jax.jit
    def make(key):
        k_embed, k_head, k_ln, k_dense, k_blocks = jax.random.split(key, 5)
        return {
            "embed": draw(k_embed, "normal", (s["v"], s["d"]), s["d"]),
            "lm_head": draw(k_head, "normal", (s["d"], s["v"]), s["d"]),
            "ln_f": draw(k_ln, "gain", (s["d"],), None),
            "dense_blocks": jax.lax.map(layer(dense_shapes(s)), jax.random.split(k_dense, s["n0"])),
            "blocks": jax.lax.map(layer(expert_shapes(s)), jax.random.split(k_blocks, s["n"] - s["n0"])),
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    return make(key)


def fake_int8(x, axis):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _matmul(x, w, q):
    """x [..., K] times w [K, N] in float32; with `q`, 8-bit activations per
    token and 8-bit weights per output channel (the control)."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if q:
        x, w = fake_int8(x, (-1,)), fake_int8(w, (0,))
    return jnp.matmul(x, w)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _swiglu(u, wg, wu, wd, q):
    import jax

    return _matmul(jax.nn.silu(_matmul(u, wg, q)) * _matmul(u, wu, q), wd, q)


@functools.lru_cache(maxsize=None)
def _fns(h, kvh, dh, k, first, held, window, theta, route_scale, eps, q, variant):
    import jax
    import jax.numpy as jnp

    g = h // kvh
    freqs = jnp.asarray(theta ** (-np.arange(0, dh // 2, dtype=np.float64) / (dh // 2)), jnp.float32)
    low = jnp.bfloat16 if variant == "bf16_attention" else jnp.float32

    def rope(x):  # x [L, heads, dh]: half-split pairing
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)

    def attention(x, bp, kind):
        length = x.shape[0]
        u = _rms_norm(x, bp["ln1"], eps)
        qh = _rms_norm(_matmul(u, bp["wq"], q).reshape(length, h, dh), bp["q_norm"], eps)
        kh = _rms_norm(_matmul(u, bp["wk"], q).reshape(length, kvh, dh), bp["k_norm"], eps)
        vh = _matmul(u, bp["wv"], q).reshape(length, kvh, dh)
        if kind == "window" or variant == "rotate_full":
            qh, kh = rope(qh), rope(kh)
        win = window if kind == "window" and variant != "past_window" else 0
        nb = -(-length // QUERY_BLOCK)
        qb = jnp.pad(qh, ((0, nb * QUERY_BLOCK - length), (0, 0), (0, 0)))
        qb = qb.reshape(nb, QUERY_BLOCK, kvh, g, dh).astype(low)
        kk, vv = kh.astype(low), vh.astype(low)
        keys = jnp.arange(length)
        scale = jnp.asarray(1.0 / np.sqrt(dh), low)

        def block(args):
            i, qq = args
            t = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
            sc = jnp.einsum("qkgd,skd->kgqs", qq, kk, preferred_element_type=low) * scale
            mask = t[:, None] >= keys[None, :]
            if win:
                mask = mask & (t[:, None] - keys[None, :] < win)
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqs,skd->qkgd", p, vv, preferred_element_type=low)

        o = jax.lax.map(block, (jnp.arange(nb), qb)).astype(jnp.float32)
        o = o.reshape(nb * QUERY_BLOCK, h * dh)[:length]
        if variant != "no_gate":
            o = o * jax.nn.sigmoid(_matmul(u, bp["w_attn_gate"], q))
        return x + _rms_norm(_matmul(o, bp["wo"], q), bp["ln_post_attn"], eps)

    def route(y, bp, forced):
        """picks [L, k] (global ids), their weights, and the router gap: how
        far the lowest biased score among the picks lies below the router's
        own k-th best (0 where the picks are the router's own)."""
        s = jax.nn.sigmoid(_matmul(y, bp["router"], q))
        biased = s + bp["expert_bias"].astype(jnp.float32)
        best, own = jax.lax.top_k(s if variant == "no_bias" else biased, k)
        picks = jnp.where(forced[:, :1] >= 0, forced, own)
        sp = jnp.take_along_axis(s, picks, axis=-1)
        w = sp / (jnp.sum(sp, axis=-1, keepdims=True) + 1e-20) * route_scale
        kth = jax.lax.top_k(biased, k)[0][:, -1]
        gap = jnp.maximum(kth - jnp.min(jnp.take_along_axis(biased, picks, axis=-1), axis=-1), 0.0)
        return picks, w, gap

    @functools.partial(jax.jit, static_argnums=(2,))
    def dense_layer(x, bp, kind):
        x = attention(x, bp, kind)
        y = _rms_norm(x, bp["ln2"], eps)
        return x + _rms_norm(_swiglu(y, bp["w_gate"], bp["w_up"], bp["w_down"], q), bp["ln_post_mlp"], eps)

    @functools.partial(jax.jit, static_argnums=(3,))
    def expert_layer(x, bp, forced, kind):
        x = attention(x, bp, kind)
        y = _rms_norm(x, bp["ln2"], eps)
        picks, w, gap = route(y, bp, forced)

        def one(acc, ew):  # every held expert on every token, weighted where it was picked
            i, wg, wu, wd = ew
            mine = jnp.sum(jnp.where(picks == first + i, w, 0.0), axis=-1, keepdims=True)
            return acc + mine * _swiglu(y, wg, wu, wd, q), None

        f, _ = jax.lax.scan(one, _swiglu(y, bp["ws_gate"], bp["ws_up"], bp["ws_down"], q),
                            (jnp.arange(held), bp["we_gate"], bp["we_up"], bp["we_down"]))
        return x + _rms_norm(f, bp["ln_post_mlp"], eps), picks, gap

    @jax.jit
    def head(x, ln_f, w):
        return _matmul(_rms_norm(x, ln_f, eps), w, q)

    return dense_layer, expert_layer, head


def _fns_of(cfg, precision, variant=None):
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    s = dims(cfg)
    return s, _fns(s["h"], s["kvh"], s["dh"], s["k"], s["first"], s["e"], s["w"],
                   float(cfg["rope_theta"]), float(cfg["route_scale"]), float(cfg["rms_norm_eps"]),
                   precision == "int8", variant)


def forward(weights, cfg, tokens, precision="float32", routing=None, variant=None):
    """Final residual [len(tokens), hidden], and of the expert layers the
    picks [expert layers, len(tokens), k] (global expert ids) and the router
    gaps [expert layers, len(tokens)], layer by layer so that only one
    layer's float32 copy of its weights exists at a time.

    `routing` [expert layers, len(tokens), k] forces picks as
    `axk1_decoder.forward` does: a token whose first entry is >= 0 takes
    those k experts, weighted by THIS router's scores at them, and the router
    gap is how far the lowest biased score among them lies below this
    router's k-th best, 0 where the sets agree."""
    import jax
    import jax.numpy as jnp

    s, (dense_layer, expert_layer, _) = _fns_of(cfg, precision, variant)
    n1, types = s["n"] - s["n0"], kinds(cfg)
    picks, gaps = [], []
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32) * math.sqrt(s["d"])
        if routing is None:
            routing = -jnp.ones((n1, x.shape[0], s["k"]), jnp.int32)
        routing = jnp.asarray(routing, jnp.int32)
        for i in range(s["n0"]):
            x = dense_layer(x, at(weights["dense_blocks"], i), types[i])
        for i in range(n1):
            x, p, g = expert_layer(x, at(weights["blocks"], i), routing[i], types[s["n0"] + i])
            picks.append(p)
            gaps.append(g)
    return x, jnp.stack(picks), jnp.stack(gaps)


def logits(weights, cfg, tokens, precision="float32", routing=None, with_routing=False,
           at=None, variant=None):
    """Teacher-forced logits [len(at), vocab] at the positions `at` (all of
    them if None), over the slice of the vocabulary the configuration holds,
    the head a block of columns at a time; with `with_routing` also the
    picks and the router gaps (`forward`).  `precision` "int8" is the
    control; `variant` one of VARIANTS."""
    import jax
    import jax.numpy as jnp

    _, fns = _fns_of(cfg, precision, variant)
    x, picks, gaps = forward(weights, cfg, tokens, precision, routing, variant)
    if at is not None:
        x = x[jnp.asarray(at, jnp.int32)]
    w = weights["lm_head"]
    cols = w.shape[1] // HEAD_BLOCKS if w.shape[1] % HEAD_BLOCKS == 0 else w.shape[1]
    with jax.default_matmul_precision("highest"):
        out = jnp.concatenate([fns[2](x, weights["ln_f"], w[:, c:c + cols])
                               for c in range(0, w.shape[1], cols)], axis=-1)
    return (out, picks, gaps) if with_routing else out
