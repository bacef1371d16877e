"""Plain reference for the A.X-K1 decoder (`axk1_l7_ep16`): multi-head latent
attention (MLA, DeepSeek-V2's, whose config keys this model's are one for one)
with YaRN rotary frequencies, `first_k_dense_replace` leading dense SwiGLU
layers, then expert layers of a shared expert beside `num_experts_per_tok`
routed ones behind a sigmoid router; RMSNorm, untied output head.

Straightforward `jax.numpy` in float32 at `highest` matmul precision, one
whole sequence at a time: the EXPANDED attention form only (per-head keys and
values from every position's latent; no cache, no pages, no absorption of
`W_kvb` into the query), no sorting (every held expert is applied to every
token under a mask), no batching.  Attention runs over `HEAD_BLOCK` heads at a
time and a layer's weights become float32 where they are used, so that a
3,072-token sequence at the published widths fits beside the bfloat16
weights.  Imports nothing of the program.  With `x` the residual, `h =
RMSNorm(x)`, 64 heads, `dn` 128, `dr` 64, `dv` 128:

  attention   c_q = RMSNorm(h W_qa);  q(j) = [q_n(j) ; q_r(j)] = c_q W_qb   (j a head)
              [c ; k_r] = h W_kva;  c <- RMSNorm(c);  q_r, k_r rotated (one k_r a
              token, shared by the heads);  [k_n(j) ; v(j)] = c W_kvb
              score(j, t, s) = (q_n(j, t) . k_n(j, s) + q_r(j, t) . k_r(s)) (dn + dr)^-1/2 m^2
              m = 0.1 mscale_all_dim ln(factor) + 1;  causal softmax;
              x <- x + concat_j(sum_s p v(j, s)) W_o
  rotary      YaRN over the dr rotated dimensions, half-split pairing: f_i =
              theta^(-2i/dr); corr(n) = dr ln(original / (2 pi n)) / (2 ln theta);
              low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)), clipped to
              0..dr-1; ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq_i =
              (1 - ramp_i) f_i + ramp_i f_i / factor; cos and sin are multiplied by
              mscale(factor, mscale) / mscale(factor, mscale_all_dim), 1 here
  dense       x <- x + W_down(silu(h W_gate) * h W_up)              (layers < first_k)
  experts     s = sigmoid(h W_r) over ALL routed experts; picks = the k largest s;
              w = s[picks] / (sum s[picks] + 1e-20) * routed_scaling_factor
              x <- x + E_shared(h) + sum over the picks HELD here of w E_e(h)

The share.  `expert_share` `{index, of}` says which of the routed experts this
configuration holds: `n_routed_experts` of them, global ids `index *
n_routed_experts ..`, of `of * n_routed_experts` the router scores.  Routing and
the weights `w` are over all of them; the picks that fall on absent experts add
nothing, here and in the program alike, and that partial result goes on to the
next layer.  `{0, 1}` is the uncut layer.

The weight tree is the layout the program consumes: `embed`, `lm_head`, `ln_f`,
`dense_blocks` (the leading dense layers, stacked) and `blocks` (the expert
layers, stacked); the benchmark makes it on the device from the seed and hands
the same arrays to both sides.
"""

import functools
import math

import numpy as np

BRANCH_SCALE = 0.5  # see make_weights
HEAD_BLOCK = 8      # attention is computed over this many heads at a time


def dims(cfg):
    """Short names for the sizes of the published config's keys."""
    share = cfg["expert_share"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
                kr=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], fd=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
                e=cfg["n_routed_experts"], e_all=cfg["n_routed_experts"] * share["of"],
                first=cfg["n_routed_experts"] * share["index"], k=cfg["num_experts_per_tok"],
                fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                n=cfg["num_hidden_layers"], n0=cfg["first_k_dense_replace"], v=cfg["vocab_size"])


def attention_shapes(s):
    """name -> (kind, shape, fan_in): see `make_weights` for the kinds."""
    hq, hkv = s["h"] * (s["dn"] + s["dr"]), s["h"] * (s["dn"] + s["dv"])
    return {
        "ln1": ("gain", (s["d"],), None), "ln2": ("gain", (s["d"],), None),
        "wq_a": ("normal", (s["d"], s["qr"]), s["d"]), "q_ln": ("gain", (s["qr"],), None),
        "wq_b": ("normal", (s["qr"], hq), s["qr"]),
        "wkv_a": ("normal", (s["d"], s["kr"] + s["dr"]), s["d"]), "kv_ln": ("gain", (s["kr"],), None),
        "wkv_b": ("normal", (s["kr"], hkv), s["kr"]),
        "wo": ("normal", (s["h"] * s["dv"], s["d"]), s["h"] * s["dv"] / BRANCH_SCALE ** 2),
    }


def dense_shapes(s):
    return {**attention_shapes(s),
            "w_gate": ("normal", (s["d"], s["fd"]), s["d"]), "w_up": ("normal", (s["d"], s["fd"]), s["d"]),
            "w_down": ("normal", (s["fd"], s["d"]), s["fd"] / BRANCH_SCALE ** 2)}


def expert_shapes(s):
    return {**attention_shapes(s),
            "router": ("normal", (s["d"], s["e_all"]), s["d"]),
            "ws_gate": ("normal", (s["d"], s["fs"]), s["d"]), "ws_up": ("normal", (s["d"], s["fs"]), s["d"]),
            "ws_down": ("normal", (s["fs"], s["d"]), s["fs"] / BRANCH_SCALE ** 2),
            "we_gate": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
            "we_up": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
            "we_down": ("normal", (s["e"], s["f"], s["d"]), s["f"] / BRANCH_SCALE ** 2)}


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in one jitted call, in `dtype`, a
    layer at a time so that no float32 copy of a stacked tensor exists.

    Matrices (`normal`) are N(0, 1 / fan_in) and gains 1 + 0.1 N(0, 1).  Random
    weights stand in for a trained model where a run's numbers depend on it:

    * the embedding is N(0, 1): the head is not tied to it, so no token's own
      logit stands out, and the residual starts at the unit scale the branches
      add to (a token's own row is about a fifth of the final residual's
      variance after 7 layers);
    * the projections that write to the residual (`wo`, `w_down`, `ws_down`,
      `we_down`) are BRANCH_SCALE = 0.5 times N(0, 1 / fan_in), as
      `zaya_decoder` argues its own;
    * the router is N(0, 1 / hidden) and no wider: `h` has unit scale, so the
      192 logits are about N(0, 1), the 8 best scores lie near sigmoid(1.7 ..
      2.8) = 0.85 .. 0.94 and differ in the second or third decimal, which
      float32 resolves a thousand times over.  A wider router would push the
      best scores into the sigmoid's flat end, where float32 rounds them to
      the same 1.0 and rounding, not the scores, would decide the picks; a
      narrower one changes nothing (the picks are those of the logits, and a
      logit's error from a rounded `h` shrinks with the logit).  There is no
      bias to overrule (`topk_method` "none": see `assumed` in the
      configuration's file) and no gelu in front of it, so nothing needs
      centring: every expert's logit has mean zero over tokens, and a chip's 12
      of 192 get 1/16 of the picks on average;
    * `W_qb` and `W_kvb` read RMS-normed latents, so `q_n . k_n + q_r . k_r`
      has variance `dn + dr` and the scores about m^2 = 1.8: attention is a
      softened average over the positions, as it is in `zaya_decoder`.
    """
    import jax
    import jax.numpy as jnp

    s = dims(cfg)

    def draw(k, kind, shape, fan):
        z = jax.random.normal(k, shape, jnp.float32)
        return (z / np.sqrt(fan) if kind == "normal" else 1.0 + 0.1 * z).astype(dtype)

    def layer(shapes):
        def one(key):
            ks = jax.random.split(key, len(shapes))
            return {name: draw(k, *spec) for k, (name, spec) in zip(ks, shapes.items())}
        return one

    @jax.jit
    def make(key):
        k_embed, k_head, k_ln, k_dense, k_blocks = jax.random.split(key, 5)
        return {
            "embed": draw(k_embed, "normal", (s["v"], s["d"]), 1.0),
            "lm_head": draw(k_head, "normal", (s["d"], s["v"]), s["d"]),
            "ln_f": draw(k_ln, "gain", (s["d"],), None),
            "dense_blocks": jax.lax.map(layer(dense_shapes(s)), jax.random.split(k_dense, s["n0"])),
            "blocks": jax.lax.map(layer(expert_shapes(s)), jax.random.split(k_blocks, s["n"] - s["n0"])),
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    return make(key)


def yarn_inv_freq(theta, dim, scaling):
    """The `dim / 2` rotary frequencies under YaRN (`scaling` the config's
    `rope_scaling`; None or factor 1 is plain rotary)."""
    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling or scaling["factor"] == 1:
        return f.astype(np.float32)

    def corr(n):
        return dim * math.log(scaling["original_max_position_embeddings"] / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling["beta_fast"])), 0)
    high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * f + ramp * f / scaling["factor"]).astype(np.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg):
    """(dn + dr)^-1/2, times m^2 under YaRN with `mscale_all_dim`."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= _mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rotary_multiplier(cfg):
    sc = cfg.get("rope_scaling")
    if not sc:
        return 1.0
    return _mscale(sc["factor"], sc.get("mscale", 1)) / _mscale(sc["factor"], sc.get("mscale_all_dim", 0))


def fake_int8(x, axis):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _matmul(x, w, q):
    """x [..., K] times w [K, N] in float32; with `q`, 8-bit activations per
    token and 8-bit weights per output channel (the control)."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if q:
        x, w = fake_int8(x, (-1,)), fake_int8(w, (0,))
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _swiglu(u, wg, wu, wd, q):
    import jax

    return _matmul(jax.nn.silu(_matmul(u, wg, q)) * _matmul(u, wu, q), wd, q)


@functools.lru_cache(maxsize=None)
def _fns(h, dn, dr, dv, kr, k, first, held, inv_freq, rot_mult, scale, routed_scale, eps, q):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    freqs = jnp.asarray(inv_freq, jnp.float32)

    def rope(x):  # x [L, ..., dr]: half-split pairing over the last axis
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
        ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dr // 2,))
        cos, sin = jnp.cos(ang) * rot_mult, jnp.sin(ang) * rot_mult
        x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)

    def attention(x, bp):
        length = x.shape[0]
        u = _rms_norm(x, bp["ln1"], eps)
        c_q = _rms_norm(_matmul(u, bp["wq_a"], q), bp["q_ln"], eps)
        qh = _matmul(c_q, bp["wq_b"], q).reshape(length, h, dn + dr)
        q_n, q_r = qh[..., :dn], rope(qh[..., dn:])
        ckr = _matmul(u, bp["wkv_a"], q)
        c, k_r = _rms_norm(ckr[:, :kr], bp["kv_ln"], eps), rope(ckr[:, kr:])
        kv = _matmul(c, bp["wkv_b"], q).reshape(length, h, dn + dv)
        k_n, v = kv[..., :dn], kv[..., dn:]
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

        def heads(blk):  # HEAD_BLOCK heads at a time: scores [heads, L, L]
            qn, qr_, kn, vv = blk
            sc = (jnp.einsum("lhd,shd->hls", qn, kn, precision=hi)
                  + jnp.einsum("lhd,sd->hls", qr_, k_r, precision=hi)) * scale
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hls,shd->lhd", p, vv, precision=hi)

        nb = max(1, h // HEAD_BLOCK)
        split = lambda z: jnp.moveaxis(z.reshape(length, nb, h // nb, z.shape[-1]), 1, 0)
        o = jax.lax.map(heads, (split(q_n), split(q_r), split(k_n), split(v)))
        o = jnp.moveaxis(o, 0, 1).reshape(length, h * dv)
        return x + _matmul(o, bp["wo"], q)

    def route(u, bp, forced):
        """picks [L, k] (global ids), their weights, and the router gap: how
        far the lowest score among the picks lies below the router's own
        k-th best (0 where the picks are the router's own)."""
        s = jax.nn.sigmoid(_matmul(u, bp["router"], q))
        best, own = jax.lax.top_k(s, k)
        picks = jnp.where(forced[:, :1] >= 0, forced, own)
        sp = jnp.take_along_axis(s, picks, axis=-1)
        w = sp / (jnp.sum(sp, axis=-1, keepdims=True) + 1e-20) * routed_scale
        return picks, w, jnp.maximum(best[:, -1] - jnp.min(sp, axis=-1), 0.0)

    def experts(x, bp, forced):
        u = _rms_norm(x, bp["ln2"], eps)
        picks, w, gap = route(u, bp, forced)

        def one(acc, ew):  # every held expert on every token, weighted where it was picked
            i, wg, wu, wd = ew
            mine = jnp.sum(jnp.where(picks == first + i, w, 0.0), axis=-1, keepdims=True)
            return acc + mine * _swiglu(u, wg, wu, wd, q), None

        y, _ = jax.lax.scan(one, _swiglu(u, bp["ws_gate"], bp["ws_up"], bp["ws_down"], q),
                            (jnp.arange(held), bp["we_gate"], bp["we_up"], bp["we_down"]))
        return x + y, picks, gap

    @jax.jit
    def dense_layer(x, bp):
        x = attention(x, bp)
        u = _rms_norm(x, bp["ln2"], eps)
        return x + _swiglu(u, bp["w_gate"], bp["w_up"], bp["w_down"], q)

    @jax.jit
    def expert_layer(x, bp, forced):
        return experts(attention(x, bp), bp, forced)

    @jax.jit
    def head(x, ln_f, w):
        return _matmul(_rms_norm(x, ln_f, eps), w, q)

    return dense_layer, expert_layer, head, jax.jit(attention), jax.jit(experts)


def _fns_of(cfg, precision):
    s = dims(cfg)
    inv = tuple(float(f) for f in yarn_inv_freq(cfg["rope_theta"], s["dr"], cfg.get("rope_scaling")))
    return s, _fns(s["h"], s["dn"], s["dr"], s["dv"], s["kr"], s["k"], s["first"], s["e"], inv,
                   rotary_multiplier(cfg), softmax_scale(cfg), float(cfg["routed_scaling_factor"]),
                   float(cfg["rms_norm_eps"]), precision == "int8")


def forward(weights, cfg, tokens, precision="float32", routing=None):
    """Final residual [len(tokens), hidden], and of the expert layers the
    picks [expert layers, len(tokens), k] (global expert ids) and the router
    gaps [expert layers, len(tokens)], layer by layer so that only one layer's
    float32 copy of its weights exists at a time.

    `routing` [expert layers, len(tokens), k] forces picks: a token whose
    first entry is >= 0 takes those k experts in place of the router's own
    (-1 leaves it its own), weighted by THIS router's scores at them, and the
    router gap there is how far the lowest of those scores lies below this
    router's k-th best, 0 where the sets agree.  Top-k routing is
    discontinuous as top-1 is (`zaya_decoder.forward` says what follows), and
    with 8 of 192 the 8th and 9th best scores lie close in every layer: the
    comparison that is meaningful follows the other side's picks and holds
    each set against this router."""
    import jax
    import jax.numpy as jnp

    s, (dense_layer, expert_layer, _, _, _) = _fns_of(cfg, precision)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    n1 = s["n"] - s["n0"]
    if routing is None:
        routing = -jnp.ones((n1, x.shape[0], s["k"]), jnp.int32)
    routing = jnp.asarray(routing, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for i in range(s["n0"]):
        x = dense_layer(x, at(weights["dense_blocks"], i))
    picks, gaps = [], []
    for i in range(n1):
        x, p, g = expert_layer(x, at(weights["blocks"], i), routing[i])
        picks.append(p)
        gaps.append(g)
    return x, jnp.stack(picks), jnp.stack(gaps)


def logits(weights, cfg, tokens, precision="float32", routing=None, with_routing=False):
    """Teacher-forced logits [len(tokens), vocab] for one sequence, over the
    slice of the vocabulary the configuration holds; with `with_routing` also
    the picks and the router gaps (`forward`).  `precision` "int8" is the
    control (see `fake_int8`)."""
    _, fns = _fns_of(cfg, precision)
    x, picks, gaps = forward(weights, cfg, tokens, precision, routing)
    out = fns[2](x, weights["ln_f"], weights["lm_head"])
    return (out, picks, gaps) if with_routing else out
