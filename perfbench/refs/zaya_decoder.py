"""Plain reference for the ZAYA1 decoder (`zaya1_8b_l20`): every layer is a
compressed-convolutional-attention sublayer (CCA, arXiv:2510.04476) and a
top-1 expert sublayer behind an MLP router with depth averaging
(arXiv:2511.17127), on one residual stream; RMSNorm, partial rotary
embedding (half-split over the rotated share), tied output head.

Straightforward `jax.numpy` in float32 at `highest` matmul precision, one
whole sequence at a time: no cache, no pages, no convolution state (the
convolutions are shifts of the whole sequence), no sorting (every expert is
applied to every token under a mask), no batching.  Imports nothing of the
program.  With `x` the residual, `D` hidden, `d` head size, `Hq`/`Hk` query
and key heads, `g = Hq / Hk`, `E` experts, `R` the router's width:

  attention   u = RMSNorm(x);  q~ = u Wq,  k~ = u Wk,  c = [q~ ; k~]
              c1[t] = a0 * c[t-1] + a1 * c[t] + b1            (per channel)
              c2[t](h) = c1[t-1](h) A0(h) + c1[t](h) A1(h) + b2(h)   (per head)
              mq(h) = (q~(h) + k~(h // g)) / 2;  mk(j) = mean of mq over group j
              q = c2[:Hq] + mq;  k = c2[Hq:] + mk
              v[t] = [u[t] Wv1 ; u[t-1] Wv2]      (zero history before position 0)
              q <- sqrt(d) q/|q|;  k <- tau(j) sqrt(d) k/|k|;  RoPE on the first
              `partial_rotary_factor` of each head; causal softmax(q k / sqrt(d)) v
              x <- x + o Wo
  experts     u = RMSNorm(x);  r = u Wr + gamma * r_below  (r_below: this token's
              r of the layer below, after its own averaging; 0 at the first layer)
              s = W3 gelu(W2 gelu(W1 RMSNorm(r)));  p = softmax(s)
              e = argmax(p + b);  x <- x + p[e] Wdown(e)(silu(u Wgate(e)) * u Wup(e))

What the config's keys do not carry and the papers give (`assumed` in the
configuration's file), and what is left out (`departures`), is stated there.

The weight tree is the layout the program consumes (`embed`, `blocks`
stacked on a leading layer axis, `ln_f`, and no `lm_head`: the head is
`embed`); the benchmark makes it on the device from the seed and hands the
same arrays to both sides.
"""

import functools

import numpy as np

ROUTER_SPREAD = 4.0  # see make_weights
BRANCH_SCALE = 0.5   # see make_weights
HEAD_BLOCKS = 8      # the head is computed over this many slices of the vocabulary


def dims(cfg):
    """Short names for the sizes of the published config's keys."""
    h, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return dict(d=cfg["hidden_size"], f=cfg["moe_intermediate_size"], h=h, kvh=kvh, dh=dh,
                hd=h * dh, kd=kvh * dh, c=(h + kvh) * dh, vs=(kvh - kvh // 2) * dh,
                e=cfg["num_experts"], r=cfg["router_hidden_size"], v=cfg["vocab_size"],
                n=cfg["num_hidden_layers"])


def block_shapes(s):
    """name -> (kind, shape, fan_in).  `normal` is N(0, 1 / fan_in); `centred`
    the same with each output's weights summing to zero; `gain` is
    1 + 0.1 N(0, 1); `half` 0.5 + 0.1 N(0, 1); `small` 0.05 N(0, 1)."""
    hh = s["h"] + s["kvh"]
    return {
        "ln1": ("gain", (s["d"],), None), "ln2": ("gain", (s["d"],), None),
        "wq": ("normal", (s["d"], s["hd"]), s["d"]), "wk": ("normal", (s["d"], s["kd"]), s["d"]),
        "wv1": ("normal", (s["d"], s["kd"] - s["vs"]), s["d"]),
        "wv2": ("normal", (s["d"], s["vs"]), s["d"]),
        "wo": ("normal", (s["hd"], s["d"]), s["hd"] / BRANCH_SCALE ** 2),
        "conv0_w": ("normal", (2, s["c"]), 2), "conv0_b": ("small", (s["c"],), None),
        "conv1_w": ("normal", (2, hh, s["dh"], s["dh"]), 2 * s["dh"]),
        "conv1_b": ("small", (hh, s["dh"]), None),
        "k_temp": ("gain", (s["kvh"],), None),
        "router_in": ("normal", (s["d"], s["r"]), s["d"]),
        "router_gamma": ("half", (), None), "router_ln": ("gain", (s["r"],), None),
        "router_w1": ("normal", (s["r"], s["r"]), s["r"]),
        "router_w2": ("centred", (s["r"], s["r"]), s["r"]),
        "router_w3": ("centred", (s["r"], s["e"]), s["r"] / ROUTER_SPREAD ** 2),
        "router_bias": ("small", (s["e"],), None),
        "we_gate": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
        "we_up": ("normal", (s["e"], s["d"], s["f"]), s["d"]),
        "we_down": ("normal", (s["e"], s["f"], s["d"]), s["f"] / BRANCH_SCALE ** 2),
    }


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in one jitted call, in `dtype`, a
    layer at a time so that no float32 copy of a stacked tensor exists.

    Matrices are N(0, 1 / fan_in).  Random weights have to stand in for a
    trained model where a run's numbers depend on it, and five choices matter:

    * the embedding is N(0, 1 / hidden): the head is tied to it, so logits
      have unit scale, and a token's own row is a small part of its final
      residual.  (At N(0, 1) with the final gain divided by sqrt(hidden) the
      input token's own logit is about 45 above the rest: every stream repeats
      its last prompt token and even the int8 control serves the reference's
      best token everywhere.  My chip run, PR 28.)
    * the key temperature `tau` is 1 + 0.1 N like every gain.  q and k are
      normalised, so the scores of random keys then have unit spread and
      attention is close to a running mean of the values: a sequence's tokens
      reach 7 or 8 of the 16 experts in the last layers, though the tokens of
      48 sequences reach nearly all.  At 4 attention picks out few positions
      and a sequence's tokens reach 14 to 16, but the model is then so
      sensitive that bfloat16 and float32 part ways whatever the routing
      (widest gap 1.2 along the same experts; CPU, hidden 2,048, 20 layers);
    * the two projections that write to the residual, `wo` and `we_down`, are
      BRANCH_SCALE = 0.5 times N(0, 1 / fan_in): the final residual has a root
      mean square near 2, forty times the embedding's;
    * the router's last matrix is ROUTER_SPREAD = 4 times N(0, 1 / R): at 1 the
      16 scores lie within a few tenths of each other, `p` is close to uniform
      and `argmax(p + b)` is decided by the fixed bias `b`, which sends every
      token to one expert; at 4 the scores' spread (about 2.5) decides and the
      gate `p[e]` is well away from 1/16;
    * the router's second and last matrices are centred, each output's weights
      summing to zero.  gelu's outputs have a positive mean, which a plain
      random matrix turns into a fixed offset per expert as large as the part
      that depends on the token: 48 tokens of 48 sequences then reach 72% of
      the experts a layer and the fullest expert gets 4.4 times its share.  A
      trained router is held level by its balancing bias; centring is what
      stands in for that here (93% and 2.6 times, close to what 48 balls thrown
      evenly into 16 bins give; hidden 2,048, 20 layers, expert width 128, the
      last tokens of 48 random sequences, CPU, float32).
    """
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    shapes = block_shapes(s)

    def draw(k, kind, shape, fan):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "centred":
            z = z - jnp.mean(z, axis=-2, keepdims=True)
        if kind in ("normal", "centred"):
            z = z / np.sqrt(fan)
        elif kind == "gain":
            z = 1.0 + 0.1 * z
        elif kind == "half":
            z = 0.5 + 0.1 * z
        else:
            z = 0.05 * z
        return z.astype(dtype)

    def layer(key):
        ks = jax.random.split(key, len(shapes))
        return {name: draw(k, *spec) for k, (name, spec) in zip(ks, shapes.items())}

    @jax.jit
    def make(key):
        k_embed, k_blocks, k_ln = jax.random.split(key, 3)
        return {
            "embed": draw(k_embed, "normal", (s["v"], s["d"]), s["d"]),
            "blocks": jax.lax.map(layer, jax.random.split(k_blocks, s["n"])),
            "ln_f": draw(k_ln, "gain", (s["d"],), None),
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


def _shift(z):
    """z[t-1] along the first axis, zeros before position 0."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)


def _rope(x, theta, share):
    """x [L, H, dh]: the first `share` of each head rotated, half-split."""
    import jax.numpy as jnp

    length, rot = x.shape[0], int(x.shape[-1] * share)
    freqs = theta ** (-jnp.arange(0, rot // 2, dtype=jnp.float32) / (rot // 2))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1)


@functools.lru_cache(maxsize=None)
def _fns(h, kvh, dh, n_experts, theta, share, eps, q):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    g = h // kvh

    def unit(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6) * np.sqrt(dh)

    def per_head(z, w):  # z [L, H, dh] through w [H, dh, dh], a matrix a head
        return jax.vmap(lambda zh, wh: _matmul(zh, wh, q), in_axes=(1, 0), out_axes=1)(z, w)

    def attention(x, bp):
        length = x.shape[0]
        u = _rms_norm(x, bp["ln1"], eps)
        qt, kt = _matmul(u, bp["wq"], q), _matmul(u, bp["wk"], q)
        c = jnp.concatenate([qt, kt], axis=-1)
        a, b1 = bp["conv0_w"].astype(jnp.float32), bp["conv0_b"].astype(jnp.float32)
        c1 = a[0] * _shift(c) + a[1] * c + b1
        c1h = c1.reshape(length, h + kvh, dh)
        c2 = (per_head(_shift(c1h), bp["conv1_w"][0]) + per_head(c1h, bp["conv1_w"][1])
              + bp["conv1_b"].astype(jnp.float32))
        qh, kh = qt.reshape(length, h, dh), kt.reshape(length, kvh, dh)
        mq = (qh + jnp.repeat(kh, g, axis=1)) / 2.0
        mk = mq.reshape(length, kvh, g, dh).mean(axis=2)
        qn = unit(c2[:, :h] + mq)
        kn = unit(c2[:, h:] + mk) * bp["k_temp"].astype(jnp.float32)[:, None]
        qn, kn = _rope(qn, theta, share), _rope(kn, theta, share)
        v = jnp.concatenate([_matmul(u, bp["wv1"], q), _shift(_matmul(u, bp["wv2"], q))],
                            axis=-1).reshape(length, kvh, dh)
        qg = qn.reshape(length, kvh, g, dh)
        s = jnp.einsum("lkgd,skd->kgls", qg, kn, precision=hi) / np.sqrt(dh)
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgls,skd->lkgd", p, v, precision=hi).reshape(length, h * dh)
        return x + _matmul(o, bp["wo"], q)

    def experts(x, r_below, bp, forced):
        u = _rms_norm(x, bp["ln2"], eps)
        r = _matmul(u, bp["router_in"], q) + bp["router_gamma"].astype(jnp.float32) * r_below
        z = jax.nn.gelu(_matmul(_rms_norm(r, bp["router_ln"], eps), bp["router_w1"], q))
        z = jax.nn.gelu(_matmul(z, bp["router_w2"], q))
        p = jax.nn.softmax(_matmul(z, bp["router_w3"], q), axis=-1)
        biased = p + bp["router_bias"].astype(jnp.float32)
        # a forced expert (>= 0) takes the place of the router's own choice;
        # how far it lies below that choice is the router gap
        chosen = jnp.where(forced >= 0, forced, jnp.argmax(biased, axis=-1))
        gap = jnp.max(biased, axis=-1) - jnp.take_along_axis(biased, chosen[:, None], axis=-1)[:, 0]
        gate = jnp.take_along_axis(p, chosen[:, None], axis=-1)

        def one(acc, ew):  # every expert on every token, kept where it was chosen
            i, wg, wu, wd = ew
            y = _matmul(jax.nn.silu(_matmul(u, wg, q)) * _matmul(u, wu, q), wd, q)
            return acc + jnp.where((chosen == i)[:, None], y, 0.0), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (jnp.arange(n_experts), bp["we_gate"], bp["we_up"], bp["we_down"]))
        return x + gate * y, r, chosen, gap

    @jax.jit
    def layer(x, r_below, bp, forced):
        return experts(attention(x, bp), r_below, bp, forced)

    @jax.jit
    def head(x, ln_f, rows):  # logits against one slice of the (tied) embedding's rows
        return _matmul(_rms_norm(x, ln_f, eps), rows.T, q)

    return layer, head, jax.jit(attention)


def _fns_of(cfg, precision):
    s = dims(cfg)
    rope = cfg["rope_parameters"]["hybrid"]
    return s, _fns(s["h"], s["kvh"], s["dh"], s["e"], float(rope["rope_theta"]),
                   float(rope["partial_rotary_factor"]), float(cfg["rms_norm_eps"]),
                   precision == "int8")


def forward(weights, cfg, tokens, precision="float32", routing=None):
    """Final residual [len(tokens), hidden], the experts chosen and the router
    gaps, both [layers, len(tokens)], layer by layer so that only one layer's
    float32 copy of the weights exists at a time.

    `routing` [layers, len(tokens)] forces experts: an entry >= 0 is taken in
    place of the router's own choice (-1 leaves it its own), and the router
    gap there is how far the forced expert's `p + b` lies below the best, 0
    where they agree.  Top-1 routing is discontinuous, so two sound
    computations in different precisions choose differently where two experts
    nearly tie, and their logits then differ by far more than rounding.  The
    comparison that is meaningful follows the other side's choices and holds
    each of them against this router: a sound side's choices are this
    router's best up to rounding, and under them its tokens are this model's
    best up to rounding."""
    import jax
    import jax.numpy as jnp

    s, (layer, _, _) = _fns_of(cfg, precision)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    if routing is None:
        routing = -jnp.ones((s["n"], x.shape[0]), jnp.int32)
    routing = jnp.asarray(routing, jnp.int32)
    r, chosen, gaps = jnp.zeros((x.shape[0], s["r"]), jnp.float32), [], []
    for i in range(s["n"]):
        x, r, e, g = layer(x, r, jax.tree_util.tree_map(lambda a: a[i], weights["blocks"]), routing[i])
        chosen.append(e)
        gaps.append(g)
    return x, jnp.stack(chosen), jnp.stack(gaps)


def logits(weights, cfg, tokens, precision="float32", routing=None, with_routing=False):
    """Teacher-forced logits [len(tokens), vocab] for one sequence; with
    `with_routing` also the experts chosen and the router gaps (`forward`).
    The head runs over HEAD_BLOCKS slices of the vocabulary, so that the
    float32 copy of the embedding it multiplies with is an eighth of the table
    at a time.  `precision` "int8" is the control (see `fake_int8`)."""
    import jax.numpy as jnp

    s, (_, head, _) = _fns_of(cfg, precision)
    x, chosen, gaps = forward(weights, cfg, tokens, precision, routing)
    step = -(-s["v"] // HEAD_BLOCKS)
    out = jnp.concatenate([head(x, weights["ln_f"], weights["embed"][i: i + step])
                           for i in range(0, s["v"], step)], axis=-1)
    return (out, chosen, gaps) if with_routing else out
