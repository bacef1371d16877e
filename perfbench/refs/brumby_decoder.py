"""Plain reference for the Brumby configuration (`brumby_14b_l8`): Qwen3's
decoder block (RMSNorm, per-head RMSNorm on q and k, half-split rotary
embedding, grouped heads, SwiGLU, untied head) with power retention
(arXiv:2507.04239) in attention's place.  Straightforward `jax.numpy` in
float32 at `highest` matmul precision, one whole sequence at a time.
Imports nothing of the program.

Retention is computed here in its ATTENTION FORM over the whole sequence,

    A_ts = exp(sum_{r=s+1..t} log g_r) (q_t . k_s / sqrt(dh))**2      s <= t
    y_t  = sum_s A_ts v_s / (sum_s A_ts + eps)

a block of query positions at a time, with no state, no feature map and no
chunk: the program's recurrent step and chunked prefill are checked against
another algorithm, not against themselves.  `g` is one gate a KV head,
`sigmoid(h W_g + b_g)`.  What the model's config.json does not carry (the
power 2, the gate's form, the normaliser and its eps, the 1/sqrt(dh) inside
the power) is listed with its equation in the configuration's file under
`assumed`.

The weight tree is the layout the program consumes (`embed`, `blocks`
stacked on a leading layer axis, `ln_f`, `lm_head`); the benchmark makes it
on the device from the seed and hands the same arrays to both sides.
"""

import functools

import numpy as np

EPS = 1e-6  # beside the normaliser
BRANCH_SCALE = 0.5  # on the two projections that write to the residual
GATE_BIAS = (3.0, 7.0)  # b_g uniform over this: sigmoid 0.953 .. 0.9991
QUERY_BLOCK = 512  # query positions a block of the attention form holds


def dims(cfg):
    """Short names for the sizes of the config dict."""
    h, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"], h=h, kvh=kvh, dh=dh,
                hd=h * dh, kd=kvh * dh, v=cfg["vocab_size"], n=cfg["num_hidden_layers"])


def block_shapes(s):
    """name -> (kind, shape, fan_in) of one layer's tensors."""
    return {
        "ln1": ("gain", (s["d"],), None), "ln2": ("gain", (s["d"],), None),
        "q_norm": ("gain", (s["dh"],), None), "k_norm": ("gain", (s["dh"],), None),
        "wq": ("normal", (s["d"], s["hd"]), s["d"]), "wk": ("normal", (s["d"], s["kd"]), s["d"]),
        "wv": ("normal", (s["d"], s["kd"]), s["d"]),
        "wo": ("normal", (s["hd"], s["d"]), s["hd"] / BRANCH_SCALE ** 2),
        "wg": ("normal", (s["d"], s["kvh"]), 4 * s["d"]), "bg": ("gate_bias", (s["kvh"],), None),
        "w_gate": ("normal", (s["d"], s["f"]), s["d"]), "w_up": ("normal", (s["d"], s["f"]), s["d"]),
        "w_down": ("normal", (s["f"], s["d"]), s["f"] / BRANCH_SCALE ** 2),
    }


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in one jitted call, in `dtype`, a
    layer at a time so that no float32 copy of a stacked tensor exists.

    Matrices are N(0, 1 / fan_in) and gains 1 + 0.1 N.  Random weights have
    to stand in for a trained model where a run's numbers depend on it:

    * the gate's bias `b_g` is uniform over 3 .. 7, one draw a KV head a
      layer, and `W_g` is N(0, 1 / (4 hidden)), so `h W_g` moves the gate by
      about a half: g lies between 0.95 and 0.999 and a head remembers 20 to
      1,000 tokens, as a trained retention layer does.  A zero-mean gate gives
      g near 0.5: the state then holds two tokens, and a comparison with the
      reference tests nothing of how the state accumulates or decays;
    * the embedding is N(0, 1 / hidden), not N(0, 1): a token's own row is
      then a small part of its residual after the first layer, and what the
      model puts out depends on what the layers (the state) made of the
      context, not on the last token alone (at N(0, 1) the embedding is the
      largest term of the final residual; `zaya_decoder.make_weights` tells
      what that did under a tied head);
    * the two projections that write to the residual, `wo` and `w_down`, are
      BRANCH_SCALE = 0.5 times N(0, 1 / fan_in), as in `zaya_decoder`: sixteen
      branches add up to a residual of root mean square near 2.
    """
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    shapes = block_shapes(s)

    def draw(k, kind, shape, fan):
        if kind == "gate_bias":
            return jax.random.uniform(k, shape, jnp.float32, *GATE_BIAS).astype(dtype)
        z = jax.random.normal(k, shape, jnp.float32)
        z = z / np.sqrt(fan) if kind == "normal" else 1.0 + 0.1 * z
        return z.astype(dtype)

    def layer(key):
        ks = jax.random.split(key, len(shapes))
        return {name: draw(k, *spec) for k, (name, spec) in zip(ks, shapes.items())}

    @jax.jit
    def make(key):
        k_embed, k_blocks, k_ln, k_head = jax.random.split(key, 4)
        return {
            "embed": draw(k_embed, "normal", (s["v"], s["d"]), s["d"]),
            "blocks": jax.lax.map(layer, jax.random.split(k_blocks, s["n"])),
            "ln_f": draw(k_ln, "gain", (s["d"],), None),
            "lm_head": draw(k_head, "normal", (s["d"], s["v"]), s["d"]),
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


def _rope(x, theta):
    """x [L, H, dh], positions 0 .. L-1, half-split pairing."""
    import jax.numpy as jnp

    length, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def retention(q, k, v, log_g):
    """The attention form over a whole sequence: q [L, h, dh], k, v [L, kvh,
    dh], log_g [L, kvh] -> [L, h, dh], a block of query positions at a time
    (the scores of all of them at once are [h, L, L])."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    length, h, dh = q.shape
    kvh = k.shape[1]
    through = jnp.cumsum(log_g, axis=0).T  # [kvh, L]: the decay from the start through t
    qg = q.reshape(length, kvh, h // kvh, dh)
    at = jnp.arange(length)
    out = []
    for t0 in range(0, length, QUERY_BLOCK):
        t1 = min(t0 + QUERY_BLOCK, length)
        s = jnp.einsum("tkgd,skd->kgts", qg[t0:t1], k[:t1], precision=hi) / np.sqrt(dh)
        decay = through[:, t0:t1, None] - through[:, None, :t1]
        seen = at[t0:t1, None] >= at[None, :t1]
        a = jnp.square(s) * jnp.exp(jnp.where(seen, decay, -jnp.inf))[:, None]
        num = jnp.einsum("kgts,skv->tkgv", a, v[:t1], precision=hi)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None] + EPS
        out.append((num / den).reshape(t1 - t0, h, dh))
    return jnp.concatenate(out, axis=0)


@functools.lru_cache(maxsize=None)
def _fns(h, kvh, dh, theta, eps, q):
    import jax

    @jax.jit
    def layer(x, bp, at):
        length = x.shape[0]
        y = _rms_norm(x, bp["ln1"], eps)
        qh = _matmul(y, bp["wq"], q).reshape(length, h, dh)
        kh = _matmul(y, bp["wk"], q).reshape(length, kvh, dh)
        vh = _matmul(y, bp["wv"], q).reshape(length, kvh, dh)
        qh = _rope(_rms_norm(qh, bp["q_norm"], eps), theta)
        kh = _rope(_rms_norm(kh, bp["k_norm"], eps), theta)
        log_g = jax.nn.log_sigmoid(_matmul(y, bp["wg"], q) + bp["bg"].astype(y.dtype))
        att = retention(qh, kh, vh, log_g)
        x = x + _matmul(att.reshape(length, h * dh), bp["wo"], q)
        y = _rms_norm(x, bp["ln2"], eps)
        ff = jax.nn.silu(_matmul(y, bp["w_gate"], q)) * _matmul(y, bp["w_up"], q)
        return x + _matmul(ff, bp["w_down"], q), qh[at], att[at]

    @jax.jit
    def head(x, ln_f, lm_head):
        return _matmul(_rms_norm(x, ln_f, eps), lm_head, q)

    return layer, head


def logits(weights, cfg, tokens, precision="float32", at=None):
    """Teacher-forced logits for one sequence, [len(at), vocab] at the
    positions `at` (all of them if None), layer by layer so that only one
    layer's float32 copy of the weights exists at a time.  `precision`
    "int8" is the control (see `fake_int8`)."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    layer, head = _fns(s["h"], s["kvh"], s["dh"], float(cfg["rope_theta"]),
                       float(cfg["rms_norm_eps"]), precision == "int8")
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i in range(s["n"]):
        x, _, _ = layer(x, jax.tree_util.tree_map(lambda a: a[i], weights["blocks"]), 0)
    if at is not None:
        x = x[jnp.asarray(at, jnp.int32)]
    return head(x, weights["ln_f"], weights["lm_head"])


def read_outs(weights, cfg, tokens, at):
    """What every layer's retention puts out at the one position `at` of the
    teacher-forced sequence, and the queries that read it: `(q, y)`, each
    [layers, h, dh] float32.  y is the attention form's sum over positions
    0 .. at, which is what a state that holds those positions has to give
    back to the same queries."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    layer, _ = _fns(s["h"], s["kvh"], s["dh"], float(cfg["rope_theta"]),
                    float(cfg["rms_norm_eps"]), False)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    qs, ys = [], []
    for i in range(s["n"]):
        x, q, y = layer(x, jax.tree_util.tree_map(lambda a: a[i], weights["blocks"]), int(at))
        qs.append(q)
        ys.append(y)
    return jnp.stack(qs), jnp.stack(ys)
