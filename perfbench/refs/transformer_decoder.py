"""Plain reference for decoder-only transformer configurations
(`mistral_7b_l8`): RMSNorm, rotary embedding (half-split), grouped-query
attention, SwiGLU, untied output head.  Straightforward `jax.numpy` in
float32 at `highest` matmul precision, one whole sequence at a time with full
causal attention: no cache, no pages, no batching.  Imports nothing of the
program.  Departure from the published model, stated in the configuration's
file: RMSNorm epsilon 1e-6 (the program's), where Mistral publishes 1e-5.

The weight tree is the layout the program consumes (`embed`, `blocks` stacked
on a leading layer axis, `ln_f`, `lm_head`); the benchmark makes it on the
device from the seed and hands the same arrays to both sides.
"""

import functools

import numpy as np

BLOCK_SHAPES = {  # name -> (fan_in key, shape keys)
    "wq": ("d", ("d", "hd")), "wk": ("d", ("d", "kd")), "wv": ("d", ("d", "kd")),
    "wo": ("hd", ("hd", "d")), "w_gate": ("d", ("d", "f")), "w_up": ("d", ("d", "f")),
    "w_down": ("f", ("f", "d")),
}


def dims(cfg):
    """Short names for the sizes of a HuggingFace-style config dict."""
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"], h=h, kvh=kvh, dh=dh,
                hd=h * dh, kd=kvh * dh, v=cfg["vocab_size"], n=cfg["num_hidden_layers"])


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in one jitted call, in `dtype`."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)

    @jax.jit
    def make(key):
        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)

        def gain(k, shape):
            return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

        ks = jax.random.split(key, len(BLOCK_SHAPES) + 6)
        blocks = {
            name: normal(ks[i], (s["n"],) + tuple(s[a] for a in shape), s[fan])
            for i, (name, (fan, shape)) in enumerate(BLOCK_SHAPES.items())
        }
        blocks["ln1"] = gain(ks[-6], (s["n"], s["d"]))
        blocks["ln2"] = gain(ks[-5], (s["n"], s["d"]))
        return {
            "embed": normal(ks[-4], (s["v"], s["d"]), 1.0),
            "blocks": blocks,
            "ln_f": gain(ks[-3], (s["d"],)),
            "lm_head": normal(ks[-2], (s["d"], s["v"]), s["d"]),
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
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if q:  # 8-bit activations per token, 8-bit weights per output channel
        x, w = fake_int8(x, (-1,)), fake_int8(w, (0,))
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    import jax.numpy as jnp

    length, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(h, kvh, dh, theta, eps, q):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def layer(x, bp):
        length = x.shape[0]
        y = _rms_norm(x, bp["ln1"], eps)
        qh = _rope(_matmul(y, bp["wq"], q).reshape(length, h, dh), theta)
        kh = _rope(_matmul(y, bp["wk"], q).reshape(length, kvh, dh), theta)
        vh = _matmul(y, bp["wv"], q).reshape(length, kvh, dh)
        qg = qh.reshape(length, kvh, h // kvh, dh)
        s = jnp.einsum("lkgd,skd->kgls", qg, kh, precision=hi) / np.sqrt(dh)
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        att = jnp.einsum("kgls,skd->lkgd", p, vh, precision=hi).reshape(length, h * dh)
        x = x + _matmul(att, bp["wo"], q)
        y = _rms_norm(x, bp["ln2"], eps)
        ff = jax.nn.silu(_matmul(y, bp["w_gate"], q)) * _matmul(y, bp["w_up"], q)
        return x + _matmul(ff, bp["w_down"], q)

    @jax.jit
    def head(x, ln_f, lm_head):
        return _matmul(_rms_norm(x, ln_f, eps), lm_head, q)

    return layer, head


def logits(weights, cfg, tokens, precision="float32"):
    """Teacher-forced logits [len(tokens), vocab] for one sequence, layer by
    layer so that only one layer's float32 copy of the weights exists at a
    time.  `precision` "int8" is the control (see `fake_int8`)."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    layer, head = _layer_fn(s["h"], s["kvh"], s["dh"], float(cfg["rope_theta"]),
                            float(cfg["rms_norm_eps_as_run"]), precision == "int8")
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i in range(s["n"]):
        x = layer(x, jax.tree_util.tree_map(lambda a: a[i], weights["blocks"]))
    return head(x, weights["ln_f"], weights["lm_head"])
