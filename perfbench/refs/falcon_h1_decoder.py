"""Plain reference for the Falcon-H1 configuration (`falcon_h1_34b_l4`): a
block whose normed input goes both to grouped-query attention and to a
Mamba-2 mixer, side by side, their outputs added to the residual, then a
SwiGLU; every one of the model's named multipliers where its config puts it;
an untied head.  Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one whole sequence at a time.
Imports nothing of the program.

Attention is computed in full causal form.  Mamba-2 is computed in SSD's
QUADRATIC (masked) form over the whole sequence,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t

a block of query positions at a time, with no state, no chunk and no
convolution cache: the program's recurrent step, its chunked prefill and its
pool are held against another algorithm.  The state a sequence leaves at
position T is computed in closed form (`ssm_states`),

    h_T = sum_{s <= T} exp(sum_{r=s+1..T} dt_r A) dt_s x_s B_s^T

What the config does not say is listed with its equation in the
configuration's file under `assumed`.

The weight tree is the layout the program consumes (`embed`, `blocks`
stacked on a leading layer axis, `ln_f`, `lm_head`); the benchmark makes it
on the device from the seed and hands the same arrays to both sides.
"""

import functools

import numpy as np

QUERY_BLOCK = 512  # query positions a block of the quadratic forms holds
HEAD_BLOCKS = 8  # the head's columns, in float32 this many at a time
A_RANGE = (1.0, 16.0)  # Mamba-2's initialisation: A uniform over this
DT_RANGE = (1e-3, 1e-1)  # and dt log-uniform over this, through dt_bias


def dims(cfg):
    """Short names for the sizes of the config dict."""
    h, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    mh, mp, mg, mn = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
                      cfg["mamba_d_state"])
    ssm = mh * mp
    conv = ssm + 2 * mg * mn
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"], h=h, kvh=kvh, dh=dh,
                hd=h * dh, kd=kvh * dh, v=cfg["vocab_size"], n=cfg["num_hidden_layers"],
                mh=mh, mp=mp, mg=mg, mn=mn, mk=cfg["mamba_d_conv"], ssm=ssm, conv=conv,
                proj=ssm + conv + mh)


def block_shapes(s):
    """name -> (kind, shape, fan_in) of one layer's tensors."""
    return {
        "ln1": ("gain", (s["d"],), None), "ln2": ("gain", (s["d"],), None),
        "wq": ("normal", (s["d"], s["hd"]), s["d"]), "wk": ("key", (s["d"], s["kd"]), s["d"]),
        "wv": ("normal", (s["d"], s["kd"]), s["d"]), "wo": ("normal", (s["hd"], s["d"]), s["hd"]),
        "ssm_in": ("normal", (s["d"], s["proj"]), s["d"]),
        "conv_w": ("normal", (s["mk"], s["conv"]), s["mk"]),
        "conv_b": ("conv_bias", (s["conv"],), s["mk"]),
        "dt_bias": ("dt_bias", (s["mh"],), None), "A_log": ("A_log", (s["mh"],), None),
        "D": ("ones", (s["mh"],), None), "ssm_norm": ("gain", (s["ssm"],), None),
        "ssm_out": ("normal", (s["ssm"], s["d"]), s["ssm"]),
        "w_gate": ("normal", (s["d"], s["f"]), s["d"]), "w_up": ("normal", (s["d"], s["f"]), s["d"]),
        "w_down": ("normal", (s["f"], s["d"]), s["f"]),
    }


def make_weights(seed, cfg, dtype):
    """All weights on the default device, in jitted calls, in `dtype`, a
    layer at a time so that no float32 copy of a stacked tensor exists.

    Matrices are N(0, 1 / fan_in) and gains 1 + 0.1 N.  Where a run's
    numbers depend on what a trained model would hold:

    * `A_log` and `dt_bias` follow Mamba-2's published initialisation: A
      uniform over 1 .. 16 (A_log its logarithm) and dt log-uniform over
      0.001 .. 0.1, `dt_bias` its inverse softplus; so exp(dt A) lies
      between 0.2 and 0.999 and a head remembers 1 to 1,000 tokens, where a
      trained model's decays lie.  D is 1, the convolution's weights N(0,
      1 / d_conv) and its bias uniform over +-1 / sqrt(d_conv), PyTorch's
      default;
    * `wk` is N(0, 1 / fan_in) divided by `key_multiplier`, so that the
      keys attention uses (after the multiplier) are of unit scale and the
      scores q.k / sqrt(128) spread by about 1: at N(0, 1 / fan_in) they
      would spread by 0.011 and attention would be a mean over the context;
    * the embedding is N(0, 1 / hidden), not N(0, 1): times
      `embedding_multiplier` its row is then of the order of what a layer's
      mixer adds, so what the model puts out depends on the context the
      layers (the state) made and not on the last token alone.
    """
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    shapes = block_shapes(s)
    key_mult = float(cfg["key_multiplier"])

    def draw(k, kind, shape, fan):
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, *A_RANGE)).astype(dtype)
        if kind == "dt_bias":
            lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        if kind == "conv_bias":
            bound = 1.0 / np.sqrt(fan)
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound).astype(dtype)
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "gain":
            return (1.0 + 0.1 * z).astype(dtype)
        z = z / np.sqrt(fan)
        return (z / key_mult if kind == "key" else z).astype(dtype)

    def layer(key):
        ks = jax.random.split(key, len(shapes))
        return {name: draw(k, *spec) for k, (name, spec) in zip(ks, shapes.items())}

    def rows(key, shape, fan, pieces=16):
        """A large matrix drawn a block of rows at a time."""
        return jax.lax.map(lambda k: draw(k, "normal", (shape[0] // pieces, shape[1]), fan),
                           jax.random.split(key, pieces)).reshape(shape)

    @jax.jit
    def make(key):
        k_embed, k_blocks, k_ln, k_head = jax.random.split(key, 4)
        return {
            "embed": rows(k_embed, (s["v"], s["d"]), s["d"]),
            "blocks": jax.lax.map(layer, jax.random.split(k_blocks, s["n"])),
            "ln_f": draw(k_ln, "gain", (s["d"],), None),
            "lm_head": rows(k_head, (s["d"], s["v"]), s["d"]),
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


def attention(q, k, v):
    """Causal softmax attention over a whole sequence: q [L, h, dh], k, v
    [L, kvh, dh] -> [L, h, dh], a block of query positions at a time."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    length, h, dh = q.shape
    kvh = k.shape[1]
    qg = q.reshape(length, kvh, h // kvh, dh)
    at = jnp.arange(length)
    out = []
    for t0 in range(0, length, QUERY_BLOCK):
        t1 = min(t0 + QUERY_BLOCK, length)
        s = jnp.einsum("tkgd,skd->kgts", qg[t0:t1], k[:t1], precision=hi) / np.sqrt(dh)
        s = jnp.where(at[t0:t1, None] >= at[None, :t1], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v[:t1], precision=hi).reshape(t1 - t0, h, dh))
    return jnp.concatenate(out, axis=0)


def ssd_quadratic(x, B, C, dt, A):
    """SSD's masked form over a whole sequence: x [L, H, P], B, C [L, G, N],
    dt [L, H], A [H] -> y [L, H, P] (D's skip apart), a block of query
    positions at a time.  Head i reads group i // (H / G)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    length, H, P = x.shape
    G = B.shape[1]
    J = H // G
    cum = jnp.cumsum(dt * A, axis=0).T.reshape(G, J, length)  # through t, inclusive
    xdt = (x * dt[..., None]).reshape(length, G, J, P)
    at = jnp.arange(length)
    out = []
    for t0 in range(0, length, QUERY_BLOCK):
        t1 = min(t0 + QUERY_BLOCK, length)
        cb = jnp.einsum("tgn,sgn->gts", C[t0:t1], B[:t1], precision=hi)
        seen = at[t0:t1, None] >= at[None, :t1]
        decay = jnp.exp(jnp.where(seen, cum[:, :, t0:t1, None] - cum[:, :, None, :t1], -jnp.inf))
        w = cb[:, None] * decay  # [G, J, t, s]
        out.append(jnp.einsum("gjts,sgjp->tgjp", w, xdt[:t1], precision=hi).reshape(t1 - t0, H, P))
    return jnp.concatenate(out, axis=0)


def ssd_state(x, B, dt, A, at):
    """The state a sequence leaves at position `at`, in closed form:
    [H, P, N]."""
    import jax
    import jax.numpy as jnp

    length, H, P = x.shape
    G = B.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)  # [L, H]
    seen = jnp.arange(length) <= at
    w = jnp.where(seen[:, None], jnp.exp(cum[at][None] - cum) * dt, 0.0)  # [L, H]
    Bh = jnp.repeat(B, H // G, axis=1)  # [L, H, N]
    return jnp.einsum("sh,shp,shn->hpn", w, x, Bh, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _fns(key, q):
    """The jitted layer and head for the sizes and multipliers in `key`."""
    import jax
    import jax.numpy as jnp

    cfg = dict(key)
    s = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    seg = [float(m) for m in cfg["ssm_multipliers"]]
    gate_mult, down_mult = (float(m) for m in cfg["mlp_multipliers"])

    def mamba(y, bp, at):
        length = y.shape[0]
        p = _matmul(y * float(cfg["ssm_in_multiplier"]), bp["ssm_in"], q)
        gn = s["mg"] * s["mn"]
        z, xs, b, c, dt = jnp.split(p, np.cumsum([s["ssm"], s["ssm"], gn, gn]), axis=-1)
        z, xs, b, c, dt = (u * m for u, m in zip((z, xs, b, c, dt), seg))
        xbc = jnp.concatenate([xs, b, c], axis=-1)
        K = s["mk"]
        padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        w = bp["conv_w"].astype(jnp.float32)
        xbc = sum(w[k] * padded[k:k + length] for k in range(K)) + bp["conv_b"].astype(jnp.float32)
        xbc = jax.nn.silu(xbc)
        xs, b, c = jnp.split(xbc, [s["ssm"], s["ssm"] + gn], axis=-1)
        xs = xs.reshape(length, s["mh"], s["mp"])
        b, c = b.reshape(length, s["mg"], s["mn"]), c.reshape(length, s["mg"], s["mn"])
        dt = jax.nn.softplus(dt + bp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(bp["A_log"].astype(jnp.float32))
        y = ssd_quadratic(xs, b, c, dt, A) + bp["D"].astype(jnp.float32)[:, None] * xs
        g = (y.reshape(length, -1) * jax.nn.silu(z)).reshape(length, s["mg"], -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        g = g.reshape(length, -1) * bp["ssm_norm"].astype(jnp.float32)
        out = _matmul(g, bp["ssm_out"], q) * float(cfg["ssm_out_multiplier"])
        return out, ssd_state(xs, b, dt, A, at)

    @jax.jit
    def layer(x, bp, at):
        length = x.shape[0]
        y = _rms_norm(x, bp["ln1"], eps)
        ya = y * float(cfg["attention_in_multiplier"])
        qh = _rope(_matmul(ya, bp["wq"], q).reshape(length, s["h"], s["dh"]), theta)
        kh = _matmul(ya, bp["wk"], q).reshape(length, s["kvh"], s["dh"]) * float(cfg["key_multiplier"])
        kh = _rope(kh, theta)
        vh = _matmul(ya, bp["wv"], q).reshape(length, s["kvh"], s["dh"])
        att = attention(qh, kh, vh).reshape(length, s["hd"])
        a = _matmul(att, bp["wo"], q) * float(cfg["attention_out_multiplier"])
        m, state = mamba(y, bp, at)
        x = x + a + m
        y = _rms_norm(x, bp["ln2"], eps)
        ff = jax.nn.silu(_matmul(y, bp["w_gate"], q) * gate_mult) * _matmul(y, bp["w_up"], q)
        return x + _matmul(ff, bp["w_down"], q) * down_mult, state

    @jax.jit
    def head(x, ln_f, lm_head):
        return _matmul(_rms_norm(x, ln_f, eps), lm_head, q) * float(cfg["lm_head_multiplier"])

    return layer, head


def _key(cfg):
    """The configuration as a hashable key of what the forward reads."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items() if not isinstance(v, (dict, str)) or k == "dtype"))


def _forward(weights, cfg, tokens, precision, at=None):
    """`(x after the layers [L, d], states [layers, H, P, N] after position
    `at` (the last if None), the head)` for one teacher-forced sequence."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    layer, head = _fns(_key(cfg), precision == "int8")
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    x = x * float(cfg["embedding_multiplier"])
    states = []
    with jax.default_matmul_precision("highest"):
        for i in range(s["n"]):
            x, st = layer(x, jax.tree_util.tree_map(lambda a: a[i], weights["blocks"]),
                          len(tokens) - 1 if at is None else int(at))
            states.append(st)
    return x, jnp.stack(states), head


def logits(weights, cfg, tokens, precision="float32", at=None):
    """Teacher-forced logits for one sequence, [len(at), vocab] at the
    positions `at` (all of them if None), after `lm_head_multiplier`: what
    the model emits.  Layer by layer so that only one layer's float32 copy
    of the weights exists at a time, and the head a block of columns at a
    time.  `precision` "int8" is the control (see `fake_int8`)."""
    import jax
    import jax.numpy as jnp

    x, _, head = _forward(weights, cfg, tokens, precision)
    if at is not None:
        x = x[jnp.asarray(at, jnp.int32)]
    w = weights["lm_head"]
    cols = w.shape[1] // HEAD_BLOCKS if w.shape[1] % HEAD_BLOCKS == 0 else w.shape[1]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([head(x, weights["ln_f"], w[:, c:c + cols])
                                for c in range(0, w.shape[1], cols)], axis=-1)


def ssm_states(weights, cfg, tokens, at):
    """The state every layer's mixer holds after position `at` of the
    teacher-forced `tokens`, in closed form: [layers, heads, P, N]
    float32."""
    return _forward(weights, cfg, tokens, "float32", at)[1]
