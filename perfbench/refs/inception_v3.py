"""Plain reference for the `inception_v3` configuration.

Inception-v3 (Szegedy et al., arXiv:1512.00567) at its published shapes:
299x299x3 in, stem, 3xA, B, 4xC, D, 2xE, global average pool, 1000 logits,
batch norm folded to a bias.  Straightforward `jax.numpy` in float32 at
`highest` matmul precision; imports nothing of the program.  The weight tree
uses the layout the program consumes (`stem`, `blocks[i][branch]`, `fc_w`,
`fc_b`; each conv `{"w": HWIO, "b": [cout]}`) because the benchmark makes
the weights and hands the same tree to both sides.

`walk` is the architecture: one traversal used for the forward pass (arrays)
and for counting work from shapes (`perfbench/work.py`).
"""

import numpy as np

INPUT_SIZE = 299
NUM_CLASSES = 1000

# (kh, kw, cout, stride, padding, max-pool 3x3/2 afterwards)
STEM = [
    (3, 3, 32, 2, "VALID", False),
    (3, 3, 32, 1, "VALID", False),
    (3, 3, 64, 1, "SAME", True),
    (1, 1, 80, 1, "VALID", False),
    (3, 3, 192, 1, "VALID", True),
]
# (variant, pool-branch channels, 7x7 branch channels)
BLOCKS = [
    ("A", 32, 0), ("A", 64, 0), ("A", 64, 0), ("B", 0, 0),
    ("C", 0, 128), ("C", 0, 160), ("C", 0, 160), ("C", 0, 192),
    ("D", 0, 0), ("E", 0, 0), ("E", 0, 0),
]


def block_graph(variant, pool_ch, c7):
    """A block as (branches, outputs): each branch is (name, input, convs)
    with input `x`, `avg` (3x3/1 SAME average pool of x) or an earlier
    branch; a conv is (kh, kw, cout, stride, padding).  `outputs` are
    concatenated on channels; `max` is the 3x3/2 VALID max pool of x."""
    S, V = "SAME", "VALID"
    if variant == "A":
        return [
            ("b1x1", "x", [(1, 1, 64, 1, S)]),
            ("b5x5", "x", [(1, 1, 48, 1, S), (5, 5, 64, 1, S)]),
            ("b3x3dbl", "x", [(1, 1, 64, 1, S), (3, 3, 96, 1, S), (3, 3, 96, 1, S)]),
            ("pool", "avg", [(1, 1, pool_ch, 1, S)]),
        ], ["b1x1", "b5x5", "b3x3dbl", "pool"]
    if variant == "B":
        return [
            ("b3x3", "x", [(3, 3, 384, 2, V)]),
            ("b3x3dbl", "x", [(1, 1, 64, 1, S), (3, 3, 96, 1, S), (3, 3, 96, 2, V)]),
        ], ["b3x3", "b3x3dbl", "max"]
    if variant == "C":
        return [
            ("b1x1", "x", [(1, 1, 192, 1, S)]),
            ("b7x7", "x", [(1, 1, c7, 1, S), (1, 7, c7, 1, S), (7, 1, 192, 1, S)]),
            ("b7x7dbl", "x", [(1, 1, c7, 1, S), (7, 1, c7, 1, S), (1, 7, c7, 1, S),
                              (7, 1, c7, 1, S), (1, 7, 192, 1, S)]),
            ("pool", "avg", [(1, 1, 192, 1, S)]),
        ], ["b1x1", "b7x7", "b7x7dbl", "pool"]
    if variant == "D":
        return [
            ("b3x3", "x", [(1, 1, 192, 1, S), (3, 3, 320, 2, V)]),
            ("b7x7x3", "x", [(1, 1, 192, 1, S), (1, 7, 192, 1, S), (7, 1, 192, 1, S),
                             (3, 3, 192, 2, V)]),
        ], ["b3x3", "b7x7x3", "max"]
    if variant == "E":
        return [
            ("b1x1", "x", [(1, 1, 320, 1, S)]),
            ("b3x3_stem", "x", [(1, 1, 384, 1, S)]),
            ("b3x3_a", "b3x3_stem", [(1, 3, 384, 1, S)]),
            ("b3x3_b", "b3x3_stem", [(3, 1, 384, 1, S)]),
            ("b3x3dbl_stem", "x", [(1, 1, 448, 1, S), (3, 3, 384, 1, S)]),
            ("b3x3dbl_a", "b3x3dbl_stem", [(1, 3, 384, 1, S)]),
            ("b3x3dbl_b", "b3x3dbl_stem", [(3, 1, 384, 1, S)]),
            ("pool", "avg", [(1, 1, 192, 1, S)]),
        ], ["b1x1", "b3x3_a", "b3x3_b", "b3x3dbl_a", "b3x3dbl_b", "pool"]
    raise ValueError(f"unknown block variant {variant}")


def walk(x, conv, avg_pool, max_pool, concat):
    """Run the architecture over `x` with the given layer functions.
    `conv(x, path, spec)` gets the weight's path in the tree, e.g.
    ("stem", 2) or ("blocks", 4, "b7x7", 1)."""
    for i, (kh, kw, cout, stride, pad, pool) in enumerate(STEM):
        x = conv(x, ("stem", i), (kh, kw, cout, stride, pad))
        if pool:
            x = max_pool(x)
    for bi, (variant, pool_ch, c7) in enumerate(BLOCKS):
        branches, outputs = block_graph(variant, pool_ch, c7)
        vals = {"x": x}
        for name, src, convs in branches:
            if src == "avg" and "avg" not in vals:
                vals["avg"] = avg_pool(x)
            y = vals[src]
            for ci, spec in enumerate(convs):
                y = conv(y, ("blocks", bi, name, ci), spec)
            vals[name] = y
        if "max" in outputs:
            vals["max"] = max_pool(x)
        x = concat([vals[n] for n in outputs])
    return x


def out_size(n, k, stride, pad):
    return -(-n // stride) if pad == "SAME" else (n - k) // stride + 1


def conv_layers():
    """Every convolution with its shapes, from the published layout alone:
    dicts of path, kh, kw, cin, cout, hin, win, hout, wout; then the final
    feature size (h, w, c)."""
    layers = []

    def conv(x, path, spec):
        h, w, c = x
        kh, kw, cout, stride, pad = spec
        ho, wo = out_size(h, kh, stride, pad), out_size(w, kw, stride, pad)
        layers.append(dict(path=path, kh=kh, kw=kw, cin=c, cout=cout,
                           hin=h, win=w, hout=ho, wout=wo))
        return (ho, wo, cout)

    def max_pool(x):
        return (out_size(x[0], 3, 2, "VALID"), out_size(x[1], 3, 2, "VALID"), x[2])

    final = walk((INPUT_SIZE, INPUT_SIZE, 3), conv, lambda x: x, max_pool,
                 lambda xs: (xs[0][0], xs[0][1], sum(v[2] for v in xs)))
    return layers, final


def make_weights(seed, dtype):
    """He-normal weights and small biases from the seed, as host numpy arrays
    of `dtype`, in the tree layout above."""
    rng = np.random.default_rng([int(seed), 0x1CE])
    layers, (_, _, feat) = conv_layers()
    tree = {"stem": [None] * len(STEM), "blocks": [dict() for _ in BLOCKS]}
    for l in layers:
        shape = (l["kh"], l["kw"], l["cin"], l["cout"])
        w = rng.standard_normal(shape, dtype=np.float32) * np.sqrt(2.0 / np.prod(shape[:3]))
        b = rng.standard_normal((l["cout"],), dtype=np.float32) * 0.05
        leaf = {"w": w.astype(dtype), "b": b.astype(dtype)}
        path = l["path"]
        if path[0] == "stem":
            tree["stem"][path[1]] = leaf
        else:
            tree["blocks"][path[1]].setdefault(path[2], []).append(leaf)
    tree["fc_w"] = (rng.standard_normal((feat, NUM_CLASSES), dtype=np.float32)
                    / np.sqrt(feat)).astype(dtype)
    tree["fc_b"] = (rng.standard_normal((NUM_CLASSES,), dtype=np.float32) * 0.05).astype(dtype)
    return tree


def _leaf(tree, path):
    node = tree
    for p in path:
        node = node[p]
    return node


def lower(x, axis, precision):
    """`x` rounded to the lower precision and back, with one absmax scale
    along `axis`: "int8", or "fp8" (e4m3, scaled so that the largest entry
    sits at the format's top)."""
    import jax.numpy as jnp

    top = 127.0 if precision == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if precision == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def forward(weights, images_u8, precision="float32"):
    """uint8 images [n, 299*299*3] -> (prediction [n], score [n], log-probs
    [n, 1000]).  `precision` "float32" is the reference; "int8" and "fp8" round
    every convolution's and the head's inputs (one scale an image) and weights
    (one scale a tensor) to 8 bits: the control, the nearest precision below
    the configuration's bfloat16."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    q = precision != "float32"

    def conv(x, path, spec):
        _, _, _, stride, pad = spec
        p = _leaf(weights, path)
        w = jnp.asarray(p["w"], jnp.float32)
        if q:
            x, w = lower(x, (1, 2, 3), precision), lower(w, None, precision)
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        return jax.nn.relu(y + jnp.asarray(p["b"], jnp.float32))

    def window_sum(x):
        return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME")

    def avg_pool(x):
        return window_sum(x) / window_sum(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype))

    def max_pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "VALID")

    x = images_u8.reshape(-1, INPUT_SIZE, INPUT_SIZE, 3).astype(jnp.float32) / 127.5 - 1.0
    x = walk(x, conv, avg_pool, max_pool, lambda xs: jnp.concatenate(xs, axis=-1))
    x = jnp.mean(x, axis=(1, 2))
    w = jnp.asarray(weights["fc_w"], jnp.float32)
    if q:
        x, w = lower(x, (1,), precision), lower(w, None, precision)
    logits = jnp.matmul(x, w, precision=hi) + jnp.asarray(weights["fc_b"], jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.argmax(logp, axis=-1), jnp.max(logp, axis=-1), logp
