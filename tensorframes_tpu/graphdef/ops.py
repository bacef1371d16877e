"""TF op -> JAX lowering registry for GraphDef import.

Covers the op vocabulary of the reference's workloads: the DSL-emitted ops
(``dsl/DslImpl.scala`` emits Placeholder/Const/Identity/Add/Div/Sum/Min with
``reduction_indices``), the test graphs (``graph.pb``/``graph2.pb``: Const +
Placeholder + Add), and the frozen-model scoring vocabulary
(``read_image.py``'s VGG/Inception class of graphs: Conv2D, pooling, batch
norm, activations, dense layers) plus the K-Means demo's
``unsorted_segment_sum``/``argmin`` pre-aggregation kernel
(``kmeans_demo.py:101-168``).

Each entry maps ``(inputs, attrs) -> jax value(s)``; multi-output ops return
tuples and consumers address them as ``node:k``.  Reduction/shape operands
that TF passes as const *inputs* (reduction_indices, shape, paddings, axis)
must be compile-time constants — the importer resolves them via constant
folding before lowering (XLA needs static shapes; SURVEY.md §7 hard part 1).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import dtypes as dt


class UnsupportedOpError(NotImplementedError):
    """A GraphDef node's op has no JAX lowering registered.

    ``code``: the stable ``TFSxxx`` diagnostic code (``docs/ANALYSIS.md``)
    ``tfs.check`` reports for the same failure pre-dispatch."""

    code = "TFS120"


def _attr(attrs, name, default=None):
    av = attrs.get(name)
    return default if av is None or av.kind == "none" else av.value


def _static(x, what: str) -> np.ndarray:
    """Require a compile-time constant operand (e.g. reshape target)."""
    if isinstance(x, np.ndarray):
        return x
    if isinstance(x, (int, float, list, tuple)):
        return np.asarray(x)
    raise UnsupportedOpError(
        f"{what} must be a compile-time constant in the imported graph "
        f"(got a traced value); freeze it into the GraphDef"
    )


def _np_dtype(attrs, key="T", default=np.float32):
    en = _attr(attrs, key)
    return dt.from_tf_enum(en).np_dtype if en is not None else default


def _axes(v) -> Optional[Tuple[int, ...]]:
    a = np.asarray(v).reshape(-1)
    return tuple(int(x) for x in a)


def _str_attr(attrs, name: str, default: bytes) -> str:
    v = _attr(attrs, name, default)
    return v.decode() if isinstance(v, bytes) else str(v)


def _padding_str(attrs) -> str:
    return _str_attr(attrs, "padding", b"VALID")


def _pool(x, attrs, reducer, init, avg=False):
    ksize = [int(k) for k in _attr(attrs, "ksize")]
    strides = [int(s) for s in _attr(attrs, "strides")]
    padding = _padding_str(attrs)
    default_fmt = b"NDHWC" if len(ksize) == 5 else b"NHWC"
    fmt = _str_attr(attrs, "data_format", default_fmt)
    if fmt not in ("NHWC", "NDHWC"):
        raise UnsupportedOpError(f"pooling data_format {fmt} not supported")
    out = lax.reduce_window(
        x, init, reducer, tuple(ksize), tuple(strides), padding
    )
    if avg:
        # window population per output position.  The ones tensor keeps
        # extent 1 on every axis the window does not span (batch,
        # channels) and broadcasts in the divide: XLA folds this
        # reduce_window at compile time with its slow evaluator, and at
        # x's full shape that cost 611 s for Inception-v3's nine SAME
        # avg-pools at 128 rows on the v5e (PR 21 chip run).
        span = [
            n if k > 1 or s > 1 else 1
            for n, k, s in zip(x.shape, ksize, strides)
        ]
        counts = lax.reduce_window(
            jnp.ones(span, x.dtype), 0.0, lax.add, tuple(ksize),
            tuple(strides), padding,
        )
        out = out / counts
    return out


def _conv2d(ins, attrs):
    x, w = ins
    strides = [int(s) for s in _attr(attrs, "strides", [1, 1, 1, 1])]
    dilations = [int(d) for d in _attr(attrs, "dilations", [1, 1, 1, 1])]
    padding = _padding_str(attrs)
    fmt = _str_attr(attrs, "data_format", b"NHWC")
    if fmt != "NHWC":
        raise UnsupportedOpError(f"Conv2D data_format {fmt} not supported")
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=strides[1:3],
        padding=padding,
        rhs_dilation=dilations[1:3],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv3d(ins, attrs):
    # the gap-table promise (docs/GRAPHDEF_OPS.md): same lowering as
    # Conv2D with three spatial dims
    x, w = ins
    strides = [int(s) for s in _attr(attrs, "strides", [1] * 5)]
    dilations = [int(d) for d in _attr(attrs, "dilations", [1] * 5)]
    padding = _padding_str(attrs)
    fmt = _str_attr(attrs, "data_format", b"NDHWC")
    if fmt != "NDHWC":
        raise UnsupportedOpError(f"Conv3D data_format {fmt} not supported")
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=strides[1:4],
        padding=padding,
        rhs_dilation=dilations[1:4],
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )


def _mirror_pad(ins, attrs):
    x, pads = ins
    mode = _str_attr(attrs, "mode", b"REFLECT")
    if mode not in ("REFLECT", "SYMMETRIC"):
        raise UnsupportedOpError(f"MirrorPad mode {mode} not supported")
    pads = np.asarray(_static(pads, "MirrorPad paddings")).astype(int)
    return jnp.pad(
        x,
        [(int(a), int(b)) for a, b in pads],
        # numpy "reflect" excludes the edge (TF REFLECT); "symmetric"
        # repeats it (TF SYMMETRIC)
        mode="reflect" if mode == "REFLECT" else "symmetric",
    )


def _depthwise_conv2d(ins, attrs):
    x, w = ins  # w: [H, W, C, M]
    strides = [int(s) for s in _attr(attrs, "strides", [1, 1, 1, 1])]
    padding = _padding_str(attrs)
    h, wd, c, m = w.shape
    # feature_group_count=C expects flat output channel index c*M + m, which
    # is exactly the [H,W,C,M] memory order — reshape directly, NO transpose
    w2 = jnp.reshape(w, (h, wd, 1, c * m))
    return lax.conv_general_dilated(
        x,
        w2,
        window_strides=strides[1:3],
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )


def _fused_batch_norm(ins, attrs):
    x, scale, offset, mean, var = ins
    eps = float(_attr(attrs, "epsilon", 1e-3))
    is_training = bool(_attr(attrs, "is_training", False))
    if is_training:
        raise UnsupportedOpError(
            "FusedBatchNorm with is_training=True is not supported for "
            "frozen-graph scoring"
        )
    inv = lax.rsqrt(var + eps) * scale
    y = x * inv + (offset - mean * inv)
    return (y, mean, var, mean, var)


def _strided_slice(ins, attrs):
    x, begin, end, strides = ins
    begin = _static(begin, "StridedSlice begin").tolist()
    end = _static(end, "StridedSlice end").tolist()
    strides = _static(strides, "StridedSlice strides").tolist()
    begin_mask = int(_attr(attrs, "begin_mask", 0))
    end_mask = int(_attr(attrs, "end_mask", 0))
    ellipsis_mask = int(_attr(attrs, "ellipsis_mask", 0))
    new_axis_mask = int(_attr(attrs, "new_axis_mask", 0))
    shrink_mask = int(_attr(attrs, "shrink_axis_mask", 0))
    if ellipsis_mask or new_axis_mask:
        raise UnsupportedOpError(
            "StridedSlice ellipsis/new_axis masks not supported"
        )
    idx = []
    for i in range(len(begin)):
        if shrink_mask & (1 << i):
            idx.append(int(begin[i]))
            continue
        b = None if begin_mask & (1 << i) else int(begin[i])
        e = None if end_mask & (1 << i) else int(end[i])
        idx.append(slice(b, e, int(strides[i])))
    return x[tuple(idx)]


def _concat_v2(ins, attrs):
    axis = int(_static(ins[-1], "ConcatV2 axis"))
    return jnp.concatenate(ins[:-1], axis=axis)


def resize_bilinear(
    x,
    out_h: int,
    out_w: int,
    align_corners: bool = False,
    half_pixel_centers: bool = False,
):
    """TF-1.x ``ResizeBilinear`` semantics (legacy kernel: source coord =
    ``out_idx * in/out`` unless align_corners/half_pixel_centers).

    Exposed as a public helper so native models (``models/vgg.py``) use
    THE SAME resize as imported frozen graphs — exporting a model and
    re-importing it cannot diverge on resize convention.  Output is
    float32 like TF's kernel (uint8 inputs included)."""
    x = jnp.asarray(x, jnp.float32)
    n, h, w, c = x.shape

    def coords(out: int, size: int):
        if align_corners and out > 1:
            src = jnp.arange(out, dtype=jnp.float32) * (
                (size - 1) / (out - 1)
            )
        else:
            idx = jnp.arange(out, dtype=jnp.float32)
            scale = size / out
            src = (idx + 0.5) * scale - 0.5 if half_pixel_centers else (
                idx * scale
            )
        src = jnp.clip(src, 0.0, size - 1)
        lo = jnp.floor(src).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, size - 1)
        return lo, hi, src - lo

    hl, hh, hf = coords(out_h, h)
    wl, wh, wf = coords(out_w, w)
    xh = (
        x[:, hl] * (1.0 - hf)[None, :, None, None]
        + x[:, hh] * hf[None, :, None, None]
    )
    return (
        xh[:, :, wl] * (1.0 - wf)[None, None, :, None]
        + xh[:, :, wh] * wf[None, None, :, None]
    )


def _resize_bilinear_op(ins, attrs):
    size = _static(ins[1], "ResizeBilinear size").reshape(-1)
    return resize_bilinear(
        ins[0],
        int(size[0]),
        int(size[1]),
        align_corners=bool(_attr(attrs, "align_corners", False)),
        half_pixel_centers=bool(_attr(attrs, "half_pixel_centers", False)),
    )


def _resize_nearest_op(ins, attrs):
    size = _static(ins[1], "ResizeNearestNeighbor size").reshape(-1)
    x = ins[0]
    n, h, w, c = x.shape
    out_h, out_w = int(size[0]), int(size[1])
    align = bool(_attr(attrs, "align_corners", False))
    half = bool(_attr(attrs, "half_pixel_centers", False))

    def idx(out, sz):
        if align and out > 1:
            src = jnp.arange(out, dtype=jnp.float32) * ((sz - 1) / (out - 1))
            return jnp.round(src).astype(jnp.int32)
        scale = sz / out
        i = jnp.arange(out, dtype=jnp.float32)
        src = jnp.floor((i + 0.5) * scale) if half else jnp.floor(i * scale)
        return jnp.clip(src.astype(jnp.int32), 0, sz - 1)

    return x[:, idx(out_h, h)][:, :, idx(out_w, w)]


def _lrn(ins, attrs):
    """TF ``LRN``: x / (bias + alpha * sum_{window over channels} x^2)^beta
    (AlexNet-era local response normalisation; depth_radius default 5)."""
    x = ins[0]
    r = int(_attr(attrs, "depth_radius", 5))
    bias = float(_attr(attrs, "bias", 1.0))
    alpha = float(_attr(attrs, "alpha", 1.0))
    beta = float(_attr(attrs, "beta", 0.5))
    sq = x * x
    win = lax.reduce_window(
        sq,
        0.0,
        lax.add,
        (1, 1, 1, 2 * r + 1),
        (1, 1, 1, 1),
        [(0, 0), (0, 0), (0, 0), (r, r)],
    )
    return x / (bias + alpha * win) ** beta


def _range(ins):
    # output dtype follows Tidx = the operands' dtype (TF emits int32
    # Range from int32 starts; numpy's platform default would widen it)
    start = np.asarray(_static(ins[0], "Range start"))
    return np.arange(
        start.item(),
        np.asarray(_static(ins[1], "Range limit")).item(),
        np.asarray(_static(ins[2], "Range delta")).item(),
        dtype=start.dtype,
    )


def _split_v(ins):
    sizes = np.asarray(
        _static(ins[1], "SplitV size_splits"), dtype=np.int64
    ).reshape(-1)
    axis = int(_static(ins[2], "SplitV axis"))
    dim = ins[0].shape[axis]
    neg = np.flatnonzero(sizes < 0)
    if neg.size > 1:
        raise UnsupportedOpError(
            "SplitV size_splits may contain at most one -1"
        )
    if neg.size == 1:  # TF's remainder convention: -1 = what's left
        sizes = sizes.copy()
        sizes[neg[0]] = dim - (sizes.sum() - sizes[neg[0]])
    return tuple(jnp.split(ins[0], np.cumsum(sizes[:-1]).tolist(), axis=axis))


def _one_hot(ins, attrs):
    indices, depth, on, off = ins
    axis = int(_attr(attrs, "axis", -1))
    # output dtype is T = on/off_value's dtype (one_hot's own float default
    # would widen f32 graphs to f64 under x64)
    return jax.nn.one_hot(
        indices,
        int(_static(depth, "OneHot depth")),
        axis=axis,
        dtype=jnp.result_type(on),
    ) * (on - off) + off


def _space_depth(ins, attrs, to_depth: bool):
    x = ins[0]
    bs = int(_attr(attrs, "block_size"))
    n, h, w, c = x.shape
    if to_depth:
        x = jnp.reshape(x, (n, h // bs, bs, w // bs, bs, c))
        x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
        return jnp.reshape(x, (n, h // bs, w // bs, bs * bs * c))
    x = jnp.reshape(x, (n, h, w, bs, bs, c // (bs * bs)))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return jnp.reshape(x, (n, h * bs, w * bs, c // (bs * bs)))


def _conv_backprop_input(ins, attrs, spatial: int, op_name: str):
    """TF ``Conv{2,3}DBackpropInput`` used as a DECONV layer in inference
    graphs (segmentation/upsampling nets): the gradient of the forward
    conv w.r.t. its input, applied as a forward op.

    Lowered in the exact adjoint form — an lhs-dilated conv of the
    spatially-flipped, channel-swapped kernel with per-edge padding
    derived from the FORWARD conv's padding — so every ``input_sizes``
    TF accepts round-trips exactly, including odd SAME shapes with
    stride 2 (the classic DeepLab 65x65) and dilated kernels."""
    in_shape = [int(d) for d in _static(ins[0], f"{op_name} input_sizes")]
    # w: [*K, Cin, Cout]; dy: [N, *out_spatial, Cout]
    w, dy = ins[1], ins[2]
    ones = [1] * (spatial + 2)
    strides = [int(s) for s in _attr(attrs, "strides", ones)]
    dilations = [int(d) for d in _attr(attrs, "dilations", ones)]
    padding = _padding_str(attrs)
    default_fmt = b"NDHWC" if spatial == 3 else b"NHWC"
    fmt = _str_attr(attrs, "data_format", default_fmt)
    if fmt != default_fmt.decode():
        raise UnsupportedOpError(
            f"{op_name} data_format {fmt} not supported"
        )
    if padding not in ("SAME", "VALID"):
        raise UnsupportedOpError(
            f"{op_name} padding {padding!r} not supported (EXPLICIT "
            f"paddings would silently change the adjoint arithmetic)"
        )
    pads = []
    for i in range(spatial):
        hi_in, ho = in_shape[1 + i], dy.shape[1 + i]
        s, d, k = strides[1 + i], dilations[1 + i], w.shape[i]
        k_eff = (k - 1) * d + 1
        if padding == "SAME":
            total = max((ho - 1) * s + k_eff - hi_in, 0)
            fwd_lo = total // 2
        else:  # VALID
            fwd_lo = 0
        lo = k_eff - 1 - fwd_lo
        hi = hi_in - 1 - (ho - 1) * s + fwd_lo
        pads.append((lo, hi))
    w2 = jnp.flip(jnp.asarray(w), tuple(range(spatial)))
    w2 = w2.swapaxes(spatial, spatial + 1)  # [*K, Cout, Cin]
    io_layout = ("NDHWC", "DHWIO", "NDHWC") if spatial == 3 else (
        "NHWC", "HWIO", "NHWC")
    return lax.conv_general_dilated(
        dy,
        w2,
        window_strides=(1,) * spatial,
        padding=pads,
        lhs_dilation=tuple(strides[1:1 + spatial]),
        rhs_dilation=tuple(dilations[1:1 + spatial]),
        dimension_numbers=io_layout,
    )


def _conv2d_backprop_input(ins, attrs):
    return _conv_backprop_input(ins, attrs, 2, "Conv2DBackpropInput")


def _space_to_batch_nd(ins, attrs):
    x = ins[0]
    block = [int(b) for b in _static(ins[1], "SpaceToBatchND block_shape")]
    pads = _static(ins[2], "SpaceToBatchND paddings")
    pad_width = [(0, 0)] + [
        (int(a), int(b)) for a, b in pads
    ] + [(0, 0)] * (x.ndim - 1 - len(block))
    x = jnp.pad(x, pad_width)
    n = x.shape[0]
    spatial = x.shape[1 : 1 + len(block)]
    rest = x.shape[1 + len(block):]
    # [N, s1/b1, b1, s2/b2, b2, ..., rest] -> [b1 b2 ... N, s/b..., rest]
    shape = [n]
    for s, b in zip(spatial, block):
        shape += [s // b, b]
    x = jnp.reshape(x, shape + list(rest))
    nb = len(block)
    perm = (
        [2 * i + 2 for i in range(nb)]
        + [0]
        + [2 * i + 1 for i in range(nb)]
        + list(range(1 + 2 * nb, x.ndim))
    )
    x = jnp.transpose(x, perm)
    out_n = n * int(np.prod(block))
    return jnp.reshape(
        x,
        [out_n] + [s // b for s, b in zip(spatial, block)] + list(rest),
    )


def _batch_to_space_nd(ins, attrs):
    x = ins[0]
    block = [int(b) for b in _static(ins[1], "BatchToSpaceND block_shape")]
    crops = _static(ins[2], "BatchToSpaceND crops")
    nb = len(block)
    n = x.shape[0] // int(np.prod(block))
    spatial = x.shape[1 : 1 + nb]
    rest = x.shape[1 + nb:]
    x = jnp.reshape(x, list(block) + [n] + list(spatial) + list(rest))
    # [b1, b2, N, s1, s2, rest] -> [N, s1, b1, s2, b2, rest]
    perm = [nb]
    for i in range(nb):
        perm += [nb + 1 + i, i]
    perm += list(range(2 * nb + 1, x.ndim))
    x = jnp.transpose(x, perm)
    x = jnp.reshape(
        x, [n] + [s * b for s, b in zip(spatial, block)] + list(rest)
    )
    idx = [slice(None)]
    for d, (a, b) in enumerate(crops):
        idx.append(slice(int(a), x.shape[1 + d] - int(b)))
    return x[tuple(idx)]


def _cum(fn):
    def go(ins, attrs):
        axis = int(_static(ins[1], "Cumsum axis"))
        reverse = bool(_attr(attrs, "reverse", False))
        exclusive = bool(_attr(attrs, "exclusive", False))
        x = ins[0]
        if reverse:
            x = jnp.flip(x, axis)
        out = fn(x, axis=axis)
        if exclusive:
            pad = [(0, 0)] * x.ndim
            pad[axis] = (1, 0)
            sl = [slice(None)] * x.ndim
            sl[axis] = slice(0, x.shape[axis])
            ident = 0 if fn is jnp.cumsum else 1
            out = jnp.pad(out, pad, constant_values=ident)[tuple(sl)]
        if reverse:
            out = jnp.flip(out, axis)
        return out

    return go


def _reduction(fn):
    def go(ins, attrs):
        x, axes = ins
        keep = bool(_attr(attrs, "keep_dims", _attr(attrs, "keepdims", False)))
        # TF semantics: reduction_indices=[] is the identity, so the empty
        # tuple must reach jnp as axis=() (NOT None = reduce-all)
        ax = _axes(_static(axes, "reduction_indices"))
        return fn(x, axis=ax, keepdims=keep)

    return go


# op name -> (inputs, attrs) -> value | tuple of values
REGISTRY: Dict[str, Callable[[List[Any], Dict], Any]] = {
    # plumbing
    "Identity": lambda ins, at: ins[0],
    "IdentityN": lambda ins, at: tuple(ins),
    "NoOp": lambda ins, at: (),
    "StopGradient": lambda ins, at: ins[0],
    "PreventGradient": lambda ins, at: ins[0],
    "CheckNumerics": lambda ins, at: ins[0],
    # arithmetic
    "Add": lambda ins, at: ins[0] + ins[1],
    "AddV2": lambda ins, at: ins[0] + ins[1],
    "AddN": lambda ins, at: sum(ins[1:], ins[0]),
    "Sub": lambda ins, at: ins[0] - ins[1],
    "Mul": lambda ins, at: ins[0] * ins[1],
    "Div": lambda ins, at: ins[0] / ins[1],
    "RealDiv": lambda ins, at: ins[0] / ins[1],
    "FloorDiv": lambda ins, at: jnp.floor_divide(ins[0], ins[1]),
    "Maximum": lambda ins, at: jnp.maximum(ins[0], ins[1]),
    "Minimum": lambda ins, at: jnp.minimum(ins[0], ins[1]),
    "Neg": lambda ins, at: -ins[0],
    "Abs": lambda ins, at: jnp.abs(ins[0]),
    "Exp": lambda ins, at: jnp.exp(ins[0]),
    "Log": lambda ins, at: jnp.log(ins[0]),
    "Sqrt": lambda ins, at: jnp.sqrt(ins[0]),
    "Rsqrt": lambda ins, at: lax.rsqrt(ins[0]),
    "Square": lambda ins, at: ins[0] * ins[0],
    "SquaredDifference": lambda ins, at: (ins[0] - ins[1]) ** 2,
    "Pow": lambda ins, at: ins[0] ** ins[1],
    "Tanh": lambda ins, at: jnp.tanh(ins[0]),
    "Sigmoid": lambda ins, at: jax.nn.sigmoid(ins[0]),
    "Relu": lambda ins, at: jax.nn.relu(ins[0]),
    "Relu6": lambda ins, at: jnp.clip(ins[0], 0.0, 6.0),
    "Elu": lambda ins, at: jax.nn.elu(ins[0]),
    "Softplus": lambda ins, at: jax.nn.softplus(ins[0]),
    "Softmax": lambda ins, at: jax.nn.softmax(ins[0], axis=-1),
    "LogSoftmax": lambda ins, at: jax.nn.log_softmax(ins[0], axis=-1),
    # comparison / select
    "Equal": lambda ins, at: ins[0] == ins[1],
    "NotEqual": lambda ins, at: ins[0] != ins[1],
    "Less": lambda ins, at: ins[0] < ins[1],
    "LessEqual": lambda ins, at: ins[0] <= ins[1],
    "Greater": lambda ins, at: ins[0] > ins[1],
    "GreaterEqual": lambda ins, at: ins[0] >= ins[1],
    "Select": lambda ins, at: jnp.where(ins[0], ins[1], ins[2]),
    "SelectV2": lambda ins, at: jnp.where(ins[0], ins[1], ins[2]),
    # linear algebra
    "MatMul": lambda ins, at: jnp.matmul(
        ins[0].T if _attr(at, "transpose_a", False) else ins[0],
        ins[1].T if _attr(at, "transpose_b", False) else ins[1],
    ),
    "BatchMatMul": lambda ins, at: jnp.matmul(
        jnp.swapaxes(ins[0], -1, -2) if _attr(at, "adj_x", False) else ins[0],
        jnp.swapaxes(ins[1], -1, -2) if _attr(at, "adj_y", False) else ins[1],
    ),
    "BatchMatMulV2": lambda ins, at: jnp.matmul(
        jnp.swapaxes(ins[0], -1, -2) if _attr(at, "adj_x", False) else ins[0],
        jnp.swapaxes(ins[1], -1, -2) if _attr(at, "adj_y", False) else ins[1],
    ),
    "BiasAdd": lambda ins, at: ins[0] + ins[1],
    # TF-2.x frozen graphs express most contractions as Einsum; the
    # equation attr is jnp.einsum's own grammar (ellipses included)
    "Einsum": lambda ins, at: jnp.einsum(
        _str_attr(at, "equation", b""), *ins
    ),
    "Conv2D": _conv2d,
    "DepthwiseConv2dNative": _depthwise_conv2d,
    "MaxPool": lambda ins, at: _pool(ins[0], at, lax.max, -jnp.inf),
    "AvgPool": lambda ins, at: _pool(ins[0], at, lax.add, 0.0, avg=True),
    "Conv3D": _conv3d,
    "MaxPool3D": lambda ins, at: _pool(ins[0], at, lax.max, -jnp.inf),
    "AvgPool3D": lambda ins, at: _pool(ins[0], at, lax.add, 0.0, avg=True),
    "MirrorPad": _mirror_pad,
    "FusedBatchNorm": _fused_batch_norm,
    "FusedBatchNormV2": _fused_batch_norm,
    "FusedBatchNormV3": _fused_batch_norm,
    # reductions (reduction indices arrive as const inputs)
    "Sum": _reduction(jnp.sum),
    "Mean": _reduction(jnp.mean),
    "Min": _reduction(jnp.min),
    "Max": _reduction(jnp.max),
    "Prod": _reduction(jnp.prod),
    "All": _reduction(jnp.all),
    "Any": _reduction(jnp.any),
    "ArgMax": lambda ins, at: jnp.argmax(
        ins[0], axis=int(_static(ins[1], "ArgMax axis"))
    ).astype(_np_dtype(at, "output_type", np.int64)),
    "ArgMin": lambda ins, at: jnp.argmin(
        ins[0], axis=int(_static(ins[1], "ArgMin axis"))
    ).astype(_np_dtype(at, "output_type", np.int64)),
    "UnsortedSegmentSum": lambda ins, at: jax.ops.segment_sum(
        ins[0],
        ins[1],
        num_segments=int(_static(ins[2], "UnsortedSegmentSum num_segments")),
    ),
    # shape ops (shape operands must be consts — _static enforces it)
    "Reshape": lambda ins, at: jnp.reshape(
        ins[0], [int(d) for d in _static(ins[1], "Reshape shape")]
    ),
    "Squeeze": lambda ins, at: jnp.squeeze(
        ins[0],
        axis=tuple(int(d) for d in _attr(at, "squeeze_dims", []) or [])
        or None,
    ),
    "ExpandDims": lambda ins, at: jnp.expand_dims(
        ins[0], int(_static(ins[1], "ExpandDims axis"))
    ),
    "Transpose": lambda ins, at: jnp.transpose(
        ins[0], _axes(_static(ins[1], "Transpose perm"))
    ),
    "ConcatV2": _concat_v2,
    "Concat": lambda ins, at: jnp.concatenate(
        ins[1:], axis=int(_static(ins[0], "Concat axis"))
    ),
    "Pack": lambda ins, at: jnp.stack(ins, axis=int(_attr(at, "axis", 0))),
    "Unpack": lambda ins, at: tuple(
        jnp.moveaxis(ins[0], int(_attr(at, "axis", 0)), 0)
    ),
    "StridedSlice": _strided_slice,
    "Slice": lambda ins, at: lax.dynamic_slice(
        ins[0],
        [int(b) for b in _static(ins[1], "Slice begin")],
        [
            int(s) if s != -1 else ins[0].shape[i] - int(b)
            for i, (b, s) in enumerate(
                zip(
                    _static(ins[1], "Slice begin"),
                    _static(ins[2], "Slice size"),
                )
            )
        ],
    ),
    "Pad": lambda ins, at: jnp.pad(
        ins[0],
        [(int(a), int(b)) for a, b in _static(ins[1], "Pad paddings")],
    ),
    "PadV2": lambda ins, at: jnp.pad(
        ins[0],
        [(int(a), int(b)) for a, b in _static(ins[1], "Pad paddings")],
        constant_values=ins[2],
    ),
    "Shape": lambda ins, at: np.asarray(ins[0].shape, dtype=np.int32),
    "Rank": lambda ins, at: np.asarray(len(ins[0].shape), dtype=np.int32),
    "Size": lambda ins, at: np.asarray(ins[0].size, dtype=np.int32),
    "Fill": lambda ins, at: jnp.full(
        [int(d) for d in _static(ins[0], "Fill dims")], ins[1]
    ),
    "ZerosLike": lambda ins, at: jnp.zeros_like(ins[0]),
    "OnesLike": lambda ins, at: jnp.ones_like(ins[0]),
    "Tile": lambda ins, at: jnp.tile(
        ins[0], [int(m) for m in _static(ins[1], "Tile multiples")]
    ),
    "GatherV2": lambda ins, at: jnp.take(
        ins[0], ins[1], axis=int(_static(ins[2], "GatherV2 axis"))
    ),
    "Gather": lambda ins, at: jnp.take(ins[0], ins[1], axis=0),
    "Cast": lambda ins, at: jnp.asarray(ins[0]).astype(
        _np_dtype(at, "DstT")
    ),
    "Range": lambda ins, at: _range(ins),
    # ---- round 5: TF-1.x inference-closure growth (VERDICT r4 next #5) ----
    # image ops (frozen scoring graphs resize in-graph: read_image.py's
    # vgg_preprocessing -> ResizeBilinear)
    "ResizeBilinear": _resize_bilinear_op,
    "ResizeNearestNeighbor": _resize_nearest_op,
    "LRN": _lrn,
    # splitting (the Concat inverse; axis is input 0 for Split, input 2
    # for SplitV, matching TF's inconsistent signatures)
    "Split": lambda ins, at: tuple(
        jnp.split(
            ins[1], int(_attr(at, "num_split")),
            axis=int(_static(ins[0], "Split axis")),
        )
    ),
    "SplitV": lambda ins, at: _split_v(ins),
    "TopKV2": lambda ins, at: tuple(
        (v, i.astype(np.int32))
        for v, i in [lax.top_k(ins[0], int(_static(ins[1], "TopKV2 k")))]
    )[0],
    # elementwise closure
    "Floor": lambda ins, at: jnp.floor(ins[0]),
    "Ceil": lambda ins, at: jnp.ceil(ins[0]),
    "Round": lambda ins, at: jnp.round(ins[0]),  # half-to-even, like TF
    "Rint": lambda ins, at: jnp.round(ins[0]),
    "Sign": lambda ins, at: jnp.sign(ins[0]),
    "FloorMod": lambda ins, at: jnp.mod(ins[0], ins[1]),
    "Mod": lambda ins, at: jnp.fmod(ins[0], ins[1]),  # truncation mod
    "Reciprocal": lambda ins, at: 1.0 / ins[0],
    "Inv": lambda ins, at: 1.0 / ins[0],
    "Log1p": lambda ins, at: jnp.log1p(ins[0]),
    "Expm1": lambda ins, at: jnp.expm1(ins[0]),
    "Erf": lambda ins, at: jax.scipy.special.erf(ins[0]),
    "Erfc": lambda ins, at: jax.scipy.special.erfc(ins[0]),
    "Sin": lambda ins, at: jnp.sin(ins[0]),
    "Cos": lambda ins, at: jnp.cos(ins[0]),
    "Tan": lambda ins, at: jnp.tan(ins[0]),
    "Asin": lambda ins, at: jnp.arcsin(ins[0]),
    "Acos": lambda ins, at: jnp.arccos(ins[0]),
    "Atan": lambda ins, at: jnp.arctan(ins[0]),
    "Atan2": lambda ins, at: jnp.arctan2(ins[0], ins[1]),
    "Sinh": lambda ins, at: jnp.sinh(ins[0]),
    "Cosh": lambda ins, at: jnp.cosh(ins[0]),
    "LeakyRelu": lambda ins, at: jax.nn.leaky_relu(
        ins[0], float(_attr(at, "alpha", 0.2))
    ),
    "Selu": lambda ins, at: jax.nn.selu(ins[0]),
    "Softsign": lambda ins, at: jax.nn.soft_sign(ins[0]),
    "ClipByValue": lambda ins, at: jnp.clip(ins[0], ins[1], ins[2]),
    # indexing / shaping closure
    "BroadcastTo": lambda ins, at: jnp.broadcast_to(
        ins[0], [int(d) for d in _static(ins[1], "BroadcastTo shape")]
    ),
    "OneHot": _one_hot,
    "GatherNd": lambda ins, at: ins[0][
        tuple(jnp.moveaxis(ins[1], -1, 0))
    ],
    "DepthToSpace": lambda ins, at: _space_depth(ins, at, to_depth=False),
    "SpaceToDepth": lambda ins, at: _space_depth(ins, at, to_depth=True),
    "InvertPermutation": lambda ins, at: jnp.argsort(ins[0]).astype(
        ins[0].dtype  # NOT np.asarray(...).dtype: input may be traced
    ),
    "Cumsum": _cum(jnp.cumsum),
    "Cumprod": _cum(jnp.cumprod),
    # deconv + dilated-conv plumbing (segmentation/deeplab-style graphs)
    "Conv2DBackpropInput": _conv2d_backprop_input,
    "Conv3DBackpropInputV2": lambda ins, at: _conv_backprop_input(
        ins, at, 3, "Conv3DBackpropInputV2"
    ),
    "SpaceToBatchND": _space_to_batch_nd,
    "BatchToSpaceND": _batch_to_space_nd,
    # graph plumbing aliases
    "Snapshot": lambda ins, at: ins[0],
    "PlaceholderWithDefault": lambda ins, at: ins[0],
}
