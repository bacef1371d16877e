"""GraphDef import tests: wire codec round-trip, op lowering, and the
frozen-model verb flows (the reference's graph.pb / read_image.py paths)."""

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.graphdef import (
    GraphDef,
    import_graphdef,
    load_graphdef,
    parse_graphdef,
)
from tensorframes_tpu.graphdef.builder import GraphBuilder
from tensorframes_tpu.graphdef.importer import GraphImportError, placeholder_specs
from tensorframes_tpu.graphdef.ops import UnsupportedOpError
from tensorframes_tpu.graphdef.proto import TensorProto


def frame(data, blocks=1):
    return tfs.analyze(tfs.TensorFrame.from_arrays(data, num_blocks=blocks))


# ----------------------------------------------------------- wire codec --


def test_roundtrip_simple_graph():
    b = GraphBuilder()
    b.placeholder("x", "float32", [-1])
    b.const("c", np.float32(3.0))
    b.op("Add", "z", ["x", "c"])
    data = b.to_bytes()
    g = parse_graphdef(data)
    assert [n.name for n in g.nodes] == ["x", "c", "z"]
    assert g.nodes[2].op == "Add"
    assert g.nodes[2].inputs == ["x", "c"]
    # re-encode is byte-stable
    assert g.encode() == parse_graphdef(g.encode()).encode()


def test_tensorproto_roundtrip_dtypes():
    for arr in [
        np.arange(6, dtype=np.float32).reshape(2, 3),
        np.arange(4, dtype=np.float64),
        np.array([1, -2, 3], dtype=np.int32),
        np.array([2**40, -(2**41)], dtype=np.int64),
        np.array([True, False]),
    ]:
        tp = TensorProto.from_numpy(arr)
        back = TensorProto.parse(tp.encode())
        np.testing.assert_array_equal(back.value, arr)
        assert back.value.dtype == arr.dtype


def test_tensorproto_scalar_broadcast():
    # proto convention: single value + shape = fill
    tp = TensorProto.from_numpy(np.float32(2.5))
    import tensorframes_tpu.graphdef.proto as proto
    import tensorframes_tpu.graphdef.wire as wire

    out = bytearray()
    wire.write_varint_field(out, 1, tp.dtype)
    wire.write_len_field(out, 2, proto.encode_shape(tfs.Shape((2, 2))))
    import struct

    wire.write_fixed32_field(out, 5, struct.pack("<f", 2.5))
    back = TensorProto.parse(bytes(out))
    np.testing.assert_array_equal(back.value, np.full((2, 2), 2.5, np.float32))


def test_string_tensor():
    arr = np.empty(2, dtype=object)
    arr[0], arr[1] = b"ab", b"cde"
    tp = TensorProto.from_numpy(arr)
    back = TensorProto.parse(tp.encode())
    assert list(back.value) == [b"ab", b"cde"]


# ------------------------------------------------------------- importer --


def test_import_add_graph_map_blocks():
    # the reference README flow: frozen graph z = x + 3 run via map_blocks
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    b.const("three", np.float64(3.0))
    b.op("Add", "z", ["x", "three"])
    p = import_graphdef(b.build(), fetches=["z"])
    tf = frame({"x": np.arange(10.0)})
    out = tfs.map_blocks(p, tf)
    np.testing.assert_allclose(out.column("z").data, np.arange(10.0) + 3.0)


def test_import_fetch_colon_zero_and_inputs_mapping():
    b = GraphBuilder()
    b.placeholder("in", "float64", [-1])
    b.const("two", np.float64(2.0))
    b.op("Mul", "y", ["in", "two"])
    p = import_graphdef(b.build(), fetches=["y:0"], inputs={"in": "x"})
    tf = frame({"x": np.arange(4.0)})
    out = tfs.map_blocks(p, tf)
    np.testing.assert_allclose(out.column("y").data, np.arange(4.0) * 2)


def test_import_mlp_map_rows():
    # benchmark config #3 shape: per-row MLP inference from a frozen graph
    rng = np.random.RandomState(0)
    w1, b1 = rng.randn(8, 16).astype(np.float32), rng.randn(16).astype(np.float32)
    w2, b2 = rng.randn(16, 4).astype(np.float32), rng.randn(4).astype(np.float32)
    g = GraphBuilder()
    g.placeholder("v", "float32", [-1, 8])
    g.const("w1", w1)
    g.const("b1", b1)
    g.const("w2", w2)
    g.const("b2", b2)
    g.op("MatMul", "h0", ["v", "w1"])
    g.op("BiasAdd", "h1", ["h0", "b1"])
    g.op("Relu", "h", ["h1"])
    g.op("MatMul", "l0", ["h", "w2"])
    g.op("BiasAdd", "logits", ["l0", "b2"])
    g.op("Softmax", "probs", ["logits"])
    p = import_graphdef(g.build(), fetches=["probs"])
    x = rng.randn(32, 8).astype(np.float32)
    tf = frame({"v": x})
    out = tfs.map_blocks(p, tf)
    h = np.maximum(x @ w1 + b1, 0)
    logits = h @ w2 + b2
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    np.testing.assert_allclose(
        out.column("probs").data, e / e.sum(axis=1, keepdims=True), rtol=1e-5
    )


def test_import_reduction_with_const_indices():
    # DSL-emitted reducer shape: Sum with reduction_indices const input
    b = GraphBuilder()
    b.placeholder("x_input", "float64", [-1])
    b.const("idx", np.array([0], dtype=np.int32))
    b.op("Sum", "x", ["x_input", "idx"], keep_dims=False)
    p = import_graphdef(b.build(), fetches=["x"])
    tf = frame({"x": np.arange(10.0)}, blocks=3)
    got = tfs.reduce_blocks(p, tf)
    assert got["x"] == pytest.approx(45.0)


def test_import_conv_pool_graph():
    rng = np.random.RandomState(0)
    img = rng.randn(2, 8, 8, 3).astype(np.float32)
    w = rng.randn(3, 3, 3, 4).astype(np.float32)
    g = GraphBuilder()
    g.placeholder("img", "float32", [-1, 8, 8, 3])
    g.const("w", w)
    g.op(
        "Conv2D", "conv", ["img", "w"],
        strides=[1, 1, 1, 1], padding=b"SAME",
    )
    g.op("Relu", "act", ["conv"])
    g.op(
        "MaxPool", "pool", ["act"],
        ksize=[1, 2, 2, 1], strides=[1, 2, 2, 1], padding=b"VALID",
    )
    p = import_graphdef(g.build(), fetches=["pool"])
    tf = frame({"img": img})
    out = tfs.map_blocks(p, tf)
    assert out.column("pool").data.shape == (2, 4, 4, 4)
    # oracle via jax directly
    import jax.numpy as jnp
    from jax import lax

    conv = lax.conv_general_dilated(
        img, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    act = np.maximum(np.asarray(conv), 0)
    pool = np.asarray(
        lax.reduce_window(act, -np.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    )
    np.testing.assert_allclose(out.column("pool").data, pool, rtol=1e-5)


def test_avg_pool_same_counts_do_not_span_the_batch():
    """SAME AvgPool divides by the window population.  The population is
    a compile-time constant that XLA folds with its slow evaluator, so it
    must be built at extent 1 on the batch and channel axes (at the
    block's full shape the fold took minutes on the chip), and must still
    give the edge-aware average."""
    import jax
    from jax import lax

    rng = np.random.RandomState(0)
    img = rng.randn(4, 5, 5, 3).astype(np.float32)
    g = GraphBuilder()
    g.placeholder("img", "float32", [-1, 5, 5, 3])
    g.op(
        "AvgPool", "pool", ["img"],
        ksize=[1, 3, 3, 1], strides=[1, 1, 1, 1], padding=b"SAME",
    )
    p = import_graphdef(g.build(), fetches=["pool"])
    out = tfs.map_blocks(p, frame({"img": img}))
    summed = lax.reduce_window(
        img, 0.0, lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME"
    )
    counts = lax.reduce_window(
        np.ones((1, 5, 5, 1), np.float32), 0.0, lax.add, (1, 3, 3, 1),
        (1, 1, 1, 1), "SAME",
    )
    np.testing.assert_allclose(
        out.column("pool").data, np.asarray(summed / counts), rtol=1e-6
    )
    windows = [
        e.invars[0].aval.shape
        for e in jax.make_jaxpr(lambda x: p.call({"img": x}, p.params))(
            img
        ).jaxpr.eqns
        if e.primitive.name == "reduce_window_sum"
    ]
    assert sorted(windows) == [(1, 5, 5, 1), (4, 5, 5, 3)], windows


def test_import_segment_sum_preagg():
    # the kmeans_demo.py:101-168 pre-aggregation kernel pattern
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    b.placeholder("seg", "int32", [-1])
    b.const("k", np.int32(3))
    b.op("UnsortedSegmentSum", "sums", ["x", "seg", "k"])
    p = import_graphdef(b.build(), fetches=["sums"])
    tf = frame(
        {
            "x": np.array([1.0, 2.0, 3.0, 4.0]),
            "seg": np.array([0, 2, 0, 1], dtype=np.int32),
        }
    )
    out = tfs.map_blocks_trimmed(p, tf)
    np.testing.assert_allclose(out.column("sums").data, [4.0, 4.0, 2.0])


def test_depthwise_conv_multiplier_gt_one():
    # regression: kernel [H,W,C,M] must reshape WITHOUT transpose so output
    # channel c*M+m gets x[...,c] * w[...,c,m] (TF depthwise semantics)
    from tensorframes_tpu.graphdef.ops import REGISTRY

    x = np.array([[[[1.0, 10.0]]]], np.float32)
    w = np.array([[[[1.0, 2.0], [3.0, 4.0]]]], np.float32)
    out = np.asarray(
        REGISTRY["DepthwiseConv2dNative"]([x, w], {})
    ).ravel()
    np.testing.assert_allclose(out, [1.0, 2.0, 30.0, 40.0])


def test_empty_reduction_indices_is_identity():
    # regression: TF Sum with reduction_indices=[] is the identity
    from tensorframes_tpu.graphdef.ops import REGISTRY

    r = REGISTRY["Sum"](
        [np.ones((2, 3), np.float32), np.array([], np.int32)], {}
    )
    assert np.asarray(r).shape == (2, 3)


def test_deep_graph_no_recursion_limit():
    # regression: Inception-scale op chains must not hit Python recursion
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    prev = "x"
    for i in range(600):
        prev = b.op("Identity", f"n{i}", [prev])
    p = import_graphdef(b.build(), fetches=[prev])
    out = tfs.map_blocks(p, frame({"x": np.arange(3.0)}))
    np.testing.assert_allclose(out.column(prev).data, np.arange(3.0))


def test_cycle_detected_at_import():
    b = GraphBuilder()
    b.placeholder("p", "float64", [-1])
    b.op("Add", "a", ["p", "b"])
    b.op("Add", "b", ["a", "p"])
    with pytest.raises(GraphImportError, match="cycle"):
        import_graphdef(b.build(), fetches=["a"])


def test_feed_dict_on_imported_program():
    # regression: feed_dict passed at verb level must apply to Programs
    b = GraphBuilder()
    b.placeholder("p", "float64", [-1])
    b.const("c", np.float64(1.0))
    b.op("Add", "z", ["p", "c"])
    p = import_graphdef(b.build(), fetches=["z"])
    out = tfs.map_blocks(p, frame({"x": np.arange(3.0)}), feed_dict={"p": "x"})
    np.testing.assert_allclose(out.column("z").data, np.arange(3.0) + 1)


def test_placeholder_pruning():
    b = GraphBuilder()
    b.placeholder("used", "float64", [-1])
    b.placeholder("unused", "float64", [-1])
    b.const("c", np.float64(1.0))
    b.op("Add", "z", ["used", "c"])
    p = import_graphdef(b.build(), fetches=["z"])
    assert p.input_names == ["used"]


def test_import_errors():
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    b.op("Identity", "y", ["x"])
    g = b.build()
    with pytest.raises(GraphImportError, match="not found"):
        import_graphdef(g, fetches=["nope"])
    with pytest.raises(GraphImportError, match="unknown placeholder"):
        import_graphdef(g, fetches=["y"], inputs={"bogus": "x"})
    b2 = GraphBuilder()
    b2.placeholder("x", "float64", [-1])
    b2.op("SomeExoticOp", "y", ["x"])
    p2 = import_graphdef(b2.build(), fetches=["y"])
    with pytest.raises(UnsupportedOpError, match="SomeExoticOp"):
        tfs.map_blocks(p2, frame({"x": np.arange(3.0)}))


def test_placeholder_specs():
    b = GraphBuilder()
    b.placeholder("x", "float32", [-1, 3])
    specs = placeholder_specs(b.build())
    st, shape = specs["x"]
    assert st.name == "float32"
    assert shape == (tfs.UNKNOWN, 3)


def test_load_graphdef_from_file(tmp_path):
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    b.const("c", np.float64(5.0))
    b.op("Add", "z", ["x", "c"])
    path = tmp_path / "g.pb"
    path.write_bytes(b.to_bytes())
    g = load_graphdef(path)
    assert isinstance(g, GraphDef)
    p = import_graphdef(g, fetches=["z"])
    out = tfs.map_blocks(p, frame({"x": np.arange(3.0)}))
    np.testing.assert_allclose(out.column("z").data, np.arange(3.0) + 5)


# --------------------------------------------------- review regressions --


def test_batch_matmul_adjoint_attrs():
    # adj_x/adj_y must transpose the last two dims (TF BatchMatMulV2 attrs)
    a = np.arange(4.0).reshape(1, 2, 2)
    bm = np.array([[[1.0, 2.0], [3.0, 4.0]]])
    for opname in ("BatchMatMul", "BatchMatMulV2"):
        b = GraphBuilder()
        b.placeholder("a", "float64", [-1, 2, 2])
        b.const("w", bm[0])
        b.op(opname, "z", ["a", "w"], adj_y=True)
        p = import_graphdef(b.build(), fetches=["z"])
        tf = frame({"a": a})
        out = tfs.map_blocks(p, tf)
        np.testing.assert_allclose(
            out.column("z").data, a @ bm.transpose(0, 2, 1)
        )


def test_packed_bool_list_attr_roundtrip():
    import tensorframes_tpu.graphdef.proto as proto
    import tensorframes_tpu.graphdef.wire as wire

    # TF writers emit `repeated bool b = 5 [packed = true]` as one blob
    packed = bytearray()
    wire.write_len_field(packed, 5, b"\x01\x00\x01")
    list_value = bytearray()
    wire.write_len_field(list_value, 1, bytes(packed))
    av = proto.AttrValue.parse(bytes(list_value))
    assert av.kind == "list"
    assert av.value == [True, False, True]


def test_float_range_lowering():
    b = GraphBuilder()
    b.placeholder("x", "float64", [-1])
    b.const("start", np.float64(0.0))
    b.const("limit", np.float64(1.0))
    b.const("delta", np.float64(0.25))
    b.op("Range", "r", ["start", "limit", "delta"])
    b.op("Sum", "s", ["r", b.const("axis", np.int32(0))])
    b.op("Mul", "z", ["x", "s"])
    p = import_graphdef(b.build(), fetches=["z"])
    out = tfs.map_blocks(p, frame({"x": np.ones(3)}))
    np.testing.assert_allclose(out.column("z").data, np.full(3, 1.5))


# ------------------------------------------- frozen conv-net scoring e2e --


def test_frozen_convnet_scoring_end_to_end():
    """A complete frozen conv-net GraphDef (conv / folded-BN / pooling /
    dense head / softmax / argmax) scored through ``map_blocks`` over a raw
    uint8 image column — the reference's flagship model-scoring contract
    (``read_image.py:108-167``: restore -> freeze -> feed image rows), with
    the in-graph Cast/normalise replacing the host-side decode."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tensorframes_tpu import OpBuilder
    from tensorframes_tpu.graphdef.proto import AttrValue
    from tensorframes_tpu import dtypes as dt

    rng = np.random.RandomState(42)
    n, side = 6, 16
    images = rng.randint(0, 256, size=(n, side, side, 3), dtype=np.uint8)

    w1 = rng.randn(3, 3, 3, 8).astype(np.float32) * 0.2
    bn_scale = rng.rand(8).astype(np.float32) + 0.5
    bn_offset = rng.randn(8).astype(np.float32) * 0.1
    bn_mean = rng.randn(8).astype(np.float32) * 0.1
    bn_var = rng.rand(8).astype(np.float32) + 0.5
    w2 = rng.randn(3, 3, 8, 16).astype(np.float32) * 0.2
    b2 = rng.randn(16).astype(np.float32) * 0.1
    wfc = rng.randn(16, 10).astype(np.float32) * 0.3
    bfc = rng.randn(10).astype(np.float32) * 0.1

    g = GraphBuilder()
    g.placeholder("image", "uint8", [-1, side, side, 3])
    g.op(
        "Cast", "to_float", ["image"],
        DstT=AttrValue("type", dt.by_name("float32").tf_enum),
    )
    g.const("half_range", np.float32(127.5))
    g.op("RealDiv", "scaled", ["to_float", "half_range"])
    g.const("one", np.float32(1.0))
    g.op("Sub", "normed", ["scaled", "one"])
    g.const("w1", w1)
    g.op(
        "Conv2D", "conv1", ["normed", "w1"],
        strides=[1, 2, 2, 1], padding=b"SAME",
    )
    g.const("bn_scale", bn_scale)
    g.const("bn_offset", bn_offset)
    g.const("bn_mean", bn_mean)
    g.const("bn_var", bn_var)
    g.op(
        "FusedBatchNormV3", "bn1",
        ["conv1", "bn_scale", "bn_offset", "bn_mean", "bn_var"],
        epsilon=1e-3,
    )
    g.op("Relu", "act1", ["bn1"])
    g.op(
        "MaxPool", "pool1", ["act1"],
        ksize=[1, 2, 2, 1], strides=[1, 2, 2, 1], padding=b"VALID",
    )
    g.const("w2", w2)
    g.op(
        "Conv2D", "conv2", ["pool1", "w2"],
        strides=[1, 1, 1, 1], padding=b"SAME",
    )
    g.const("b2", b2)
    g.op("BiasAdd", "bias2", ["conv2", "b2"])
    g.op("Relu", "act2", ["bias2"])
    g.const("gap_axes", np.asarray([1, 2], np.int32))
    g.op("Mean", "gap", ["act2", "gap_axes"])
    g.const("wfc", wfc)
    g.op("MatMul", "fc", ["gap", "wfc"])
    g.const("bfc", bfc)
    g.op("BiasAdd", "logits", ["fc", "bfc"])
    g.op("Softmax", "probs", ["logits"])
    g.const("argmax_axis", np.int32(1))
    g.op("ArgMax", "prediction", ["logits", "argmax_axis"])

    # serialize -> wire bytes -> re-parse: the full GraphDef transport path
    graph_bytes = g.to_bytes()

    out = (
        OpBuilder.map_blocks(frame({"image_data": images}, blocks=2))
        .graph(graph_bytes)
        .fetches(["probs", "prediction"])
        .inputs({"image": "image_data"})
        .build_df()
    )

    # oracle: same computation straight through jax
    x = images.astype(np.float32) / 127.5 - 1.0
    y = lax.conv_general_dilated(
        x, w1, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    inv = bn_scale / np.sqrt(bn_var + 1e-3)
    y = np.asarray(y) * inv + (bn_offset - bn_mean * inv)
    y = np.maximum(y, 0)
    y = np.asarray(
        lax.reduce_window(y, -np.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    )
    y = np.asarray(
        lax.conv_general_dilated(
            y, w2, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
    )
    y = np.maximum(y + b2, 0)
    gap = y.mean(axis=(1, 2))
    logits = gap @ wfc + bfc
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    pred = logits.argmax(axis=1)

    np.testing.assert_allclose(
        np.asarray(out.column("probs").data), probs, rtol=2e-4, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(out.column("prediction").data), pred
    )
    # passthrough column (non-trimmed map keeps inputs)
    assert "image_data" in out.column_names


def test_frozen_mlp_scored_via_map_rows():
    """BASELINE config #3: per-row inference of a frozen MLP GraphDef (the
    MNIST-style read_image.py flow, row variant) — the cell-level program is
    vmapped over rows by the engine."""
    rng = np.random.RandomState(7)
    d, h, classes = 16, 32, 10
    w1 = rng.randn(d, h).astype(np.float32) * 0.3
    b1 = rng.randn(h).astype(np.float32) * 0.1
    w2 = rng.randn(h, classes).astype(np.float32) * 0.3
    b2 = rng.randn(classes).astype(np.float32) * 0.1

    g = GraphBuilder()
    # cell-level graph: one example [1, d] per row (MatMul needs rank 2)
    g.placeholder("pixels", "float32", [1, d])
    g.const("w1", w1)
    g.op("MatMul", "h1", ["pixels", "w1"])
    g.const("b1", b1)
    g.op("BiasAdd", "h1b", ["h1", "b1"])
    g.op("Relu", "act", ["h1b"])
    g.const("w2", w2)
    g.op("MatMul", "h2", ["act", "w2"])
    g.const("b2", b2)
    g.op("BiasAdd", "logits", ["h2", "b2"])
    g.const("axis", np.int32(1))
    g.op("ArgMax", "prediction", ["logits", "axis"])

    n = 6
    x = rng.randn(n, 1, d).astype(np.float32)
    frame_rows = tfs.analyze(
        tfs.TensorFrame.from_arrays({"image_data": x})
    )
    p = import_graphdef(
        g.build(), fetches=["prediction"], inputs={"pixels": "image_data"}
    )
    out = tfs.map_rows(p, frame_rows)
    logits = np.maximum(x[:, 0] @ w1 + b1, 0) @ w2 + b2
    np.testing.assert_array_equal(
        np.asarray(out.column("prediction").data).reshape(n),
        logits.argmax(1),
    )


# ---------------------------------------------------------------------------
# round-5 registry growth (VERDICT r4 next #5): the TF-1.x inference
# closure — image ops, splits, top-k, cumulative and elementwise closure
# ---------------------------------------------------------------------------


def _run_graph(build, feeds, fetches):
    b = GraphBuilder()
    build(b)
    p = import_graphdef(b.build(), fetches=fetches)
    tf = frame(feeds)
    out = tfs.map_blocks(p, tf, trim=True)
    return {f: np.asarray(out.column(f.split(":")[0]).data) for f in fetches}


def test_resize_bilinear_legacy_convention():
    """TF-1.x legacy kernel: src = out_idx * in/out (no half-pixel).  A
    2x upscale of [0, 1] must produce [0, 0.5, 1, 1] (edge clamp), which
    the half-pixel convention would NOT."""
    x = np.asarray([[[[0.0], [1.0]]]], np.float32)  # [1, 1, 2, 1]

    def build(b):
        b.placeholder("x", "float32", [-1, 1, 2, 1])
        b.const("size", np.asarray([1, 4], np.int32))
        b.op("ResizeBilinear", "y", ["x", "size"])

    out = _run_graph(build, {"x": x}, ["y"])
    np.testing.assert_allclose(
        out["y"].reshape(-1), [0.0, 0.5, 1.0, 1.0], atol=1e-6
    )


def test_resize_bilinear_align_corners():
    x = np.asarray([[[[0.0], [3.0]]]], np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 1, 2, 1])
        b.const("size", np.asarray([1, 4], np.int32))
        b.op("ResizeBilinear", "y", ["x", "size"], align_corners=True)

    out = _run_graph(build, {"x": x}, ["y"])
    np.testing.assert_allclose(
        out["y"].reshape(-1), [0.0, 1.0, 2.0, 3.0], atol=1e-6
    )


def test_lrn_matches_definition():
    rng = np.random.RandomState(0)
    x = rng.randn(1, 2, 2, 8).astype(np.float32)
    r, bias, alpha, beta = 2, 1.5, 0.5, 0.75

    def build(b):
        b.placeholder("x", "float32", [-1, 2, 2, 8])
        b.op(
            "LRN", "y", ["x"],
            depth_radius=r, bias=bias, alpha=alpha, beta=beta,
        )

    out = _run_graph(build, {"x": x}, ["y"])
    want = np.empty_like(x)
    for c in range(8):
        lo, hi = max(0, c - r), min(8, c + r + 1)
        sq = (x[..., lo:hi] ** 2).sum(-1)
        want[..., c] = x[..., c] / (bias + alpha * sq) ** beta
    np.testing.assert_allclose(out["y"], want, rtol=1e-5)


def test_split_and_splitv():
    x = np.arange(24.0).reshape(2, 12).astype(np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 12])
        b.const("axis", np.int32(1))
        b.op("Split", "parts", ["axis", "x"], num_split=3)
        b.const("sizes", np.asarray([2, 4, 6], np.int32))
        b.const("axis2", np.int32(1))
        b.op("SplitV", "vparts", ["x", "sizes", "axis2"])
        b.op("Identity", "s1", ["parts:1"])
        b.op("Identity", "v2", ["vparts:2"])

    out = _run_graph(build, {"x": x}, ["s1", "v2"])
    np.testing.assert_allclose(out["s1"], x[:, 4:8])
    np.testing.assert_allclose(out["v2"], x[:, 6:])


def test_topkv2():
    x = np.asarray([[3.0, 1.0, 4.0, 1.5], [2.0, 9.0, 7.0, 1.0]], np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 4])
        b.const("k", np.int32(2))
        b.op("TopKV2", "tk", ["x", "k"])
        b.op("Identity", "vals", ["tk:0"])
        b.op("Identity", "idx", ["tk:1"])

    out = _run_graph(build, {"x": x}, ["vals", "idx"])
    np.testing.assert_allclose(out["vals"], [[4.0, 3.0], [9.0, 7.0]])
    np.testing.assert_array_equal(out["idx"], [[2, 0], [1, 2]])
    assert out["idx"].dtype == np.int32


def test_cumsum_exclusive_reverse():
    x = np.asarray([[1.0, 2.0, 3.0, 4.0]], np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 4])
        b.const("ax", np.int32(1))
        b.op("Cumsum", "plain", ["x", "ax"])
        b.const("ax2", np.int32(1))
        b.op("Cumsum", "excl", ["x", "ax2"], exclusive=True)
        b.const("ax3", np.int32(1))
        b.op("Cumsum", "rev", ["x", "ax3"], reverse=True)

    out = _run_graph(build, {"x": x}, ["plain", "excl", "rev"])
    np.testing.assert_allclose(out["plain"], [[1, 3, 6, 10]])
    np.testing.assert_allclose(out["excl"], [[0, 1, 3, 6]])
    np.testing.assert_allclose(out["rev"], [[10, 9, 7, 4]])


def test_one_hot_depth_to_space_gather_nd():
    idx = np.asarray([[0], [2]], np.int32)

    def build(b):
        b.placeholder("i", "int32", [-1, 1])
        b.const("depth", np.int32(3))
        b.const("on", np.float32(5.0))
        b.const("off", np.float32(-1.0))
        b.op("OneHot", "oh", ["i", "depth", "on", "off"])

    out = _run_graph(build, {"i": idx}, ["oh"])
    np.testing.assert_allclose(
        out["oh"],
        [[[5.0, -1.0, -1.0]], [[-1.0, -1.0, 5.0]]],
    )

    x = np.arange(16.0).reshape(1, 2, 2, 4).astype(np.float32)

    def build2(b):
        b.placeholder("x", "float32", [-1, 2, 2, 4])
        b.op("DepthToSpace", "d2s", ["x"], block_size=2)
        b.op("SpaceToDepth", "back", ["d2s"], block_size=2)

    out2 = _run_graph(build2, {"x": x}, ["d2s", "back"])
    assert out2["d2s"].shape == (1, 4, 4, 1)
    np.testing.assert_allclose(out2["back"], x)  # inverse pair


def test_elementwise_closure_ops():
    x = np.asarray([[-1.5, 0.25, 2.0]], np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 3])
        b.op("Floor", "fl", ["x"])
        b.op("LeakyRelu", "lr", ["x"], alpha=0.1)
        b.op("Reciprocal", "rc", ["x"])
        b.op("Erf", "erf", ["x"])
        b.const("c", np.float32(2.0))
        b.op("Atan2", "at2", ["x", "c"])
        b.const("lo", np.float32(-1.0))
        b.const("hi", np.float32(1.0))
        b.op("ClipByValue", "cl", ["x", "lo", "hi"])

    out = _run_graph(
        build, {"x": x}, ["fl", "lr", "rc", "erf", "at2", "cl"]
    )
    np.testing.assert_allclose(out["fl"], np.floor(x))
    np.testing.assert_allclose(
        out["lr"], np.where(x > 0, x, 0.1 * x), rtol=1e-6
    )
    np.testing.assert_allclose(out["rc"], 1.0 / x, rtol=1e-6)
    import math

    np.testing.assert_allclose(
        out["erf"],
        np.vectorize(math.erf)(x).astype(np.float32),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        out["at2"], np.arctan2(x, 2.0), rtol=1e-6
    )
    np.testing.assert_allclose(out["cl"], np.clip(x, -1, 1))


def test_invert_permutation_traced_input():
    """Regression (r5 review): InvertPermutation must accept a TRACED
    permutation (e.g. TopKV2 indices), not just Const-folded ones."""
    x = np.asarray([[0.3, 0.1, 0.4, 0.2]], np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 4])
        b.const("k", np.int32(4))
        b.op("TopKV2", "tk", ["x", "k"])
        b.op("InvertPermutation", "rank0", ["tk:1"])

    # rank of each element = inverse of the sort permutation
    out = _run_graph(build, {"x": x}, ["rank0"])
    np.testing.assert_array_equal(out["rank0"], [[1, 3, 0, 2]])
    assert out["rank0"].dtype == np.int32


def test_conv2d_backprop_input_deconv():
    """Deconv (Conv2DBackpropInput as a forward op) matches the TF
    definition: the adjoint of the corresponding Conv2D."""
    rng = np.random.RandomState(0)
    w = rng.randn(3, 3, 2, 4).astype(np.float32)  # [H,W,Cin,Cout]
    dy = rng.randn(1, 4, 4, 4).astype(np.float32)

    def build(b):
        b.const("sizes", np.asarray([1, 8, 8, 2], np.int32))
        b.const("w", w)
        b.placeholder("dy", "float32", [-1, 4, 4, 4])
        b.op(
            "Conv2DBackpropInput", "dx", ["sizes", "w", "dy"],
            strides=[1, 2, 2, 1], padding=b"SAME",
        )

    out = _run_graph(build, {"dy": dy}, ["dx"])
    assert out["dx"].shape == (1, 8, 8, 2)
    _assert_deconv_matches_vjp(out["dx"], w, dy, (1, 8, 8, 2), (2, 2), "SAME")


def _assert_deconv_matches_vjp(dx, w, dy, in_shape, strides, padding, dil=(1, 1)):
    """Oracle: the vjp of the corresponding forward conv."""
    import jax
    from jax import lax

    def fwd(x):
        return lax.conv_general_dilated(
            x, w, strides, padding, rhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    x0 = np.zeros(in_shape, np.float32)
    _, vjp = jax.vjp(fwd, x0)
    np.testing.assert_allclose(
        dx, np.asarray(vjp(dy)[0]), rtol=1e-4, atol=1e-5
    )


def test_conv2d_backprop_input_odd_same_and_dilated():
    """r5 review regressions: odd SAME input sizes (the DeepLab 65x65
    class — here 9 with stride 2) and dilated deconvs must both lower
    exactly, not get rejected or silently mis-computed."""
    rng = np.random.RandomState(2)
    # odd SAME, stride 2: Hi=9 -> Ho=5
    w = rng.randn(3, 3, 2, 4).astype(np.float32)
    dy = rng.randn(1, 5, 5, 4).astype(np.float32)

    def build(b):
        b.const("sizes", np.asarray([1, 9, 9, 2], np.int32))
        b.const("w", w)
        b.placeholder("dy", "float32", [-1, 5, 5, 4])
        b.op(
            "Conv2DBackpropInput", "dx", ["sizes", "w", "dy"],
            strides=[1, 2, 2, 1], padding=b"SAME",
        )

    out = _run_graph(build, {"dy": dy}, ["dx"])
    _assert_deconv_matches_vjp(out["dx"], w, dy, (1, 9, 9, 2), (2, 2), "SAME")

    # dilated deconv, stride 1
    dy2 = rng.randn(1, 8, 8, 4).astype(np.float32)

    def build2(b):
        b.const("sizes", np.asarray([1, 8, 8, 2], np.int32))
        b.const("w", w)
        b.placeholder("dy", "float32", [-1, 8, 8, 4])
        b.op(
            "Conv2DBackpropInput", "dx", ["sizes", "w", "dy"],
            strides=[1, 1, 1, 1], padding=b"SAME",
            dilations=[1, 2, 2, 1],
        )

    out2 = _run_graph(build2, {"dy": dy2}, ["dx"])
    _assert_deconv_matches_vjp(
        out2["dx"], w, dy2, (1, 8, 8, 2), (1, 1), "SAME", dil=(2, 2)
    )

    # VALID deconv
    dy3 = rng.randn(1, 3, 3, 4).astype(np.float32)

    def build3(b):
        b.const("sizes", np.asarray([1, 7, 7, 2], np.int32))
        b.const("w", w)
        b.placeholder("dy", "float32", [-1, 3, 3, 4])
        b.op(
            "Conv2DBackpropInput", "dx", ["sizes", "w", "dy"],
            strides=[1, 2, 2, 1], padding=b"VALID",
        )

    out3 = _run_graph(build3, {"dy": dy3}, ["dx"])
    _assert_deconv_matches_vjp(
        out3["dx"], w, dy3, (1, 7, 7, 2), (2, 2), "VALID"
    )


def test_space_batch_nd_round_trip_and_semantics():
    """SpaceToBatchND/BatchToSpaceND: inverse pair, and parity with the
    reshape/transpose definition on an asymmetric-pad case."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 5, 7, 3).astype(np.float32)

    def build(b):
        b.placeholder("x", "float32", [-1, 5, 7, 3])
        b.const("block", np.asarray([2, 2], np.int32))
        b.const("pads", np.asarray([[1, 0], [0, 1]], np.int32))
        b.op("SpaceToBatchND", "s2b", ["x", "block", "pads"])
        b.const("block2", np.asarray([2, 2], np.int32))
        b.const("crops", np.asarray([[1, 0], [0, 1]], np.int32))
        b.op("BatchToSpaceND", "back", ["s2b", "block2", "crops"])

    # trimmed maps require agreeing row counts; fetch separately
    out = _run_graph(build, {"x": x}, ["s2b"])
    out.update(_run_graph(build, {"x": x}, ["back"]))
    assert out["s2b"].shape == (8, 3, 4, 3)
    np.testing.assert_allclose(out["back"], x, rtol=0)
    # spot semantics: batch index (b1*2+b2)*N+n holds rows b1::2, cols b2::2
    padded = np.pad(x, [(0, 0), (1, 0), (0, 1), (0, 0)])
    np.testing.assert_allclose(
        out["s2b"][0], padded[0, 0::2, 0::2, :], rtol=0
    )
    np.testing.assert_allclose(
        out["s2b"][3 * 2], padded[0, 1::2, 1::2, :], rtol=0
    )


class TestStaticCond:
    """v1 Switch/Merge with constant predicates (the frozen tf.cond
    residue): the branch resolves at import time, the dead branch never
    executes, and non-static predicates fail with guidance."""

    def _cond_graph(self, pred_value):
        g = GraphBuilder()
        g.placeholder("x", "float64", [4])
        g.const("pred", np.bool_(pred_value))
        g.op("Switch", "sw", ["x", "pred"])
        g.op("Mul", "false_branch", ["sw:0", g.const("two", np.float64(2.0))])
        g.op("Add", "true_branch", ["sw:1", g.const("one", np.float64(1.0))])
        g.op("Merge", "m", ["false_branch", "true_branch"])
        g.op("Neg", "out", ["m"])
        return g.to_bytes()

    def test_true_branch_taken(self):
        p = import_graphdef(self._cond_graph(True), fetches=["out", "m:1"])
        res = p.call({"x": np.arange(4.0)})
        np.testing.assert_allclose(
            np.asarray(res["out"]), -(np.arange(4.0) + 1.0))
        assert int(np.asarray(res["m_1"])) == 1  # value_index

    def test_false_branch_taken(self):
        p = import_graphdef(self._cond_graph(False), fetches=["out"])
        res = p.call({"x": np.arange(4.0)})
        np.testing.assert_allclose(
            np.asarray(res["out"]), -(np.arange(4.0) * 2.0))

    def test_dead_branch_never_executes(self, monkeypatch):
        """The untaken branch's op must not run (TF dead-tensor rule)."""
        from tensorframes_tpu.graphdef import ops as op_mod

        calls = []
        orig = op_mod.REGISTRY["Mul"]
        monkeypatch.setitem(
            op_mod.REGISTRY, "Mul",
            lambda ins, at: calls.append(1) or orig(ins, at))
        p = import_graphdef(self._cond_graph(True), fetches=["out"])
        p.call({"x": np.arange(4.0)})
        assert not calls  # Mul lives only in the (dead) false branch

    def test_fetching_dead_branch_errors(self):
        p = import_graphdef(
            self._cond_graph(True), fetches=["false_branch"])
        with pytest.raises(GraphImportError, match="statically-dead"):
            p.call({"x": np.arange(4.0)})

    def test_const_returning_branches_via_control_edges(self):
        """TF's cond ties const branch values to the Switch only through
        control edges (^switch_t / ^switch_f pivots); deadness must
        follow control edges or both Merge inputs stay live."""
        g = GraphBuilder()
        g.placeholder("x", "float64", [2])
        g.const("pred", np.bool_(True))
        g.op("Switch", "sw", ["x", "pred"])
        g.op("Identity", "switch_f", ["sw:0"])
        g.op("Identity", "switch_t", ["sw:1"])
        g.const("cf", np.float64(-2.5))
        g.const("ct", np.float64(7.5))
        g.op("Identity", "fv", ["cf", "^switch_f"])
        g.op("Identity", "tv", ["ct", "^switch_t"])
        g.op("Merge", "m", ["fv", "tv"])
        p = import_graphdef(g.to_bytes(), fetches=["m"])
        assert float(np.asarray(p.call({"x": np.zeros(2)})["m"])) == 7.5

    def test_nested_cond_in_dead_branch(self):
        """An inner cond living entirely inside the outer's dead branch
        must itself go dead (0 live Merge inputs -> propagate, not
        raise)."""
        g = GraphBuilder()
        g.placeholder("x", "float64", [2])
        g.const("outer_p", np.bool_(True))
        g.op("Switch", "osw", ["x", "outer_p"])
        # dead outer-false branch contains a whole inner cond
        g.const("inner_p", np.bool_(False))
        g.op("Switch", "isw", ["osw:0", "inner_p"])
        g.op("Neg", "inf_", ["isw:0"])
        g.op("Abs", "int_", ["isw:1"])
        g.op("Merge", "im", ["inf_", "int_"])
        # live outer-true branch
        g.op("Mul", "tv", ["osw:1", g.const("three", np.float64(3.0))])
        g.op("Merge", "om", ["im", "tv"])
        p = import_graphdef(g.to_bytes(), fetches=["om"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.asarray([1.0, 2.0])})["om"]),
            [3.0, 6.0])

    def test_concrete_fed_predicate_specializes_eagerly(self):
        """A pred fed as a concrete host value resolves per call (eager
        eval sees real numpy, like constant folding does)."""
        g = GraphBuilder()
        g.placeholder("x", "float64", [4])
        g.placeholder("p", "bool", [])
        g.op("Switch", "sw", ["x", "p"])
        g.op("Merge", "m", ["sw:0", "sw:1"])
        p = import_graphdef(g.to_bytes(), fetches=["m"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.arange(4.0),
                               "p": np.bool_(True)})["m"]),
            np.arange(4.0))

    def test_traced_predicate_rejected(self):
        """Under jit (the verb path) the predicate is a tracer — the
        static-cond contract must fail loudly, not silently pick."""
        import jax

        g = GraphBuilder()
        g.placeholder("x", "float64", [4])
        g.placeholder("p", "bool", [])
        g.op("Switch", "sw", ["x", "p"])
        g.op("Merge", "m", ["sw:0", "sw:1"])
        p = import_graphdef(g.to_bytes(), fetches=["m"])
        with pytest.raises(UnsupportedOpError, match="data-dependent"):
            jax.jit(lambda x, pr: p.call({"x": x, "p": pr}))(
                np.arange(4.0), np.bool_(True))


class TestFunctionConds:
    """TF2 control flow: StatelessIf/If call branch FunctionDefs from the
    graph library; constant predicates resolve statically (the modern
    frozen-graph counterpart of the v1 Switch/Merge residue)."""

    def _if_graph(self, pred_value):
        from tensorframes_tpu.graphdef.proto import (
            AttrValue, FunctionDef, GraphDef, NodeDef,
        )

        then_fd = FunctionDef(
            "tb", [("ax", 2)], [("r", 2)],
            [
                NodeDef("c", "Const", [], {
                    "value": AttrValue(
                        "tensor", TensorProto.from_numpy(np.float64(1.0))),
                    "dtype": AttrValue("type", 2),
                }),
                NodeDef("add", "Add", ["ax", "c:output:0"], {}),
            ],
            {"r": "add:z:0"},
        )
        else_fd = FunctionDef(
            "eb", [("ax", 2)], [("r", 2)],
            [NodeDef("m", "Mul", ["ax", "ax"], {})],
            {"r": "m:z:0"},
        )
        nodes = [
            NodeDef("x", "Placeholder", [],
                    {"dtype": AttrValue("type", 2)}),
            NodeDef("p", "Const", [], {
                "value": AttrValue(
                    "tensor", TensorProto.from_numpy(np.bool_(pred_value))),
                "dtype": AttrValue("type", 10),
            }),
            NodeDef("cond", "StatelessIf", ["p", "x"], {
                "then_branch": AttrValue("func", ("tb", {})),
                "else_branch": AttrValue("func", ("eb", {})),
            }),
            NodeDef("out", "Identity", ["cond"], {}),
        ]
        return GraphDef(nodes, {"tb": then_fd, "eb": else_fd})

    def test_then_branch(self):
        p = import_graphdef(self._if_graph(True), fetches=["out"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.arange(3.0)})["out"]),
            np.arange(3.0) + 1.0)

    def test_else_branch(self):
        p = import_graphdef(self._if_graph(False), fetches=["out"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.arange(3.0)})["out"]),
            np.arange(3.0) ** 2)

    def test_library_wire_fixpoint(self):
        """The library (signature, bodies, ret maps, func attrs) survives
        encode -> parse byte-stably."""
        g = self._if_graph(True)
        data = g.encode()
        g2 = parse_graphdef(data)
        assert sorted(g2.functions) == ["eb", "tb"]
        fd = g2.functions["tb"]
        assert fd.input_args == [("ax", 2)]
        assert fd.output_args == [("r", 2)]
        assert fd.ret == {"r": "add:z:0"}
        assert [n.op for n in fd.nodes] == ["Const", "Add"]
        cond = g2.node_map()["cond"]
        assert cond.attrs["then_branch"].kind == "func"
        assert cond.attrs["then_branch"].value[0] == "tb"
        assert g2.encode() == data
        # and the re-parsed graph still executes
        p = import_graphdef(g2, fetches=["out"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.arange(3.0)})["out"]),
            np.arange(3.0) + 1.0)

    def test_traced_predicate_rejected(self):
        import jax

        from tensorframes_tpu.graphdef.proto import (
            AttrValue, GraphDef, NodeDef,
        )

        g = self._if_graph(True)
        nodes = [n for n in g.nodes if n.name not in ("p",)]
        nodes.insert(1, NodeDef("p", "Placeholder", [],
                                {"dtype": AttrValue("type", 10)}))
        g2 = GraphDef(nodes, g.functions)
        p = import_graphdef(g2, fetches=["out"])
        with pytest.raises(UnsupportedOpError, match="data-dependent"):
            jax.jit(lambda x, pr: p.call({"x": x, "p": pr}))(
                np.arange(3.0), np.bool_(True))

    def test_non_scalar_predicate_names_the_node(self):
        """A vector-valued constant predicate must raise GraphImportError
        naming the If node, not numpy's opaque truth-value-ambiguous
        ValueError (round-6 regression, ADVICE r5)."""
        from tensorframes_tpu.graphdef.proto import (
            AttrValue, GraphDef, NodeDef,
        )

        g = self._if_graph(True)
        nodes = [n for n in g.nodes if n.name != "p"]
        nodes.insert(1, NodeDef("p", "Const", [], {
            "value": AttrValue(
                "tensor",
                TensorProto.from_numpy(np.array([True, False]))),
            "dtype": AttrValue("type", 10),
        }))
        g2 = GraphDef(nodes, g.functions)
        with pytest.raises(GraphImportError, match="cond.*shape \\(2,\\)"):
            p = import_graphdef(g2, fetches=["out"])
            p.call({"x": np.arange(3.0)})

    def test_complete_for_tf_preserves_functions(self):
        """``complete_for_tf`` must carry the FunctionDefLibrary through —
        dropping it leaves StatelessIf/If with dangling function refs that
        real TF rejects (round-6 regression, ADVICE r5 medium)."""
        from tensorframes_tpu.graphdef.tfcompat import complete_for_tf

        g = self._if_graph(True)
        done = complete_for_tf(g)
        assert sorted(done.functions) == ["eb", "tb"]
        assert done.functions["tb"].ret == {"r": "add:z:0"}
        # the library dict is a copy, not shared mutable state
        done.functions["extra"] = done.functions["tb"]
        assert "extra" not in g.functions
        # the attr-completed graph still encodes with its library and the
        # re-parsed bytes still import and execute the then-branch
        g2 = parse_graphdef(done.encode())
        assert sorted(g2.functions) == ["eb", "tb"]
        p = import_graphdef(g2, fetches=["out"])
        np.testing.assert_allclose(
            np.asarray(p.call({"x": np.arange(3.0)})["out"]),
            np.arange(3.0) + 1.0)


# ------------------------------------------------- tfcompat attr filling --


def test_complete_for_tf_out_of_range_output_leaves_attr_unset():
    """A consumer referencing an output index beyond what the producer's
    attrs define (e.g. Unpack missing ``num``) must NOT get a guessed
    dtype attr stamped from output 0 — best-effort means leaving the attr
    for TF's own importer to reject or default (round-6 regression)."""
    from tensorframes_tpu.graphdef.proto import AttrValue, NodeDef
    from tensorframes_tpu.graphdef.tfcompat import complete_for_tf

    nodes = [
        NodeDef("x", "Placeholder", [], {"dtype": AttrValue("type", 2)}),
        # no ``num`` attr: the pass cannot know Unpack's output arity and
        # assumes 1 output
        NodeDef("u", "Unpack", ["x"], {}),
        NodeDef("keep", "Identity", ["u:0"], {}),
        NodeDef("oob", "Identity", ["u:2"], {}),
    ]
    done = complete_for_tf(GraphDef(nodes)).node_map()
    assert done["keep"].attrs["T"].value == 2
    assert "T" not in done["oob"].attrs


# -------------------------------------- function-body output refs (r8) --


def test_function_output_arg_index_not_dropped(monkeypatch):
    """A ``node:arg:idx`` body ref must honour the index WITHIN a sized
    output arg: flat slot = named arg's position + idx.  Round-8
    regression — idx was dropped for ``_OUTPUT_ARGS`` ops, so any future
    number_attr-sized output arg would silently alias its slot 0."""
    from tensorframes_tpu.graphdef import importer as imp
    from tensorframes_tpu.graphdef import ops as op_registry
    from tensorframes_tpu.graphdef.proto import AttrValue, FunctionDef, NodeDef

    def fake_multi(ins, attrs):
        (x,) = ins
        # output args ("first", "parts"): first is one tensor, parts is a
        # number_attr-sized pair -> flat tuple (first, parts[0], parts[1])
        return (x + 1.0, x + 2.0, x + 3.0)

    monkeypatch.setitem(op_registry.REGISTRY, "FakeMultiOut", fake_multi)
    monkeypatch.setitem(
        imp._OUTPUT_ARGS, "FakeMultiOut", ("first", "parts")
    )
    fd = FunctionDef(
        "fb",
        [("ax", 2)],
        [("r", 2), ("r2", 2)],
        [NodeDef("m", "FakeMultiOut", ["ax"], {})],
        {"r": "m:parts:1", "r2": "m:first:0"},
    )
    nodes = [
        NodeDef("x", "Placeholder", [], {"dtype": AttrValue("type", 2)}),
        NodeDef(
            "call",
            "PartitionedCall",
            ["x"],
            {"f": AttrValue("func", ("fb", {}))},
        ),
    ]
    g = GraphDef(nodes, {"fb": fd})
    p = import_graphdef(g, fetches=["call:0", "call:1"])
    out = p.call({"x": np.arange(3.0)})
    # parts:1 is the SECOND tensor of the sized arg -> flat slot 2 (x+3),
    # not the arg's slot 1 (x+2) the dropped-index bug returned
    np.testing.assert_allclose(np.asarray(out["call"]), np.arange(3.0) + 3.0)
    np.testing.assert_allclose(
        np.asarray(out["call_1"]), np.arange(3.0) + 1.0
    )


def test_function_output_arg_inner_index_on_nonfinal_arg_rejected(monkeypatch):
    """Indexing INTO a named output arg that precedes other args cannot
    be resolved without per-arg sizes — refuse loudly, never alias."""
    from tensorframes_tpu.graphdef import importer as imp
    from tensorframes_tpu.graphdef import ops as op_registry
    from tensorframes_tpu.graphdef.proto import AttrValue, FunctionDef, NodeDef

    monkeypatch.setitem(
        op_registry.REGISTRY, "FakeMultiOut",
        lambda ins, attrs: (ins[0], ins[0] + 1.0, ins[0] + 2.0),
    )
    monkeypatch.setitem(
        imp._OUTPUT_ARGS, "FakeMultiOut", ("parts", "last")
    )
    fd = FunctionDef(
        "fb",
        [("ax", 2)],
        [("r", 2)],
        [NodeDef("m", "FakeMultiOut", ["ax"], {})],
        {"r": "m:parts:1"},  # sized arg is NOT last: base unknowable
    )
    nodes = [
        NodeDef("x", "Placeholder", [], {"dtype": AttrValue("type", 2)}),
        NodeDef(
            "call",
            "PartitionedCall",
            ["x"],
            {"f": AttrValue("func", ("fb", {}))},
        ),
    ]
    p = import_graphdef(GraphDef(nodes, {"fb": fd}), fetches=["call:0"])
    with pytest.raises(GraphImportError, match="precedes other output"):
        p.call({"x": np.arange(3.0)})
