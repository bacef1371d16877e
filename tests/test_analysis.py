"""Static program analysis (round 17): the rowdep classifier, the
differential classifier-vs-probe fence, `tfs.check` diagnostics, the
bridge `check` RPC, the doctor rule, and the repo lint.

The differential corpus here is what `run_tests.sh lint` re-runs with
``TFS_ANALYZE_XCHECK=1`` exported: every `analysis.rows_independent`
call then runs BOTH the classifier and the exact-size compile probe and
raises on an unsound disagreement, so the corpus doubles as the
soundness fence the acceptance criteria name.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import analysis, observability
from tensorframes_tpu.analysis import rowdep
from tensorframes_tpu.ops import segment_compile
from tensorframes_tpu.program import Program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(cell=(), dt=np.float64, name="x"):
    return {name: jax.ShapeDtypeStruct((2,) + cell, np.dtype(dt))}


# ---------------------------------------------------------------------------
# lattice unit tests — one per propagation rule
# ---------------------------------------------------------------------------


def _classify(fn, cell=(), **extra_specs):
    p = Program.wrap(fn)
    specs = {}
    for n in p.input_names:
        shape = extra_specs.get(n, cell)
        specs[n] = jax.ShapeDtypeStruct((2,) + tuple(shape), np.float64)
    return rowdep.classify(p, specs)


def test_elementwise_is_row_independent():
    c = _classify(lambda x: {"z": jnp.tanh(x * 2.0 + 1.0)})
    assert c.verdict == rowdep.ROW_INDEPENDENT
    assert c.outputs == {"z": rowdep.ROW_INDEPENDENT}


def test_multi_input_elementwise():
    c = _classify(lambda x, y: {"z": x * y, "w": x - y})
    assert c.verdict == rowdep.ROW_INDEPENDENT
    assert set(c.outputs) == {"w", "z"}


def test_cell_axis_reduce_is_row_independent():
    c = _classify(lambda x: {"z": x.sum(axis=1)}, cell=(4,))
    assert c.verdict == rowdep.ROW_INDEPENDENT


def test_block_axis_reduce_is_cross_row():
    c = _classify(lambda x: {"z": x - x.sum()})
    assert c.verdict == rowdep.CROSS_ROW
    assert c.outputs == {"z": rowdep.CROSS_ROW}


def test_count_literal_is_size_dependent():
    c = _classify(lambda x: {"z": x / x.shape[0]})
    assert c.verdict == rowdep.SIZE_DEPENDENT
    assert c.outputs == {"z": rowdep.SIZE_DEPENDENT}


def test_non_whitelisted_prim_is_cross_row():
    for fn in (
        lambda x: {"z": jnp.sort(x)},
        lambda x: {"z": jnp.cumsum(x)},
    ):
        c = _classify(fn)
        assert c.verdict == rowdep.CROSS_ROW, c


def test_const_broadcast_output_is_not_independent():
    c = _classify(lambda x: {"z": jnp.zeros_like(x)})
    assert c.verdict == rowdep.CROSS_ROW


def test_row_axis_rev_is_cross_row():
    # the round-17 soundness fix: row-shaped but position-dependent —
    # BOTH layers must reject it
    p = Program.wrap(lambda x: {"z": x[::-1]})
    assert rowdep.classify(p, _spec()).verdict == rowdep.CROSS_ROW
    assert not segment_compile.rows_independent_at(p, _spec(), (3, 8))


def test_cell_axis_rev_stays_independent():
    c = _classify(lambda x: {"z": x[:, ::-1]}, cell=(4,))
    assert c.verdict == rowdep.ROW_INDEPENDENT


def test_size_branching_python_is_unknown():
    def fn(x):
        if x.shape[0] < 4:
            return {"z": x + 1.0}
        return {"z": x * 2.0}

    c = _classify(fn)
    assert c.verdict == rowdep.UNKNOWN


def test_mixed_outputs_classify_independently():
    c = _classify(lambda x: {"a": x + 1.0, "b": x - x.sum()})
    assert c.outputs["a"] == rowdep.ROW_INDEPENDENT
    assert c.outputs["b"] == rowdep.CROSS_ROW
    assert c.verdict == rowdep.CROSS_ROW  # program meet


def test_params_are_const_class():
    p = Program.wrap(
        lambda x, w: {"z": x * w}, params={"w": np.float64(3.0)}
    )
    c = rowdep.classify(p, _spec())
    assert c.verdict == rowdep.ROW_INDEPENDENT


def test_classification_memoized():
    p = Program.wrap(lambda x: {"z": x + 1.0})
    c1 = rowdep.classify(p, _spec())
    c2 = rowdep.classify(p, _spec())
    assert c1 is c2  # same object out of program._derived


# ---------------------------------------------------------------------------
# the shared gate: static answers, probe fallback, counters, xcheck
# ---------------------------------------------------------------------------


def test_classified_program_answers_without_probe(monkeypatch):
    monkeypatch.setenv("TFS_ANALYZE_XCHECK", "0")
    p = Program.wrap(lambda x: {"z": x * 3.0})
    specs = _spec()
    rowdep.classify(p, specs)  # one-time classification

    calls = []

    def probe(*a, **k):
        calls.append(a)
        return True

    monkeypatch.setattr(segment_compile, "rows_independent_at", probe)
    before = observability.counters()
    # NEW size sets — the per-size probe memo has never seen these
    assert analysis.rows_independent(p, specs, (11, 16))
    assert analysis.rows_independent(p, specs, (23, 32))
    assert not calls, "a classified program must answer with 0 probes"
    delta = observability.counters_delta(before)
    assert delta["analysis_static_hits"] == 2
    assert delta["analysis_probe_fallbacks"] == 0


def test_unknown_falls_back_to_probe(monkeypatch):
    monkeypatch.setenv("TFS_ANALYZE_XCHECK", "0")

    def branchy(x):
        if x.shape[0] < 4:
            return {"z": x + 1.0}
        return {"z": x * 2.0}

    p = Program.wrap(branchy)
    before = observability.counters()
    # exact sizes on one side of the branch: the probe proves it there
    assert analysis.rows_independent(p, _spec(), (8, 16))
    delta = observability.counters_delta(before)
    assert delta["analysis_probe_fallbacks"] == 1
    assert delta["analysis_static_hits"] == 0


def test_analyze_off_probes_as_before(monkeypatch):
    monkeypatch.setenv("TFS_ANALYZE", "0")
    p = Program.wrap(lambda x: {"z": x + 1.0})
    before = observability.counters()
    assert analysis.rows_independent(p, _spec(), (3, 8))
    delta = observability.counters_delta(before)
    assert delta["analysis_static_hits"] == 0
    assert delta["analysis_probe_fallbacks"] == 0


def test_xcheck_raises_on_unsound_claim(monkeypatch):
    monkeypatch.setenv("TFS_ANALYZE_XCHECK", "1")
    p = Program.wrap(lambda x: {"z": x + 1.0})
    specs = _spec()
    rowdep.classify(p, specs)
    # force a disagreement: the probe "disproves" what the classifier
    # claims — the differential mode must raise, not silently pick one
    monkeypatch.setattr(
        segment_compile, "cached_rows_independent",
        lambda *a, **k: False,
    )
    with pytest.raises(rowdep.AnalysisXCheckError):
        analysis.rows_independent(p, specs, (3, 8))


# ---------------------------------------------------------------------------
# differential corpus — the soundness fence
# ---------------------------------------------------------------------------


def _graph_add3():
    from tensorframes_tpu.graphdef import import_graphdef
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.const("three", np.float64(3.0))
    g.op("Add", "z", ["x", "three"])
    return import_graphdef(g.to_bytes(), fetches=["z"])


def _corpus():
    def branchy(x):
        if x.shape[0] < 4:
            return {"z": x + 1.0}
        return {"z": x * 2.0}

    def branchy_cross(x):
        if x.shape[0] < 50:
            return {"z": x + 1.0}
        return {"z": x + x.sum()}

    out = [
        ("ew", Program.wrap(lambda x: {"z": x * 2.0 + 1.0}), ()),
        ("ew2", Program.wrap(lambda x, y: {"z": x * y}), ()),
        ("tanh", Program.wrap(lambda x: {"z": jnp.tanh(x)}), ()),
        ("where", Program.wrap(
            lambda x: {"z": jnp.where(x > 0, x, -x)}), ()),
        ("clip", Program.wrap(lambda x: {"z": jnp.clip(x, 0, 1)}), ()),
        ("cast", Program.wrap(
            lambda x: {"z": x.astype(np.float32)}), ()),
        ("cellsum", Program.wrap(lambda x: {"z": x.sum(axis=1)}), (4,)),
        ("cellrev", Program.wrap(lambda x: {"z": x[:, ::-1]}), (4,)),
        ("reshape", Program.wrap(
            lambda x: {"z": x.reshape(x.shape[0], -1)}), (2, 3)),
        ("mean", Program.wrap(lambda x: {"z": x / x.shape[0]}), ()),
        ("blocksum", Program.wrap(lambda x: {"z": x - x.sum()}), ()),
        ("sort", Program.wrap(lambda x: {"z": jnp.sort(x)}), ()),
        ("cumsum", Program.wrap(lambda x: {"z": jnp.cumsum(x)}), ()),
        ("rev0", Program.wrap(lambda x: {"z": x[::-1]}), ()),
        ("zeros", Program.wrap(lambda x: {"z": jnp.zeros_like(x)}), ()),
        ("matmul", Program.wrap(
            lambda x: {"z": x @ np.ones((3, 3))}), (3,)),
        ("branchy", Program.wrap(branchy), ()),
        ("branchy_cross", Program.wrap(branchy_cross), ()),
        ("multi", Program.wrap(
            lambda x: {"a": x + 1.0, "b": x - x.sum()}), ()),
        ("params", Program.wrap(
            lambda x, w: {"z": x * w}, params={"w": np.float64(2.0)}),
         ()),
        ("graphdef", _graph_add3(), ()),
    ]
    return out


SIZE_SETS = [(3, 8), (4, 16), (5, 97, 128), (7, 7)]


def test_differential_corpus_soundness():
    """The acceptance fence: zero cases where the classifier claims
    ROW_INDEPENDENT and the probe disproves it — and definitive
    negatives agree with the probe too (the trace/compile fences depend
    on the gates deciding exactly as before)."""
    failures = []
    for name, p, cell in _corpus():
        specs = {
            n: jax.ShapeDtypeStruct((2,) + tuple(cell), np.float64)
            for n in p.input_names
        }
        cls = rowdep.classify(p, specs)
        for sizes in SIZE_SETS:
            probed = segment_compile.rows_independent_at(p, specs, sizes)
            if cls.verdict == rowdep.ROW_INDEPENDENT and not probed:
                failures.append((name, sizes, "UNSOUND: claims "
                                 "independent, probe disproves"))
            if cls.verdict in (
                rowdep.CROSS_ROW, rowdep.SIZE_DEPENDENT
            ) and probed:
                failures.append((name, sizes, "over-negative: probe "
                                 "proves what the classifier denies"))
    assert not failures, failures


def test_corpus_through_the_gate():
    """Run every corpus program through analysis.rows_independent —
    under the lint tier (TFS_ANALYZE_XCHECK=1 exported) this IS the
    differential mode over the corpus; it must never raise."""
    for name, p, cell in _corpus():
        specs = {
            n: jax.ShapeDtypeStruct((2,) + tuple(cell), np.float64)
            for n in p.input_names
        }
        for sizes in SIZE_SETS:
            got = analysis.rows_independent(p, specs, sizes)
            want = segment_compile.cached_rows_independent(
                p, specs, sizes
            )
            assert got == want or (got is False and want is True), (
                name, sizes, got, want,
            )


def test_bit_identity_analyzer_on_vs_off(monkeypatch):
    """Six-verb results identical with the analyzer on vs off (the
    acceptance bit-identity fence, map/bucket leg — uneven blocks so
    the pad/bucket gate actually consults the analyzer)."""
    data = {"x": np.arange(11.0), "y": np.arange(11.0) * 0.5}

    def run_all():
        tf = tfs.TensorFrame.from_arrays(dict(data), num_blocks=3)
        out = {}
        m = tfs.map_blocks(lambda x, y: {"z": x * y + 1.0}, tf)
        out["map"] = np.asarray(m.column("z").data)
        r = tfs.reduce_blocks(
            lambda x_input: {"x": x_input.sum(0)}, tf
        )
        out["reduce"] = np.asarray(r["x"])
        rr = tfs.reduce_rows(lambda x_1, x_2: {"x": x_1 + x_2}, tf)
        out["rr"] = np.asarray(rr["x"])
        return out

    monkeypatch.setenv("TFS_ANALYZE", "0")
    off = run_all()
    monkeypatch.setenv("TFS_ANALYZE", "")
    on = run_all()
    for k in off:
        np.testing.assert_array_equal(off[k], on[k])


# ---------------------------------------------------------------------------
# input_specs_for — the shared spec builder
# ---------------------------------------------------------------------------


def test_input_specs_for_column_infos():
    tf = tfs.TensorFrame.from_arrays(
        {"x": np.arange(12.0).reshape(6, 2)}, num_blocks=2
    )
    p = Program.wrap(lambda x: {"z": x + 1.0})
    infos = {"x": tf.schema["x"]}
    specs = analysis.input_specs_for(p, infos)
    assert specs["x"].shape == (2, 2)
    assert specs["x"].dtype == np.float64


def test_input_specs_for_layout_pairs():
    p = Program.wrap(lambda x: {"z": x + 1.0})
    specs = analysis.input_specs_for(
        p, {"x": (np.zeros((5, 3)), np.float32)}
    )
    assert specs["x"].shape == (2, 3)
    assert specs["x"].dtype == np.float32


def test_input_specs_for_missing_or_ragged():
    p = Program.wrap(lambda x: {"z": x + 1.0})
    assert analysis.input_specs_for(p, {}) is None
    blobs = [np.zeros((2,)), np.zeros((3,))]  # ragged
    tf = tfs.TensorFrame.from_arrays({"x": blobs}, num_blocks=1)
    assert analysis.input_specs_for(p, {"x": tf.schema["x"]}) is None


# ---------------------------------------------------------------------------
# tfs.check — one test per diagnostic code
# ---------------------------------------------------------------------------


@pytest.fixture()
def frame():
    return tfs.TensorFrame.from_arrays(
        {"x": np.arange(10.0), "y": np.arange(20.0).reshape(10, 2)},
        num_blocks=2,
    )


def _codes(diags):
    return [d.code for d in diags]


def test_check_clean(frame):
    assert tfs.check(frame, lambda x: {"z": x + 1.0}, "map_blocks") == []


def test_check_TFS101_unknown_verb(frame):
    assert _codes(tfs.check(frame, lambda x: x, "frobnicate")) == [
        "TFS101"
    ]


def test_check_TFS102_bad_program(frame):
    d = tfs.check(frame, lambda *a: {"z": a[0]}, "map_blocks")
    assert _codes(d) == ["TFS102"]


def test_check_TFS103_missing_column(frame):
    d = tfs.check(frame, lambda q: {"z": q + 1.0}, "map_blocks")
    assert _codes(d) == ["TFS103"]
    assert d[0].severity == "error"
    assert "q" in d[0].summary


def test_check_TFS104_host_only_column():
    tf = tfs.TensorFrame.from_arrays(
        {"blob": [b"aa", b"bb", b"cc"]}, num_blocks=1
    )
    d = tfs.check(tf, lambda blob: {"z": blob}, "map_blocks")
    assert "TFS104" in _codes(d)


def test_check_TFS105_ragged_block_verb():
    tf = tfs.TensorFrame.from_arrays(
        {"x": [np.zeros((2,)), np.zeros((3,))]}, num_blocks=1
    )
    d = tfs.check(tf, lambda x: {"z": x}, "map_blocks")
    assert "TFS105" in _codes(d)


def test_check_TFS106_reduce_rows_naming(frame):
    d = tfs.check(frame, lambda x_1: {"x": x_1}, "reduce_rows")
    assert _codes(d) == ["TFS106"]


def test_check_TFS107_pair_feed_mismatch(frame):
    p = Program.wrap(
        lambda x_1, x_2: {"x": x_1 + x_2},
        feed_dict={"x_1": "x", "x_2": "y"},
    )
    d = tfs.check(frame, p, "reduce_rows")
    assert _codes(d) == ["TFS107"]


def test_check_TFS108_reduce_blocks_naming(frame):
    d = tfs.check(frame, lambda a: {"x": a.sum()}, "reduce_blocks")
    assert _codes(d) == ["TFS108"]


def test_check_TFS109_reduce_output_shape(frame):
    d = tfs.check(
        frame, lambda x_input: {"x": x_input}, "reduce_blocks"
    )
    assert _codes(d) == ["TFS109"]


def test_check_TFS110_shape_hint_contradiction(frame):
    p = Program.wrap(
        lambda y: {"z": y * 1.0}, fetches=["z"]
    ).with_shape_hints({"z": [-1, 5]})
    d = tfs.check(frame, p, "map_blocks")
    assert _codes(d) == ["TFS110"]


def test_check_TFS111_trace_failure(frame):
    d = tfs.check(
        frame, lambda x: {"z": x @ np.ones((3, 3))}, "map_blocks"
    )
    assert _codes(d) == ["TFS111"]


def test_check_TFS112_host_stage_unknown_name(frame):
    d = tfs.check(
        frame, lambda x: {"z": x + 1.0}, "map_blocks",
        host_stage={"nope": lambda cells: cells},
    )
    assert "TFS112" in _codes(d)


def test_check_TFS120_graphdef_unsupported_op(frame):
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.op("FrobnicateV2", "z", ["x"])
    d = tfs.check(frame, g.to_bytes(), "map_blocks", fetches=["z"])
    assert _codes(d) == ["TFS120"]


def test_check_TFS121_decode_mixed_consumer(frame):
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("contents", "binary", [])
    g.op("DecodeJpeg", "decoded", ["contents"], channels=3)
    g.op("Neg", "neg", ["contents"])  # non-decode byte consumer
    d = tfs.check(
        frame, g.to_bytes(), "map_rows", fetches=["decoded", "neg"]
    )
    assert _codes(d) == ["TFS121"]


def test_check_TFS123_graphdef_bad_fetch(frame):
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    g = GraphBuilder()
    g.placeholder("x", "float64", [-1])
    g.const("three", np.float64(3.0))
    g.op("Add", "z", ["x", "three"])
    d = tfs.check(frame, g.to_bytes(), "map_blocks", fetches=["nope"])
    assert _codes(d) == ["TFS123"]


def test_check_TFS130_cross_row_info(frame):
    d = tfs.check(frame, lambda x: {"z": x - x.sum()}, "map_blocks")
    assert _codes(d) == ["TFS130"]
    assert d[0].severity == "info"


def test_check_TFS131_unknown_info(frame):
    def branchy(x):
        if x.shape[0] < 4:
            return {"z": x + 1.0}
        return {"z": x * 2.0}

    d = tfs.check(frame, branchy, "map_blocks")
    assert _codes(d) == ["TFS131"]
    assert d[0].severity == "info"


def test_check_aggregate_missing_key(frame):
    d = tfs.check(
        frame, lambda x_input: {"x": x_input.sum(0)}, "aggregate",
        keys=["nope"],
    )
    assert "TFS103" in _codes(d)


def test_check_codes_registry_consistent():
    from tensorframes_tpu.analysis import contracts

    for code, (title, sev) in contracts.CODES.items():
        assert code.startswith("TFS") and len(code) == 6
        assert sev in ("error", "warn", "info")


def test_validation_errors_carry_codes(frame):
    with pytest.raises(tfs.ValidationError) as ei:
        tfs.map_blocks(lambda q: {"z": q + 1.0}, frame)
    assert ei.value.code == "TFS103"


# ---------------------------------------------------------------------------
# bridge check RPC
# ---------------------------------------------------------------------------


def test_bridge_check_rpc():
    from tensorframes_tpu.bridge import BridgeClient, serve
    from tensorframes_tpu.graphdef.builder import GraphBuilder

    def add3():
        g = GraphBuilder()
        g.placeholder("x", "float64", [-1])
        g.const("three", np.float64(3.0))
        g.op("Add", "z", ["x", "three"])
        return g.to_bytes()

    srv = serve()
    try:
        with BridgeClient(*srv.address) as c:
            rf = c.create_frame(
                {"x": np.arange(8.0)}, num_blocks=2
            ).analyze()
            assert rf.check("map_blocks", add3(), fetches=["z"]) == []
            bad = rf.check(
                "map_blocks", add3(), fetches=["z"],
                inputs={"x": "missing"},
            )
            assert [d["code"] for d in bad] == ["TFS103"]
            assert bad[0]["severity"] == "error"
            # the RPC is pure: running it twice gives the same answer
            assert rf.check(
                "map_blocks", add3(), fetches=["z"],
                inputs={"x": "missing"},
            ) == bad
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# doctor rule + metrics exposition
# ---------------------------------------------------------------------------


def test_doctor_indep_probe_churn_fires():
    diags = tfs.doctor(
        counters={
            "analysis_probe_fallbacks": 64, "analysis_static_hits": 2,
        },
        latency={}, ledger=None, spans=(), tenants={},
    )
    churn = [d for d in diags if d["code"] == "indep_probe_churn"]
    assert len(churn) == 1
    assert churn[0]["knob"] == "TFS_ANALYZE"
    assert churn[0]["evidence"]["analysis_probe_fallbacks"] == 64


def test_doctor_indep_probe_churn_quiet_when_static_dominates():
    diags = tfs.doctor(
        counters={
            "analysis_probe_fallbacks": 8, "analysis_static_hits": 100,
        },
        latency={}, ledger=None, spans=(), tenants={},
    )
    assert not [d for d in diags if d["code"] == "indep_probe_churn"]


def test_analysis_counters_in_delta_and_metrics():
    before = observability.counters()
    assert "analysis_static_hits" in before
    assert "analysis_probe_fallbacks" in before
    delta = observability.counters_delta(before)
    assert "analysis_static_hits" in delta
    text = observability.metrics_text()
    assert "tfs_analysis_static_hits_total" in text
    assert "tfs_analysis_probe_fallbacks_total" in text


# ---------------------------------------------------------------------------
# the repo lint
# ---------------------------------------------------------------------------


def test_lint_clean_on_repo():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tfs_lint.py"),
         "--root", REPO],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def _mini_repo(tmp_path, foo_src, docs="", conftest=""):
    pkg = tmp_path / "tensorframes_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "foo.py").write_text(foo_src)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "COMPONENTS.md").write_text(docs)
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "conftest.py").write_text(conftest)
    return tmp_path


def _lint(root):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tfs_lint.py"),
         "--root", str(root)],
        capture_output=True, text=True, timeout=120,
    )
    return proc.returncode, proc.stdout


def test_lint_flags_raw_environ_read(tmp_path):
    rc, out = _lint(_mini_repo(
        tmp_path,
        'import os\nV = os.environ.get("TFS_SOMETHING", "")\n',
    ))
    assert rc == 1
    assert "env-routing" in out and "TFS_SOMETHING" in out


def test_lint_flags_undocumented_unpinned_knob(tmp_path):
    rc, out = _lint(_mini_repo(
        tmp_path,
        'from . import envutil\nV = envutil.env_int("TFS_NEW_KNOB", 1)\n',
    ))
    assert rc == 1
    assert "knob-docs" in out and "knob-pins" in out


def test_lint_accepts_documented_pinned_knob(tmp_path):
    rc, out = _lint(_mini_repo(
        tmp_path,
        'from . import envutil\nV = envutil.env_int("TFS_NEW_KNOB", 1)\n',
        docs="`TFS_NEW_KNOB` does things",
        conftest='import os\nos.environ.setdefault("TFS_NEW_KNOB", "")\n',
    ))
    assert rc == 0, out


def test_lint_flags_undeclared_counter(tmp_path):
    root = _mini_repo(
        tmp_path,
        "",
    )
    (root / "tensorframes_tpu" / "observability.py").write_text(
        '_counters = {"a": 0}\n'
        "def _bump(k, n=1):\n    pass\n"
        "def note():\n    _bump(\"unheard_of\")\n"
        "def counters_delta(before, after=None):\n"
        "    return {k: 0 for k in (\"a\",)}\n"
    )
    rc, out = _lint(root)
    assert rc == 1
    assert "counter-decl" in out and "unheard_of" in out


def test_lint_flags_a_block_kind_read_in_the_bridge(tmp_path):
    root = _mini_repo(tmp_path, "")
    bridge = root / "tensorframes_tpu" / "bridge"
    bridge.mkdir()
    (bridge / "sched.py").write_text(
        "def ring(cfg, pool):\n"
        "    return pool.ring if cfg.block.window else 0\n"
        "def kernel(self):\n"
        "    return self.cfg.block.mixer is not None\n"
    )
    rc, out = _lint(root)
    assert rc == 1
    assert out.count("[block-kind]") == 2
    assert ".block.window" in out and ".block.mixer" in out


def test_lint_accepts_the_kind_asked_of_kv_pager(tmp_path):
    root = _mini_repo(tmp_path, "")
    bridge = root / "tensorframes_tpu" / "bridge"
    bridge.mkdir()
    (bridge / "sched.py").write_text(
        "def ring(cfg, pool, kv_pager):\n"
        "    return pool.ring, pool.window, kv_pager.holds_pages(cfg)\n"
    )
    # the models themselves read the kind: the rule is the bridge's
    (root / "tensorframes_tpu" / "kinds.py").write_text(
        "def kind(cfg):\n    return cfg.block.attention\n"
    )
    rc, out = _lint(root)
    assert rc == 0, out


def test_lint_flags_uncheckpointed_block_loop(tmp_path):
    root = _mini_repo(tmp_path, "")
    ops = root / "tensorframes_tpu" / "ops"
    ops.mkdir()
    (ops / "engine.py").write_text(
        "def dispatch(self, blocks, session):\n"
        "    outs = []\n"
        "    for bi in blocks:\n"
        "        outs.append(session.run(bi, 1, None))\n"
        "    return outs\n"
    )
    rc, out = _lint(root)
    assert rc == 1
    assert "checkpoint-coverage" in out


def test_lint_knob_match_is_word_bounded(tmp_path):
    # TFS_ANALYZE must not pass on the back of TFS_ANALYZE_XCHECK's
    # docs/pin entries (substring superset)
    rc, out = _lint(_mini_repo(
        tmp_path,
        'from . import envutil\nV = envutil.env_raw("TFS_ANALYZE")\n',
        docs="`TFS_ANALYZE_XCHECK` documented",
        conftest='import os\n'
                 'os.environ.setdefault("TFS_ANALYZE_XCHECK", "")\n',
    ))
    assert rc == 1
    assert "knob-docs" in out and "knob-pins" in out


def test_lint_checkpoint_rule_nested_loops(tmp_path):
    # an inner loop's dispatch must not force an outer checkpoint...
    root = _mini_repo(tmp_path, "")
    ops = root / "tensorframes_tpu" / "ops"
    ops.mkdir()
    (ops / "engine.py").write_text(
        "def dispatch(self, groups, session, cancellation):\n"
        "    for g in groups:\n"
        "        for bi in g:\n"
        "            cancellation.checkpoint()\n"
        "            session.run(bi, 1, None)\n"
    )
    rc, out = _lint(root)
    assert rc == 0, out
    # ...and an inner loop's checkpoint (which may run zero times) must
    # not satisfy a directly-dispatching outer loop
    (ops / "engine.py").write_text(
        "def dispatch(self, blocks, session, cancellation):\n"
        "    for bi in blocks:\n"
        "        session.run(bi, 1, None)\n"
        "        for r in (1, 2):\n"
        "            cancellation.checkpoint()\n"
    )
    rc, out = _lint(root)
    assert rc == 1
    assert "checkpoint-coverage" in out
