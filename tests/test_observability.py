"""Per-verb timing spans + logging setup (VERDICT r1 item 9; reference
``Logging.scala`` / ``PythonInterface.initialize_logging``)."""

import logging

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import observability


@pytest.fixture(autouse=True)
def _reset():
    observability.disable()
    observability._state["spans"] = []
    yield
    observability.disable()


def _frame():
    return tfs.analyze(
        tfs.TensorFrame.from_arrays({"x": np.arange(8.0)}, num_blocks=2)
    )


def test_disabled_by_default_no_spans():
    tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame())
    assert observability.last_spans() == []


def test_spans_recorded_for_all_verbs():
    observability.enable()
    f = _frame()
    tfs.map_blocks(lambda x: {"z": x + 1.0}, f)
    tfs.map_rows(lambda x: {"z": x * 2.0}, f)
    tfs.reduce_blocks(lambda x_input: {"x": x_input.sum(0)}, f)
    tfs.reduce_rows(lambda x_1, x_2: {"x": x_1 + x_2}, f)
    kf = tfs.analyze(
        tfs.TensorFrame.from_arrays(
            {"k": np.array([0, 1, 0, 1]), "v": np.arange(4.0)}
        )
    )
    tfs.aggregate(lambda v_input: {"v": v_input.sum(0)}, tfs.group_by(kf, "k"))
    spans = observability.last_spans()
    verbs = [s["verb"] for s in spans]
    assert verbs == [
        "map_blocks",
        "map_rows",
        "reduce_blocks",
        "reduce_rows",
        "aggregate",
    ]
    mb = spans[0]
    assert mb["rows"] == 8 and mb["blocks"] == 2
    assert "validate" in mb["phases_s"] and "dispatch" in mb["phases_s"]
    rb = spans[2]
    assert {"validate", "dispatch", "sync"} <= set(rb["phases_s"])
    assert rb["total_s"] >= sum(rb["phases_s"].values()) - 1e-6


def test_failed_verb_still_records_span():
    """ADVICE r2: a verb that raises must still record its span (tagged
    failed) — the diagnostic matters most on the error path."""
    observability.enable()
    f = _frame()
    with pytest.raises(Exception):
        tfs.map_blocks(lambda x: {"z": x + undefined_name}, f)  # noqa: F821
    spans = observability.last_spans()
    assert spans and spans[-1]["verb"] == "map_blocks"
    assert spans[-1]["failed"] is True


def test_span_log_records(caplog):
    observability.enable()
    with caplog.at_level(logging.INFO, logger="tensorframes_tpu.verbs"):
        tfs.map_blocks(lambda x: {"z": x + 1.0}, _frame())
    assert any("map_blocks" in r.message for r in caplog.records)


def test_initialize_logging_configures_handler():
    import io

    buf = io.StringIO()
    tfs.initialize_logging(logging.DEBUG, stream=buf)
    observability.logger.info("hello-from-test")
    assert "hello-from-test" in buf.getvalue()
    observability.logger.handlers[:] = []
    observability.logger.propagate = True


def test_span_buffer_bounded():
    observability.enable()
    observability._state["spans"] = [
        {"verb": "x"} for _ in range(observability._MAX_SPANS)
    ]
    tfs.map_blocks(lambda x: {"z": x}, _frame())
    assert len(observability._state["spans"]) == observability._MAX_SPANS
    assert observability._state["spans"][-1]["verb"] == "map_blocks"


# ---------------------------------------------------------------------------
# retrace counters (round 7)
# ---------------------------------------------------------------------------


def test_counters_count_program_traces_per_verb():
    c0 = observability.counters()
    tfs.map_blocks(lambda x: {"z": x + 2.0}, _frame())
    d = observability.counters_delta(c0)
    assert d["program_traces"] >= 1
    by_verb = observability.counters()["by_verb"]
    assert by_verb["map_blocks"]["program_traces"] >= 1


def test_counters_repeat_call_adds_no_traces():
    frame = _frame()
    prog = tfs.Program.wrap(lambda x: {"z": x * 2.0}, fetches=["z"])
    tfs.map_blocks(prog, frame)
    c0 = observability.counters()
    tfs.map_blocks(prog, frame)  # same Program, same shapes: cache hit
    d = observability.counters_delta(c0)
    assert d["program_traces"] == 0, d
    assert d["backend_compiles"] == 0, d


def test_analysis_tracing_is_suppressed():
    prog = tfs.Program.wrap(lambda x: {"z": x + 1.0}, fetches=["z"])
    c0 = observability.counters()
    prog.analyze({"x": (tfs.scalar_type("float64"), (-1,))})
    d = observability.counters_delta(c0)
    assert d["program_traces"] == 0, d


def test_enabled_spans_carry_retrace_delta():
    observability.enable()
    tfs.map_blocks(lambda x: {"z": x - 1.0}, _frame())
    span = observability.last_spans()[-1]
    assert "retrace" in span
    assert span["retrace"]["program_traces"] >= 1
    assert "backend_compiles" in span["retrace"]
