"""The per-layer readers of PR 23: the ones that read the new scopes, on a
small hand-made trace that holds the new tokens (``test_trace_reduce.py``
pins every reader whole on the two chip fixtures, recorded before the
scopes existed); and the ones that read the program's own recorder, on
hand-made records, on the live recorder, and on a program that has
none."""

import pytest

from benchmark import cells, program_records
from test_trace_reduce import _host, _meta, _op, _view

STEP = "jit(step_fn)/"
UPDATE = STEP + "dgcph.update/"
EXCHANGE = UPDATE + "dgcph.update.exchange/"


def scoped_trace():
    """Two arms, one chip, one step each; microseconds. Every new token
    appears once, beside what XLA inserts with no ``tf_op`` at all."""
    return [
        _meta(1, "/device:TPU:0"), _meta(9, "/host:CPU"),
        _host("dgc:segment", 0, 2000),
        _op(1, "slice.1", 100, 100, STEP + "dgcph.params_view/slice:"),
        _op(1, "opaque_view.2", 200, 50,
            STEP + "dgcph.params_view/opaque_view/pallas_call:",
            "custom-call"),
        _op(1, "reshape.3", 250, 10, STEP + "dgcph.plumbing/reshape:"),
        _op(1, "convolution.4", 260, 500, STEP + "dgcph.fwd_bwd/conv:",
            "convolution"),
        _op(1, "fusion.5", 760, 30, EXCHANGE + "convert_element_type:"),
        _op(1, "fusion.6", 790, 80, EXCHANGE + "dgcph.select.b1/sort:"),
        _op(1, "fusion.7", 870, 200, UPDATE + "dgcph.update.optimizer/add:"),
        _op(1, "copy.8", 1070, 40, "", "copy"),
        _op(1, "copy-done.9", 1110, 25, "", "copy-done"),
        _op(1, "slice-done.10", 1135, 15, "", "slice-done"),
        _op(1, "all-reduce-done.11", 1150, 20, "", "all-reduce-done"),
        _host("dense:segment", 3000, 2000),
        _op(1, "convolution.1", 3100, 500, STEP + "dgcph.fwd_bwd/conv:",
            "convolution"),
        _op(1, "all-reduce.2", 3600, 300, EXCHANGE + "dgcph.dense/psum:",
            "all-reduce"),
        _op(1, "fusion.3", 3900, 200, UPDATE + "dgcph.update.optimizer/add:"),
    ]


def _read(view, name):
    return cells.load_reader(name)(view, {}, None)


#: the record readers ask the view which arms a profiler session ran, and
#: ``input.produce_ms`` for the harness's own set-up spans
ARMS = {"steps": {"dgc": 8, "dense": 8}, "setup_spans": {}}


@pytest.mark.parametrize("metric, want", [
    ("step.params_view_ms", 0.15),          # the slice and its guard copy
    ("step.unscoped_ms", 0.1),              # copy + the three -done ops
    ("step.async_wait_ms", 0.04),           # copy-done + slice-done only
    ("step.optimizer_ms", 0.2),
    ("exchange.glue_ms", 0.03),             # the convert, not the sort
    ("collectives.dense_arm_ms", 0.3),      # the DENSE arm's psum
    # and what the older readers return is what it was without the parts:
    ("step.update_ms", 0.23),               # glue + optimizer
    ("exchange.device_ms", 0.08),
    ("step.fwd_bwd_ms", 0.5),
    ("kernels.pallas_ms", 0.05),
])
def test_scope_readers_on_a_trace_with_the_new_tokens(metric, want):
    view = _view(scoped_trace(), {"dgc": 1, "dense": 1})
    assert _read(view, metric) == pytest.approx(want)
    # the tables show the split: new phases are rows of their own
    phases = view["tables"]["dgc"]["phases"]
    assert phases["plumbing"] == pytest.approx(0.01)
    assert view["tables"]["dense"]["phases"]["update"] == pytest.approx(0.2)


# ---------------------------------------------------------------------- #
# readers of the program's recorder                                      #
# ---------------------------------------------------------------------- #

def _span(name, ident, ms, parent=None, **args):
    return {"kind": "span", "name": name, "id": ident, "parent": parent,
            "thread": 1, "t0_ns": 1000, "t1_ns": 1000 + int(ms * 1e6),
            "step": None, "seq": None, "args": args}


def _count(value, parent, engine, kind="psum"):
    return {"kind": "count", "name": "exchange.collective", "value": value,
            "parent": parent, "thread": 1, "t_ns": 0, "step": None,
            "seq": None, "args": {"kind": kind, "axis": "data",
                                  "engine": engine}}


RECORDS = [
    _span("input.get_batch", 1, 200.0, images=64),
    _span("input.get_batch", 2, 250.0, images=64),
    _count(7, None, "FlatDGCEngine"),           # the check's own jit: no trace
    _count(100, 3, "FlatDGCEngine", "all_gather"),
    _count(100, 3, "FlatDGCEngine", "all_gather"),
    _count(40, 3, "FlatDGCEngine"),
    _span("step.trace", 3, 3000.0, compressor="DGCCompressor", flat=True),
    _count(5000, 4, "FlatDenseExchange"),
    _span("step.trace", 4, 2000.0, compressor="NoneCompressor", flat=True),
    # the dgc step traced once more (a lowering for another shape)
    _count(100, 5, "FlatDGCEngine", "all_gather"),
    _count(100, 5, "FlatDGCEngine", "all_gather"),
    _count(40, 5, "FlatDGCEngine"),
    _span("step.trace", 5, 1000.0, compressor="DGCCompressor", flat=True),
]


@pytest.mark.parametrize("metric, want", [
    ("input.produce_ms", 225.0),
    ("step.trace_s", 6.0),
    ("exchange.wire_bytes", 240),           # the last trace's, once
    ("exchange.dense_wire_bytes", 5000),
])
def test_record_readers_on_hand_made_records(monkeypatch, metric, want):
    monkeypatch.setattr(program_records, "records", lambda: list(RECORDS))
    assert _read(ARMS, metric) == pytest.approx(want)
    # a view with no arm is not a traced run of this process
    assert _read({"steps": {}, "setup_spans": {}}, metric) is None


#: ten token batches as ``inputs.host_batches`` times them: the pool's one
#: draw, shared, and each batch's cut
HARNESS = {"input.pool": [0.040], "input.batch": [0.001] * 10}


@pytest.mark.parametrize("records, setup_spans, want", [
    (RECORDS, {}, 225.0),
    (RECORDS, HARNESS, 225.0),              # the program's, where it has any
    ([r for r in RECORDS if r["name"] != "input.get_batch"], HARNESS,
     4.0 + 1.0),                            # else the harness's
    ([], {"input.batch": [0.002, 0.004]}, 3.0),     # images: no pool span
    ([], {}, None),
    ([], {"input.pool": [0.040]}, None),    # a pool and no batch: nothing
], ids=["program", "program-first", "harness", "harness-no-pool", "neither",
        "pool-alone"])
def test_produce_ms_reads_the_programs_spans_else_the_harnesss(
        monkeypatch, records, setup_spans, want):
    monkeypatch.setattr(program_records, "records", lambda: list(records))
    view = {**ARMS, "setup_spans": setup_spans}
    got = _read(view, "input.produce_ms")
    assert got == (None if want is None else pytest.approx(want))
    source, made = program_records.batch_seconds(setup_spans)
    assert source == (
        None if want is None else "program:input.get_batch"
        if any(r["name"] == "input.get_batch" for r in records)
        else "harness:input.batch")
    assert len(made) == {None: 0, 225.0: 2, 5.0: 10, 3.0: 2}[want]
    # and never outside a traced run
    assert _read({**view, "steps": {}}, "input.produce_ms") is None


def test_record_readers_on_the_live_recorder_and_on_a_program_without():
    from dgc_tpu.telemetry import trace
    if not hasattr(trace, "span"):
        pytest.skip("this program has no recorder (the benchmark laid over "
                    "PR 23's parent)")
    metrics = ("input.produce_ms", "step.trace_s", "exchange.wire_bytes",
               "exchange.dense_wire_bytes")
    prev = trace.enable(False)
    try:
        assert [_read(ARMS, m) for m in metrics] == [None] * 4      # off
        trace.enable(True)
        with trace.span("input.get_batch", images=4):
            pass
        for _ in range(2):                                       # two traces
            with trace.span("step.trace", flat=True):
                trace.count("exchange.collective", 64, kind="psum",
                            axis="data", engine="FlatDGCEngine")
                trace.count("exchange.collective", 16, kind="all_gather",
                            axis="data", engine="FlatDGCEngine")
        assert _read(ARMS, "exchange.wire_bytes") == 80
        assert _read(ARMS, "exchange.dense_wire_bytes") is None
        assert 0 < _read(ARMS, "input.produce_ms") < 1e3
        assert 0 < _read(ARMS, "step.trace_s") < 1.0
        # the parent's program has a trace module and no recorder in it:
        # nothing is read, and nothing raises
        records = trace.records
        del trace.records
        try:
            assert [_read(ARMS, m) for m in metrics] == [None] * 4
        finally:
            trace.records = records
    finally:
        trace.enable(False)
        trace.enable(prev)
