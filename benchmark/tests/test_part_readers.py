"""The eight per-layer readers of PR 42: the ones that read a scope PART
(``dgcph.apply.sort``, ``dgcph.apply.stage``, ``dgcph.fwd_bwd.pack``), the
pass and the difference of the arms' ``fwd_bwd``, on a small hand-made
trace with and without the tokens; the ones that read the children of
``step.trace`` and the count ``exchange.apply``, on hand-made records with
and without them; and the whole result of every reader ``BENCHMARK.json``
lists on the two chip fixtures (``test_trace_reduce.py`` pins the same 29
since PR 44 folded the eight into its dictionaries).

A reader here returns a FINITE number wherever its enclosing thing ran: the
driver runs the parent's program, which has none of the new tokens, under
these files, and ``run.refuse_a_short_line`` refuses a traced line that
lacks a metric of its cell."""

import inspect
import math

import pytest

from benchmark import cells, program_records
from test_program_readers import ARMS, _count, _read, _span
from test_trace_reduce import _chip_view, _host, _meta, _op, _read_all, _view

STEP = "jit(step_fn)/"
FWD = STEP + "dgcph.fwd_bwd/"
APPLY = (STEP + "dgcph.update/dgcph.update.exchange/dgcph.apply/"
         "dgcph.apply/")

DEVICE_READERS = ("exchange.apply_sort_ms", "exchange.apply_stage_ms",
                  "exchange.apply_pass_ms", "step.grad_pack_ms",
                  "step.fwd_bwd_gap_ms")
RECORD_READERS = ("exchange.apply_pairs", "step.trace_model_s",
                  "exchange.trace_s")


def parts_trace(parts=True):
    """Two arms, one chip, one step each; microseconds. With ``parts`` the
    apply pass's sort and staging and the gradient pack carry their part;
    without, the same ops carry their phase alone, as the parent's do."""
    sort = "dgcph.apply.sort/" if parts else ""
    stage = "dgcph.apply.stage/" if parts else ""
    pack = "dgcph.fwd_bwd.pack/" if parts else ""
    return [
        _meta(1, "/device:TPU:0"), _meta(9, "/host:CPU"),
        _host("dgc:segment", 0, 2000),
        _op(1, "convolution.1", 100, 500, FWD + "conv:", "convolution"),
        _op(1, "fusion.2", 600, 70, FWD + pack + "concatenate:"),
        _op(1, "fusion.3", 670, 40, APPLY + sort + "div:"),
        _op(1, "sort.4", 710, 120, APPLY + sort + "sort:", "sort"),
        _op(1, "fusion.5", 830, 25, APPLY + stage + "select_n:"),
        _op(1, "fusion.6", 855, 15, APPLY + stage + "concatenate:"),
        _op(1, "payload_update_bits.7", 870, 600,
            APPLY + "payload_update_bits/pallas_call:", "custom-call"),
        # phase ``apply``, and neither staging nor the pass
        _op(1, "bitcast.8", 1470, 5, APPLY + "reshape:"),
        # a Pallas call of another phase is not the pass
        _op(1, "topk_rows.9", 1475, 50,
            STEP + "dgcph.select.b1/topk_rows/pallas_call:", "custom-call"),
        _host("dense:segment", 3000, 2000),
        _op(1, "convolution.1", 3100, 460, FWD + "conv:", "convolution"),
        _op(1, "fusion.2", 3560, 70, FWD + pack + "concatenate:"),
    ]


@pytest.mark.parametrize("metric, with_parts, without", [
    ("exchange.apply_sort_ms", 0.16, 0.0),
    ("exchange.apply_stage_ms", 0.04, 0.0),
    ("exchange.apply_pass_ms", 0.6, 0.6),       # the pass needs no part
    ("step.grad_pack_ms", 0.07, 0.0),
    ("step.fwd_bwd_gap_ms", 0.04, 0.04),        # 570 - 530, pack included
])
def test_part_readers_on_a_trace_with_and_without_the_tokens(
        metric, with_parts, without):
    for parts, want in ((True, with_parts), (False, without)):
        view = _view(parts_trace(parts), {"dgc": 1, "dense": 1})
        got = _read(view, metric)
        assert got == pytest.approx(want) and math.isfinite(got)
        phases = view["tables"]["dgc"]["phases"]
        # a part reads as its phase: the tables are the same either way
        assert phases["apply"] == pytest.approx(0.805)
        assert phases["fwd_bwd"] == pytest.approx(0.57)
        assert view["tables"]["dense"]["phases"]["fwd_bwd"] == pytest.approx(
            0.53)
    # sort + stage + pass is phase ``apply`` but for the ops named here
    view = _view(parts_trace(), {"dgc": 1, "dense": 1})
    three = sum(_read(view, m) for m in DEVICE_READERS[:3])
    assert view["tables"]["dgc"]["phases"]["apply"] - three == pytest.approx(
        0.005)


def test_part_readers_without_a_dgc_arm_and_without_a_dense_one():
    events = [e for e in parts_trace() if "dgc:segment" not in e["name"]
              and e.get("ts", 3000) >= 3000]
    dense_only = _view(events, {"dense": 1})
    assert [_read(dense_only, m) for m in DEVICE_READERS] == [None] * 5
    events = [e for e in parts_trace() if "dense:segment" not in e["name"]
              and e.get("ts", 0) < 3000]
    dgc_only = _view(events, {"dgc": 1})
    assert _read(dgc_only, "step.fwd_bwd_gap_ms") is None
    assert _read(dgc_only, "exchange.apply_pass_ms") == pytest.approx(0.6)


# ---------------------------------------------------------------------- #
# readers of the program's recorder                                      #
# ---------------------------------------------------------------------- #

def _apply(value, parent, path="update"):
    return {"kind": "count", "name": "exchange.apply", "value": value,
            "parent": parent, "thread": 1, "t_ns": 0, "step": None,
            "seq": None, "args": {"path": path}}


#: a dgc step traced twice and a dense step, as the recorder holds them:
#: children close before their ``step.trace``; the counts made inside
#: ``exchange.trace`` hang under ``step.trace``
RECORDS = [
    _apply(7, None, "scatter"),                 # the check's own program
    _span("step.trace_model", 11, 1500.0, parent=10, nbps=1),
    _apply(400, 10),
    _count(100, 10, "FlatDGCEngine", "all_gather"),
    _span("exchange.trace", 12, 900.0, parent=10, engine="FlatDGCEngine"),
    _span("step.trace", 10, 3000.0, compressor="DGCCompressor", flat=True),
    _span("step.trace_model", 21, 1400.0, parent=20, nbps=1),
    _count(5000, 20, "FlatDenseExchange"),
    _span("exchange.trace", 22, 100.0, parent=20,
          engine="FlatDenseExchange"),
    _span("step.trace", 20, 2000.0, compressor="NoneCompressor", flat=True),
    _span("step.trace_model", 31, 1100.0, parent=30, nbps=1),
    _apply(500, 30),
    _span("exchange.trace", 32, 700.0, parent=30, engine="FlatDGCEngine"),
    _span("step.trace", 30, 2500.0, compressor="DGCCompressor", flat=True),
    _apply(9, None, "scatter"),                 # the check again, afterwards
    # ... which since PR 44 drives ``step_flat``, and so opens the span
    # under no ``step.trace``: not the step's seconds
    _span("exchange.trace", 40, 800.0, engine="FlatDGCEngine"),
]
#: the parent's recorder: the traces and their counts, no child span
PARENT_RECORDS = [r for r in RECORDS if r["name"] not in (
    "step.trace_model", "exchange.trace")]


@pytest.mark.parametrize("metric, with_children, without", [
    ("exchange.apply_pairs", 500, 500),     # the LAST trace that holds one
    ("step.trace_model_s", 4.0, 0.0),       # both arms, every trace
    ("exchange.trace_s", 1.7, 0.0),
])
def test_record_readers_with_and_without_the_child_spans(
        monkeypatch, metric, with_children, without):
    for records, want in ((RECORDS, with_children),
                          (PARENT_RECORDS, without)):
        monkeypatch.setattr(program_records, "records", lambda: list(records))
        got = _read(ARMS, metric)
        assert got == pytest.approx(want) and math.isfinite(got)
        # a view with no arm is not a traced run of this process
        assert _read({"steps": {}, "setup_spans": {}}, metric) is None
    # the children are no longer than what they split, and the older
    # readers return what they did: the counts kept their parent
    monkeypatch.setattr(program_records, "records", lambda: list(RECORDS))
    assert (_read(ARMS, "step.trace_model_s") + _read(ARMS, "exchange.trace_s")
            <= _read(ARMS, "step.trace_s") == pytest.approx(7.5))
    assert _read(ARMS, "exchange.wire_bytes") == 100
    assert _read(ARMS, "exchange.dense_wire_bytes") == 5000


@pytest.mark.parametrize("records, want", [
    ([], None),                                         # no recorder
    ([_apply(7, None, "scatter")], None),               # no traced step
    ([_span("step.trace", 1, 10.0, flat=True)], 0.0),   # a step, no count
    ([_apply(3, 1), _apply(4, 1), _span("step.trace", 1, 10.0)], 7),
], ids=["empty", "check-only", "no-count", "two-counts"])
def test_apply_pairs_walks_the_parent_itself(monkeypatch, records, want):
    monkeypatch.setattr(program_records, "records", lambda: list(records))
    assert _read(ARMS, "exchange.apply_pairs") == want
    # the dense arm alone applies nothing
    assert _read({"steps": {"dense": 8}, "setup_spans": {}},
                 "exchange.apply_pairs") is None


def test_record_readers_on_the_live_recorder():
    from dgc_tpu.telemetry import trace
    prev = trace.enable(False)
    try:
        assert [_read(ARMS, m) for m in RECORD_READERS] == [None] * 3  # off
        trace.enable(True)
        with trace.span("step.trace", flat=True):
            pass
        # a program with the recorder and without the children or the
        # count: finite, and nothing raises
        assert [_read(ARMS, m) for m in RECORD_READERS] == [0.0] * 3
        if "owns_counts" not in inspect.signature(trace.span).parameters:
            pytest.skip("this program's span() has no owns_counts (the "
                        "benchmark laid over PR 42's parent)")
        with trace.span("step.trace", flat=True) as step:
            with trace.span("step.trace_model", owns_counts=False, nbps=1):
                pass
            with trace.span("exchange.trace", owns_counts=False,
                            engine="FlatDGCEngine"):
                trace.count("exchange.apply", 64, path="update")
                trace.count("exchange.collective", 16, kind="psum",
                            axis="data", engine="FlatDGCEngine")
        counts = [r for r in trace.records() if r["kind"] == "count"]
        assert [c["parent"] for c in counts] == [step.id] * 2
        assert _read(ARMS, "exchange.apply_pairs") == 64
        assert _read(ARMS, "exchange.wire_bytes") == 16
        assert 0 < _read(ARMS, "step.trace_model_s") < 1.0
        assert 0 < _read(ARMS, "exchange.trace_s") < 1.0
        assert (_read(ARMS, "step.trace_model_s")
                + _read(ARMS, "exchange.trace_s")
                <= _read(ARMS, "step.trace_s"))
    finally:
        trace.enable(False)
        trace.enable(prev)


# ---------------------------------------------------------------------- #
# the whole result on the chip fixtures                                  #
# ---------------------------------------------------------------------- #

#: what the recorder's readers return on a trace recorded with no recorder
NO_RECORDS = dict.fromkeys((
    "input.produce_ms", "step.trace_s", "exchange.wire_bytes",
    "exchange.dense_wire_bytes", "exchange.apply_pairs",
    "step.trace_model_s", "exchange.trace_s"))
#: what PR 23's scope readers return on a trace recorded before their
#: scopes (they say None where they find no token; PR 42's say 0.0)
PR23_ABSENT = dict.fromkeys((
    "step.params_view_ms", "step.optimizer_ms", "exchange.glue_ms",
    "collectives.dense_arm_ms"))
#: PR 42's device readers on a PR 22 trace: no part, no Pallas apply (the
#: scatter): finite zeros
PR42_ZERO = dict.fromkeys((
    "exchange.apply_sort_ms", "exchange.apply_stage_ms",
    "exchange.apply_pass_ms", "step.grad_pack_ms"), 0.0)


@pytest.mark.parametrize("fixture, steps, want", [
    ("chip_trace_vgg16_bn.json.gz", 2, {
        "input.wait_ms": 0.003,
        "step.fwd_bwd_ms": 49.136492187,
        "step.update_ms": 4.141931289,
        "exchange.device_ms": 9.965785043,
        "exchange.unexplained_ms": -0.540872777,
        "exchange.dgc_minus_dense_ms": 3.4,
        "kernels.pallas_ms": 4.531906797,
        "kernels.compensate_roofline": 82.869233906,
        "collectives.ms": None,
        "collectives.exposed_ms": None,
        "device.idle_share": 0.0721400308,
        "step.unscoped_ms": 5.308634644,
        "step.async_wait_ms": 1.091578241,
        "step.fwd_bwd_gap_ms": -1.225332813,    # the DENSE arm's is slower
    }),
    ("chip_trace_vgg16_bn_x4.json.gz", 1, {
        "input.wait_ms": 0.003,
        "step.fwd_bwd_ms": 48.944180078,
        "step.update_ms": 4.1420081055,
        "exchange.device_ms": 13.8989252735,
        "exchange.unexplained_ms": -10.445841858,
        "exchange.dgc_minus_dense_ms": 3.4,
        "kernels.pallas_ms": 4.5320640035,
        "kernels.compensate_roofline": 82.874503457,
        "collectives.ms": 0.1032683985,
        "collectives.exposed_ms": 0.1032683985,
        "device.idle_share": 2.74536607367,
        "step.unscoped_ms": 5.3476499775,
        "step.async_wait_ms": 1.090399471,
        # read by the reader whatever the cell; BENCHMARK.json lists the
        # metric for the one-chip cells only
        "step.fwd_bwd_gap_ms": -1.453179961,
    }),
], ids=["one-chip", "four-chip"])
def test_every_reader_of_the_benchmark_on_the_chip_fixtures(
        monkeypatch, fixture, steps, want):
    """All 29 entries of ``per_layer``, the whole result, in
    ``BENCHMARK.json``'s order: the 21 values ``test_trace_reduce.py``
    pinned before PR 42, letter for letter, and PR 42's eight."""
    monkeypatch.setattr(program_records, "records", lambda: [])
    got = _read_all(_chip_view(fixture, steps))
    want = {**NO_RECORDS, **PR23_ABSENT, **PR42_ZERO, **want}
    assert [e["name"] for e in cells.load_benchmark()["per_layer"]] == list(
        got)
    assert len(want) == 29 and set(got) == set(want)
    assert got == {k: v if v is None else pytest.approx(v, rel=1e-6, abs=0)
                   for k, v in want.items()}
