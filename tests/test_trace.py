"""Tests for the structured-tracing stack (docs/TELEMETRY.md §Tracing):

* the recorder — off is one shared null context at a bounded cost; on,
  spans record parent id, thread and request ids, counts belong to the
  open span, and the annotations reach a live profiler session only;
* device phase markers — phase() is a nullcontext when off, a
  dgcph.<phase>[.<part>][.b<idx>] named scope when on;
* attrib — op→phase/bucket mapping and the per-bucket table against a
  recorded device-format trace fixture (CPU profiler traces carry no op
  metadata, so the fixture stands in for a TPU trace);
* flight recorder — ring wraparound, raw-value storage, atomic dump +
  load, the nonfinite-streak breaker;
* regress exit codes — 3 (missing artifact) and 4 (schema mismatch)
  stay distinct and actionable.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgc_tpu.telemetry import attrib, regress
from dgc_tpu.telemetry import trace as trace_mod
from dgc_tpu.telemetry.flight import (
    FlightRecorder,
    NonfiniteStreak,
    load_dump,
)
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "device_trace.json")


def _spans(records, name=None):
    return [r for r in records if r["kind"] == "span"
            and (name is None or r["name"] == name)]


# --------------------------------------------------------------------- #
# the recorder                                                           #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_off_span_is_the_shared_null_context_and_count_records_nothing():
    prev = trace_mod.enable(False)
    try:
        a, b = trace_mod.span("x", step=1), trace_mod.span("y")
        assert a is b is trace_mod.carry(seq=3)
        with a as s:
            s.set(anything=1)               # inert, not an error
        trace_mod.count("c", 5, kind="k")
        assert trace_mod.records() == []
        assert trace_mod.step_summary() == {}
        assert trace_mod.step_annotation(7) is a
    finally:
        trace_mod.enable(prev)


@pytest.mark.fast
def test_off_cost_per_call_is_bounded():
    """The off path is one global read: no lock, no allocation of a
    context, no jax. Best of five batches, so a busy core cannot fail it;
    measured 0.45 us (span) and 0.19 us (count) on the sandbox's CPU."""
    import time
    prev = trace_mod.enable(False)
    try:
        def best(fn, n=20000):
            out = []
            for _ in range(5):
                t0 = time.perf_counter_ns()
                for i in range(n):
                    fn(i)
                out.append((time.perf_counter_ns() - t0) / n)
            return min(out)

        def one_span(i):
            with trace_mod.span("step.dispatch", step=i):
                pass

        assert best(one_span) < 5_000                       # ns per call
        assert best(lambda i: trace_mod.count("c", i, kind="k")) < 3_000
    finally:
        trace_mod.enable(prev)


@pytest.mark.fast
def test_span_records_parent_id_thread_and_request_ids(rec):
    import threading
    with rec.span("epoch", epoch=0):
        with rec.span("step.dispatch", step=1):
            with rec.span("step.trace", flat=True):
                pass
        with rec.span("step.dispatch", step=2):
            pass
    trace, d1, d2, outer = rec.records()
    # completion order: inner spans close before the outer one
    assert [r["name"] for r in (trace, d1, d2, outer)] == [
        "step.trace", "step.dispatch", "step.dispatch", "epoch"]
    # the cause is an id, not a name
    assert d1["parent"] == d2["parent"] == outer["id"]
    assert trace["parent"] == d1["id"] and outer["parent"] is None
    assert len({r["id"] for r in (trace, d1, d2, outer)}) == 4
    # a request's id is inherited by what the span causes, and ends with it
    assert (d1["step"], trace["step"], d2["step"], outer["step"]) == (
        1, 1, 2, None)
    assert outer["args"] == {"epoch": 0} and trace["args"] == {"flat": True}
    assert {r["thread"] for r in rec.records()} == {threading.get_ident()}
    assert outer["t0_ns"] <= d1["t0_ns"] <= d1["t1_ns"] <= d2["t0_ns"]
    assert d2["t1_ns"] <= outer["t1_ns"]


@pytest.mark.fast
def test_span_survives_exception(rec):
    with pytest.raises(RuntimeError):
        with rec.span("bad", step=9):
            raise RuntimeError("boom")
    assert [r["name"] for r in rec.records()] == ["bad"]
    # the per-thread stack unwound: a new span has no stale parent or id
    with rec.span("after"):
        pass
    after = rec.records()[-1]
    assert after["parent"] is None and after["step"] is None


@pytest.mark.fast
def test_step_summary_accumulates_and_resets(rec):
    for _ in range(2):
        with rec.span("step.dispatch"):
            pass
    s = rec.step_summary()
    assert set(s) == {"step.dispatch"} and s["step.dispatch"] >= 0
    assert rec.step_summary() == {}          # reset drained it


@pytest.mark.fast
def test_count_belongs_to_the_span_open_when_it_was_made(rec):
    rec.count("orphan", 1)
    with rec.span("step.trace", step=4) as s:
        rec.count("exchange.collective", 128, kind="psum", seq=11)
    orphan, made, span = rec.records()
    assert orphan["parent"] is None and orphan["kind"] == "count"
    assert made["parent"] == span["id"] and made["value"] == 128
    assert made["args"] == {"kind": "psum"}
    assert (made["step"], made["seq"]) == (4, 11)


@pytest.mark.fast
def test_carry_hands_ids_on_without_a_record_of_its_own(rec):
    with rec.carry(seq=5):
        with rec.span("input.get_batch", images=8):
            pass
    with rec.span("input.get_batch", images=8):
        pass
    inside, outside = rec.records()
    assert (inside["seq"], outside["seq"]) == (5, None)
    assert inside["parent"] is None          # carry is not a span


@pytest.mark.fast
def test_set_adds_what_only_the_work_inside_can_tell(rec):
    with rec.span("input.queue_wait") as wait:
        with rec.span("child"):
            pass
        wait.set(seq=3, kind="item")
    child, wait = rec.records()
    assert wait["seq"] == 3 and wait["args"] == {"kind": "item"}
    assert child["seq"] is None              # set after the child closed


@pytest.mark.fast
def test_ring_keeps_the_newest_records(monkeypatch):
    prev = trace_mod.enable(False)
    monkeypatch.setattr(trace_mod, "MAX_RECORDS", 4)
    trace_mod.enable(True)
    try:
        for i in range(10):
            trace_mod.count("c", i)
        assert [r["value"] for r in trace_mod.records()] == [6, 7, 8, 9]
    finally:
        trace_mod.enable(False)
        trace_mod.enable(prev)


@pytest.mark.fast
def test_enable_keeps_the_recorder_it_has_and_off_drops_it(rec):
    rec.count("c", 1)
    assert rec.enable(True) is True
    assert len(rec.records()) == 1
    rec.enable(False)
    assert rec.records() == []


@pytest.mark.fast
def test_write_is_json_lines_once(rec, tmp_path):
    with rec.span("eval", epoch=np.int64(2)):
        rec.count("c", 1)
    path = tmp_path / "run" / "trace_records.jsonl"
    assert rec.write(str(path)) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["kind"] for r in rows] == ["count", "span"]
    assert rows[1]["name"] == "eval" and rows[0]["parent"] == rows[1]["id"]
    assert not os.path.exists(str(path) + ".tmp")


def test_annotations_reach_the_profile_only_while_a_session_is_live(
        rec, tmp_path):
    """The program's spans are ``dgc:*`` events in the profiler's own trace
    (its clock, its file); outside a session no annotation is made."""
    import glob
    assert trace_mod._profiling() is None
    with rec.span("input.stage", seq=1):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace_mod._profiling() is jax.profiler
        ann = rec.step_annotation(7)
        assert isinstance(ann, jax.profiler.StepTraceAnnotation)
        with rec.span("step.dispatch", step=7), ann:
            jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = {ev.name: dict(ev.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("dgc:")}
    assert found["dgc:step.dispatch"] == {"step": 7}
    # the step marker (the viewer's JSON export leaves it out; the
    # profile's own step tools read it)
    assert found["dgc:step"]["step_num"] == 7
    assert "dgc:input.stage" not in found
    # and the recorder kept both, session or not
    assert [r["name"] for r in rec.records()] == ["input.stage",
                                                  "step.dispatch"]


# --------------------------------------------------------------------- #
# device phase markers                                                   #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_phase_off_is_nullcontext():
    prev = trace_mod.enable(False)
    try:
        import contextlib
        assert isinstance(trace_mod.phase("select", 3),
                          contextlib.nullcontext)
    finally:
        trace_mod.enable(prev)


@pytest.mark.fast
@pytest.mark.parametrize("args, token", [
    (("pack",), "dgcph.pack"),
    (("select", 4), "dgcph.select.b4"),
    (("update", -1, "optimizer"), "dgcph.update.optimizer"),
    (("update", -1, "exchange"), "dgcph.update.exchange"),
])
def test_scope_names(args, token):
    assert trace_mod.scope_name(*args) == token


@pytest.mark.fast
@pytest.mark.parametrize("bucket, part", [(-1, "b3"), (2, "optimizer"),
                                          (-1, "a.b"), (-1, "")])
def test_scope_part_may_not_read_as_a_bucket(bucket, part):
    with pytest.raises(ValueError, match="scope part"):
        trace_mod.scope_name("update", bucket, part)


def test_markers_land_in_compiled_text_only_when_on():
    # a FRESH function per build: jax's jaxpr cache keys on the function
    # object, not the trace flag, so reusing one across enable() flips
    # would leak the first build's markers (the same hazard that keeps
    # module-level jitted kernels undecorated — see ops/kernels.py)
    def make():
        def f(x):
            with trace_mod.phase("select", 2):
                return jnp.sum(x * 2.0)
        return f

    x = jnp.arange(8, dtype=jnp.float32)
    prev = trace_mod.enable(True)
    try:
        on = jax.jit(make()).lower(x).compile().as_text()
    finally:
        trace_mod.enable(prev)
    trace_mod.enable(False)
    off_l = jax.jit(make()).lower(x)
    off = off_l.compile().as_text()
    assert "dgcph.select.b2" in on
    assert "dgcph" not in off
    # and the off build's LOWERED text carries no trace of the marker
    assert "dgcph" not in off_l.as_text()


# --------------------------------------------------------------------- #
# attrib: op -> phase mapping over the recorded fixture                  #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_op_phase_mapping():
    ev = {"args": {"tf_op": "jit(s)/dgcph.select.b2/sort"}}
    assert attrib.op_phase(ev) == ("select", 2)
    ev = {"args": {"tf_op": "jit(s)/dgcph.pack/concat"}}
    assert attrib.op_phase(ev) == ("pack", None)
    # innermost token wins when scopes nest
    ev = {"args": {"tf_op": "jit(s)/dgcph.compensate/dgcph.pack/bitcast"}}
    assert attrib.op_phase(ev) == ("pack", None)
    assert attrib.op_phase({"args": {"tf_op": "jit(s)/mul"}}) == (None, None)
    assert attrib.op_phase({}) == (None, None)


@pytest.mark.fast
@pytest.mark.parametrize("reader", ["attrib", "trace_reduce"])
@pytest.mark.parametrize("tf_op, want", [
    # a part reads as its phase: every reader returns what it returned
    # before the parts existed
    ("jit(s)/dgcph.update/dgcph.update.optimizer/add:", ("update", None)),
    ("jit(s)/dgcph.update/dgcph.update.exchange/mul:", ("update", None)),
    ("jit(s)/dgcph.update/dgcph.update.exchange/dgcph.select.b2/sort:",
     ("select", 2)),
    ("jit(s)/dgcph.update/dgcph.update.exchange/dgcph.dense/psum:",
     ("dense", None)),
    ("jit(s)/dgcph.params_view/slice:", ("params_view", None)),
    ("jit(s)/dgcph.plumbing/reshape:", ("plumbing", None)),
])
def test_both_readers_take_a_part_token_for_its_phase(reader, tf_op, want):
    if reader == "attrib":
        assert attrib.op_phase({"args": {"tf_op": tf_op}}) == want
    else:
        from benchmark import trace_reduce
        assert trace_reduce.op_phase(tf_op) == want


@pytest.mark.fast
def test_device_events_filters_fixture():
    events = attrib.load_trace_events(FIXTURE)
    dev = attrib.device_events(events)
    names = sorted(e["name"] for e in dev)
    # envelope (jit_train_step), no-category (step 42) and host-pid
    # events are all excluded; the 9 leaf device ops remain
    assert len(dev) == 9
    assert "jit_train_step" not in names and "step 42" not in names


@pytest.mark.fast
def test_phase_table_against_fixture():
    dev = attrib.device_events(attrib.load_trace_events(FIXTURE))
    t = attrib.phase_table(dev, steps=1)
    # durations are µs in the fixture -> ms here
    assert t["total_ms"] == pytest.approx(2.39)
    assert t["unattributed_ms"] == pytest.approx(0.5)    # copy.2
    assert t["phases"]["threshold"] == pytest.approx(0.1)
    assert t["phases"]["select"] == pytest.approx(0.2)
    assert t["phases"]["pack"] == pytest.approx(0.09)    # incl. nested win
    assert t["phases"]["allgather"] == pytest.approx(0.3)
    assert t["phases"]["decode"] == pytest.approx(0.08)
    assert t["phases"]["apply"] == pytest.approx(0.12)
    assert t["phases"]["fwd_bwd"] == pytest.approx(1.0)
    # bucket split: b0 carries threshold+select, b1 decode
    assert t["buckets"]["b0"]["threshold"] == pytest.approx(0.1)
    assert t["buckets"]["b0"]["select"] == pytest.approx(0.2)
    assert t["buckets"]["b1"]["decode"] == pytest.approx(0.08)
    # phase keys come out in canonical pipeline order
    order = [p for p in trace_mod.PHASES if p in t["phases"]]
    assert list(t["phases"]) == order


@pytest.mark.fast
def test_phase_table_prints_a_part_as_a_row_under_its_phase():
    """A part's time is in its phase's total; ``parts`` splits it and
    ``phase_rows`` prints each part beneath its phase (the innermost
    token decides: an op under ``update.exchange`` AND an engine phase is
    the engine phase's, not glue)."""
    def ev(tf_op, dur):
        return {"name": "fusion", "dur": dur, "args": {"tf_op": tf_op}}

    step = "jit(step_fn)/"
    up = step + "dgcph.update/"
    ex = up + "dgcph.update.exchange/"
    events = [
        ev(step + "dgcph.fwd_bwd/conv", 1000),
        ev(step + "dgcph.fwd_bwd/dgcph.fwd_bwd.pack/concatenate", 70),
        ev(ex + "convert", 5),                              # glue
        ev(ex + "dgcph.select.b3/sort", 80),
        ev(ex + "dgcph.apply/dgcph.apply/dgcph.apply.sort/sort", 120),
        ev(ex + "dgcph.apply/dgcph.apply/dgcph.apply.stage/select_n", 30),
        ev(ex + "dgcph.apply/dgcph.apply/pallas_call", 600),    # the pass
        ev(up + "dgcph.update.optimizer/add", 200),
        ev("", 40),
    ]
    t = attrib.phase_table(events, steps=1)
    assert t["phases"] == {
        "select": pytest.approx(0.08), "apply": pytest.approx(0.75),
        "fwd_bwd": pytest.approx(1.07), "update": pytest.approx(0.205)}
    assert t["parts"] == {
        "apply.sort": pytest.approx(0.12), "apply.stage": pytest.approx(0.03),
        "fwd_bwd.pack": pytest.approx(0.07),
        "update.exchange": pytest.approx(0.005),
        "update.optimizer": pytest.approx(0.2)}
    # totals are what they were without the parts
    assert t["total_ms"] == pytest.approx(2.145)
    assert t["attributed_ms"] == pytest.approx(sum(t["phases"].values()))
    assert t["buckets"] == {"b3": {"select": pytest.approx(0.08)}}
    assert [row for row, _ in attrib.phase_rows(t)] == [
        "select", "apply", "  apply.sort", "  apply.stage", "fwd_bwd",
        "  fwd_bwd.pack", "update", "  update.exchange",
        "  update.optimizer"]
    assert dict(attrib.phase_rows(t))["  apply.sort"] == t["parts"][
        "apply.sort"]
    # a trace recorded before the parts has none, and the rows are the
    # phases
    dev = attrib.device_events(attrib.load_trace_events(FIXTURE))
    old = attrib.phase_table(dev, steps=1)
    assert old["parts"] == {} and attrib.phase_rows(old) == list(
        old["phases"].items())


@pytest.mark.fast
def test_profile_json_roundtrip(tmp_path):
    dev = attrib.device_events(attrib.load_trace_events(FIXTURE))
    t = attrib.phase_table(dev, steps=2)
    dense = attrib.phase_table([], steps=2)
    prof = attrib.profile_json(t, dense, static={"world": 8},
                               measured_overhead_ms=0.106)
    assert prof["delta_ms"] == pytest.approx(t["total_ms"])
    # exchange phases exclude fwd_bwd/update/loss
    assert prof["exchange_phase_ms"] == pytest.approx(
        sum(v for p, v in t["phases"].items() if p != "fwd_bwd"))
    p = attrib.write_profile(prof, str(tmp_path / "profile.json"))
    assert attrib.load_profile(p)["measured_overhead_ms"] == 0.106
    with pytest.raises(ValueError):
        attrib.load_profile(FIXTURE)       # wrong schema


# --------------------------------------------------------------------- #
# flight recorder                                                        #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_flight_ring_wraparound():
    fr = FlightRecorder(capacity=3)
    for s in range(5):
        fr.record(s, loss=float(s))
    assert len(fr) == 3
    assert [r["step"] for r in fr.records()] == [2, 3, 4]


@pytest.mark.fast
def test_flight_dump_atomic_and_loadable(tmp_path):
    fr = FlightRecorder(capacity=4, static={"world": 8})
    # raw device arrays + a nonfinite + an unconvertible value
    fr.record(1, loss=jnp.float32(1.5), spans_ms={"step.dispatch": 2.0})
    fr.record(2, loss=float("nan"), weird=object())
    p = fr.dump(str(tmp_path / "flight.json"), reason="test",
                extra={"note": "x"})
    assert p is not None
    assert os.listdir(tmp_path) == ["flight.json"]     # tmp file renamed
    obj = load_dump(p)
    assert obj["reason"] == "test" and obj["static"] == {"world": 8}
    assert obj["recorded"] == 2 and obj["capacity"] == 4
    r1, r2 = obj["records"]
    assert r1["loss"] == 1.5
    assert r1["spans_ms"] == {"step.dispatch": 2.0}
    assert r2["loss"] == "nan"                          # guarded repr
    assert r2["weird"].startswith("<unconvertible:")
    # dump never raises, even to an unwritable path
    assert fr.dump("/proc/nope/flight.json") is None


@pytest.mark.fast
def test_flight_dump_truncates_arrays(tmp_path):
    fr = FlightRecorder()
    fr.record(1, grad=np.arange(1000, dtype=np.float32))
    obj = load_dump(fr.dump(str(tmp_path / "f.json")))
    assert len(obj["records"][0]["grad"]) == 64


@pytest.mark.fast
def test_nonfinite_streak_breaker():
    ns = NonfiniteStreak(threshold=3)
    assert not ns.update(float("nan"))
    assert not ns.update(float("inf"))
    assert not ns.update(1.0)                 # finite resets
    assert ns.streak == 0
    assert not ns.update(float("nan"))
    assert not ns.update(float("nan"))
    assert ns.update(float("nan"))            # third consecutive trips
    assert ns.update(0.0)                     # tripped stays tripped


@pytest.mark.fast
def test_flight_load_rejects_foreign_schema(tmp_path):
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"schema": "other", "version": 1}))
    with pytest.raises(ValueError):
        load_dump(str(p))


# --------------------------------------------------------------------- #
# regress exit codes                                                     #
# --------------------------------------------------------------------- #

@pytest.mark.fast
def test_regress_exit_3_on_missing_artifact(tmp_path, capsys):
    run = tmp_path / "run.json"
    run.write_text(json.dumps({"metric": "x", "value": 1.0}))
    rc = regress.main([str(tmp_path / "nope.json"), str(run)])
    assert rc == 3
    err = capsys.readouterr().err
    assert "record one first" in err


@pytest.mark.fast
def test_regress_exit_4_on_schema_mismatch(tmp_path, capsys):
    from dgc_tpu.telemetry.registry import SCHEMA
    base = tmp_path / "base.jsonl"
    base.write_text(json.dumps(
        {"schema": SCHEMA, "version": 999, "static": {}}) + "\n")
    run = tmp_path / "run.json"
    run.write_text(json.dumps({"metric": "x", "value": 1.0}))
    rc = regress.main([str(base), str(run)])
    assert rc == 4
    err = capsys.readouterr().err
    assert "schema version" in err and "re-record" in err
