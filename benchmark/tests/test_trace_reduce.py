"""The trace reduction: on small hand-made traces for the rules (self time,
busy union, arm split, cap), and on a trace recorded on the chip in PR 22
(``fixtures/chip_trace.json.gz``) for every reader."""

import gzip
import json
import os

import pytest

from benchmark import cells, program_records, trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _meta(pid, name):
    return {"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}


def _op(pid, name, ts, dur, tf_op="", cat="fusion", tid=1):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": {"hlo_category": cat, "tf_op": tf_op}}


def _host(name, ts, dur):
    return {"ph": "X", "pid": 9, "tid": 7, "name": "bench:" + name,
            "ts": ts, "dur": dur}


def small_trace():
    """Two arms, one chip; times in microseconds. dgc: a while op holding
    two convolutions, a compensate custom call, an all-reduce, with a
    100 us hole before the all-reduce. dense: one convolution."""
    return [
        _meta(1, "/device:TPU:0"), _meta(9, "/host:CPU"),
        _host("dgc:segment", 0, 2000),
        _host("dgc:dispatch", 0, 50), _host("dgc:wait", 50, 1950),
        _op(1, "while.1", 100, 600, "jit(step_fn)/dgcph.fwd_bwd/while",
            cat="while"),
        _op(1, "convolution.1", 100, 250,
            "jit(step_fn)/dgcph.fwd_bwd/while/body/conv", "convolution"),
        _op(1, "convolution.2", 400, 250,
            "jit(step_fn)/dgcph.fwd_bwd/while/body/conv", "convolution"),
        _op(1, "custom-call.3", 700, 100,
            "jit(step_fn)/dgcph.update/dgcph.compensate/"
            "jit(fused_compensate_bits_cands)/pallas_call", "custom-call"),
        _op(1, "fusion.4", 800, 100,
            "jit(step_fn)/dgcph.update/dgcph.select.b2/sort"),
        _op(1, "all-reduce.5", 1000, 200,
            "jit(step_fn)/dgcph.update/dgcph.allgather/all_gather",
            "all-reduce"),
        _op(1, "copy.6", 1200, 50, "jit(step_fn)/copy", "copy"),
        {"ph": "X", "pid": 1, "tid": 2, "name": "jit_step_fn", "ts": 100,
         "dur": 1150, "args": {}},                  # module lane: no category
        _host("dense:segment", 3000, 1000),
        _op(1, "convolution.1", 3100, 500,
            "jit(step_fn)/dgcph.fwd_bwd/conv", "convolution"),
        _op(9, "fusion.9", 3100, 500, "x"),         # host lane: not a device
    ]


def _session_of(arm, device_shift_us=0):
    """``small_trace`` cut to the profiler session of one arm (the dense
    arm's events are those from 3000 us on), its device lane moved by
    ``device_shift_us`` against the host's annotations."""
    mine = [e for e in small_trace() if e["ph"] == "M"
            or (e["ts"] >= 3000) == (arm == "dense")]
    return [dict(e, ts=e["ts"] + device_shift_us)
            if e["ph"] == "X" and e["pid"] == 1 else e for e in mine]


def test_a_session_of_one_arm_gives_it_every_device_op():
    """Read on the chip (PR 38): the device lane 0.78 s ahead of the host
    annotations in an arm's own session. The segment's bounds then hold
    part of the ops; the arm is alone in its session and takes them all."""
    want = tr.split_arms(small_trace(), {"dgc": 1, "dense": 1})
    for arm, shift in (("dgc", -400), ("dense", -400), ("dgc", 1500)):
        got = tr.split_arms(_session_of(arm, shift), {arm: 1})[arm]
        assert tr.phase_table(got) == tr.phase_table(want[arm])
        assert got.chips[0].busy_s == pytest.approx(want[arm].chips[0].busy_s)
        assert tr.idle_share(got.chips[0]) == pytest.approx(
            tr.idle_share(want[arm].chips[0]))


def test_a_session_of_two_arms_off_the_hosts_clock_is_an_error():
    """Two arms are told apart by the host's segments alone: ops that
    start outside both, with more than a hundredth of the device time, are
    refused, not dropped; a stray sliver is not."""
    shifted = [dict(e, ts=e["ts"] - 400)
               if e["ph"] == "X" and e["pid"] == 1 else e
               for e in small_trace()]
    with pytest.raises(tr.TraceError, match="not on the host annotations' "
                                            "clock"):
        tr.split_arms(shifted, {"dgc": 1, "dense": 1})
    sliver = small_trace() + [_op(1, "copy.0", 2500, 10, "x", "copy")]
    arms = tr.split_arms(sliver, {"dgc": 1, "dense": 1})
    assert tr.phase_table(arms["dgc"])["total_ms"] == pytest.approx(1.05)


def test_self_time_takes_nested_ops_out():
    arms = tr.split_arms(small_trace(), {"dgc": 1, "dense": 1})
    ops = {o.name: o for o in arms["dgc"].chips[0].ops}
    assert ops["while.1"].self_dur == pytest.approx(100e-6)
    assert not tr.is_leaf(ops["while.1"])
    assert tr.is_leaf(ops["convolution.2"])
    assert ops["convolution.2"].self_dur == pytest.approx(250e-6)


def test_phase_table_innermost_scope_and_bucket():
    arms = tr.split_arms(small_trace(), {"dgc": 1, "dense": 1})
    t = tr.phase_table(arms["dgc"])
    assert t["phases"]["fwd_bwd"] == pytest.approx(0.6)
    assert t["phases"]["compensate"] == pytest.approx(0.1)
    assert t["phases"]["select"] == pytest.approx(0.1)
    assert t["phases"]["allgather"] == pytest.approx(0.2)
    assert t["phases"]["unattributed"] == pytest.approx(0.05)
    assert "update" not in t["phases"]              # innermost token wins
    assert t["buckets"] == {"b2": {"select": pytest.approx(0.1)}}
    assert t["total_ms"] == pytest.approx(1.05)
    assert tr.phase_table(arms["dense"])["total_ms"] == pytest.approx(0.5)


def test_busy_union_idle_share_and_gap_labels():
    arm = tr.split_arms(small_trace(), {"dgc": 1, "dense": 1})["dgc"]
    chip = arm.chips[0]
    assert chip.window == (pytest.approx(100e-6), pytest.approx(1250e-6))
    # leaves only: the 50 us after each convolution (the while op spans
    # both) and the 100 us before the all-reduce are idle
    assert chip.busy_s == pytest.approx(950e-6)
    assert tr.idle_share(chip) == pytest.approx(200 / 1150)
    assert tr.label_gaps(arm) == {"wait": pytest.approx(200e-6)}


def test_interval_helpers():
    assert tr.merge_intervals([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    assert tr.gaps([(0, 1), (1, 2), (3, 4)]) == [(2, 3)]
    assert tr.overlap_s([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2.0)


def test_zero_device_events_is_an_error():
    events = [e for e in small_trace() if e.get("pid") != 1]
    with pytest.raises(tr.TraceError, match="no device-op event"):
        tr.split_arms(events, {"dgc": 1})


def test_arm_without_segment_is_an_error():
    events = [e for e in small_trace()
              if e.get("name") != "bench:dense:segment"]
    with pytest.raises(tr.TraceError, match="cannot be told apart"):
        tr.split_arms(events, {"dgc": 1, "dense": 1})


def test_export_at_the_cap_is_an_error(tmp_path, monkeypatch):
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": small_trace()}, fh)
    assert len(tr.load_events(str(path))) == len(small_trace())
    monkeypatch.setattr(tr, "EVENT_CAP", len(small_trace()))
    with pytest.raises(tr.TraceError, match="cap"):
        tr.load_events(str(path))


def _view(events, steps, paired=None):
    arms = tr.split_arms(events, steps)
    return {"arms": arms, "steps": dict(steps), "setup_spans": {},
            "tables": {n: tr.phase_table(a) for n, a in arms.items()},
            "paired": paired or {},
            "engine": {"T": 819_000, "total": 900_000, "payload_size": 800,
                       "grad_itemsize": 4, "state_itemsize": 4},
            "peaks": cells.load_peaks("TPU v5 lite")}


def test_readers_on_the_small_trace():
    view = _view(small_trace(), {"dgc": 1, "dense": 1},
                 {"dgc_minus_dense_ms": {"median": 0.55}})
    spans = {"dgc": {"input.next": [0.001, 0.003]}}

    def read(name):
        return cells.load_reader(name)(view, spans, None)

    assert read("input.wait_ms") == pytest.approx(2.0)
    assert read("step.fwd_bwd_ms") == pytest.approx(0.6)
    assert read("step.update_ms") is None
    assert read("exchange.device_ms") == pytest.approx(0.4)
    # (1.05 - 0.5) - 0.4
    assert read("exchange.unexplained_ms") == pytest.approx(0.15)
    assert read("exchange.dgc_minus_dense_ms") == 0.55
    assert read("kernels.pallas_ms") == pytest.approx(0.1)
    # 819,000 coordinates x (4 + 4x4) B + 1 bit each = 16.48 MB at
    # 819 GB/s = 20.1 us, over 100 us
    assert read("kernels.compensate_roofline") == pytest.approx(
        100 * (819_000 * 20 + 819_000 // 8) / 819e9 / 100e-6)
    assert read("device.idle_share") == pytest.approx(100 * 200 / 1150)
    assert read("collectives.ms") is None           # one chip: not reported


def test_readers_return_nothing_when_there_is_nothing_to_read():
    view = {"arms": {}, "steps": {}, "setup_spans": {}, "tables": {},
            "paired": {}, "engine": None, "peaks": {}}
    for entry in cells.load_benchmark()["per_layer"]:
        assert cells.load_reader(entry["name"])(view, {}, None) is None


def test_collectives_exposed_on_two_chips():
    events = small_trace() + [_meta(2, "/device:TPU:1")] + [
        dict(e, pid=2) for e in small_trace()
        if e.get("pid") == 1 and e.get("ph") == "X"]
    # on chip 1 a fusion overlaps the second half of the all-reduce
    events.append(_op(2, "fusion.7", 1100, 100, "jit(step_fn)/x", tid=3))
    view = _view(events, {"dgc": 1, "dense": 1})
    assert len(view["arms"]["dgc"].chips) == 2
    read = lambda n: cells.load_reader(n)(view, {}, None)
    assert read("collectives.ms") == pytest.approx(0.2)
    assert read("collectives.exposed_ms") == pytest.approx((0.2 + 0.1) / 2)


def test_async_collective_counts_from_start_to_done():
    """all-reduce-start at 100 (5 us), a fusion 110-160, all-reduce-done
    170-200: in flight 100 us, of which 50 us behind compute."""
    events = [
        _meta(1, "/device:TPU:0"), _meta(2, "/device:TPU:1"),
        _meta(9, "/host:CPU"), _host("dgc:segment", 0, 1000)]
    for pid in (1, 2):
        events += [
            _op(pid, "all-reduce-start.7", 100, 5, "x", "all-reduce-start"),
            _op(pid, "fusion.1", 110, 50, "x"),
            _op(pid, "all-reduce-done.7", 170, 30, "x", "all-reduce-done"),
            _op(pid, "all-gather.2", 300, 20, "x", "all-gather"),
        ]
    view = _view(events, {"dgc": 1})
    spans = tr.collective_spans(view["arms"]["dgc"].chips[0].ops)
    assert spans == [(pytest.approx(100e-6), pytest.approx(200e-6)),
                     (pytest.approx(300e-6), pytest.approx(320e-6))]
    read = lambda n: cells.load_reader(n)(view, {}, None)
    assert read("collectives.ms") == pytest.approx(0.12)
    assert read("collectives.exposed_ms") == pytest.approx(0.07)


def test_result_assembly_from_a_traced_measurement():
    """run.py's path from loaded events to the last line's parts."""
    from benchmark import run
    # a profiler session for the arms that shared the chip: here one
    m = {"traced": [{"events": small_trace(),
                     "steps": {"dgc": 1, "dense": 1}}], "engine": None,
         "setup_spans": {"input.batch": [0.004]}}
    view = run.trace_view(m, {"dgc_minus_dense_ms": {"median": 0.5}},
                          "TPU v5 lite")
    assert view["engine"] is None and set(view["arms"]) == {"dgc", "dense"}
    # and one each where the arms came one after the other: a session of
    # one arm holds that arm's device ops only
    apart = run.trace_view(
        {"traced": [{"events": _session_of(arm), "steps": {arm: 1}}
                    for arm in ("dgc", "dense")], "engine": None,
         "setup_spans": {}},
        {}, "TPU v5 lite")
    assert apart["tables"] == view["tables"]
    assert apart["steps"] == view["steps"] == {"dgc": 1, "dense": 1}
    # what needs no device lane is the same view, less the lanes
    assert run.host_view(m, {}) == {
        **view, "arms": {}, "tables": {}, "peaks": {}, "paired": {}}
    busy, window = run.device_busy(view)
    assert busy == pytest.approx(950e-6 + 500e-6)
    assert window == pytest.approx(1150e-6 + 500e-6)
    b = run.breakdown(view)
    assert b["device_ops"][0] == ["dgc:dgcph.fwd_bwd", pytest.approx(600e-6)]
    assert ["dense:dgcph.fwd_bwd", pytest.approx(500e-6)] in b["device_ops"]
    assert ["dgc:unattributed", pytest.approx(50e-6)] in b["device_ops"]
    assert b["idle_gaps"] == [["dgc:wait", pytest.approx(200e-6)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    fixture = os.path.join(FIXTURES, "rehearsal")
    cell = cells.load_cell(
        "tiny.steady.x4",
        bench=cells.load_benchmark(os.path.join(fixture, "BENCHMARK.json")),
        traffic_dir=os.path.join(fixture, "traffic"))
    values = run.per_layer_values(cell, view, {"dgc": {"input.next": [0.002]}})
    assert values["input.wait_ms"] == pytest.approx(2.0)
    assert values["exchange.dgc_minus_dense_ms"] == 0.5
    assert values["input.produce_ms"] == pytest.approx(4.0)
    # of the repo's metrics this one-chip trace with no engine and no
    # program record leaves some unread, and the line is refused for them
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
              "memory_peak_bytes": 1, "busy_s": busy, "window_s": window}
    with pytest.raises(SystemExit) as refusal:
        run.refuse_a_short_line(cell, values, device, traced=True)
    missing = [e["name"] for e in cell.per_layer if e["name"] not in values]
    assert str(missing) in str(refusal.value) and "step.trace_s" in missing


# ---------------------------------------------------------------------- #
# traces recorded on the chip in PR 22 (TPU v5 lite, JAX 0.9.0)          #
# ---------------------------------------------------------------------- #
# vgg16_bn at 32 images per chip, resident input, `--trace 1`: the one-chip
# cell cut to the first 2 steps of each arm, the four-chip cell to the
# first step; every event cut down to what the reduction reads (name,
# lane, ts, dur, hlo_category, tf_op).

VGG_ENGINE = {"T": 139028480, "total": 139038720, "payload_size": 138360,
              "grad_itemsize": 4, "state_itemsize": 4}


def _chip_view(name, steps):
    events = tr.load_events(os.path.join(FIXTURES, name))
    view = _view(events, {"dgc": steps, "dense": steps},
                 {"dgc_minus_dense_ms": {"median": 3.4}})
    view["engine"] = VGG_ENGINE
    return view


def _read_all(view):
    spans = {"dgc": {"input.next": [2e-6, 4e-6]}}
    return {e["name"]: cells.load_reader(e["name"])(view, spans, None)
            for e in cells.load_benchmark()["per_layer"]}


#: eight of PR 23's ten readers find nothing in a trace recorded before
#: their scopes existed, nor in a recorder that holds nothing; the two that
#: need no new token read what ISSUE 23 quotes (5.31 / 5.35 unattributed,
#: 1.09 of -done waits)
PR23_ABSENT = dict.fromkeys((
    "input.produce_ms", "step.trace_s", "step.params_view_ms",
    "step.optimizer_ms", "exchange.glue_ms", "exchange.wire_bytes",
    "exchange.dense_wire_bytes", "collectives.dense_arm_ms"))
#: PR 42's eight on the same traces (folded in by PR 44; they stood in
#: ``test_part_readers.py`` alone): its recorder readers find no record,
#: its device readers no part and no Pallas apply (the scatter), and say
#: a finite zero; ``step.fwd_bwd_gap_ms`` reads a number on each trace
PR42_ABSENT = dict.fromkeys((
    "exchange.apply_pairs", "step.trace_model_s", "exchange.trace_s"))
PR42_ZERO = dict.fromkeys((
    "exchange.apply_sort_ms", "exchange.apply_stage_ms",
    "exchange.apply_pass_ms", "step.grad_pack_ms"), 0.0)


def _assert_reads(monkeypatch, view, want):
    """Every entry of BENCHMARK.json's per_layer, the whole result."""
    monkeypatch.setattr(program_records, "records", lambda: [])
    got = _read_all(view)
    want = {**PR23_ABSENT, **PR42_ABSENT, **PR42_ZERO, **want}
    assert len(want) == 29 and set(got) == set(want)
    assert got == {k: v if v is None else pytest.approx(v, rel=1e-6, abs=0)
                   for k, v in want.items()}
    # the waits are part of the unscoped time, and it is XLA's own ops
    # (no tf_op) that make up the -done families
    done = [o for c in view["arms"]["dgc"].chips for o in c.ops
            if o.name.partition(".")[0] in ("copy-done", "slice-done")]
    assert done and all(o.tf_op == "" and o.phase is None for o in done)


def test_every_reader_on_the_one_chip_trace(monkeypatch):
    view = _chip_view("chip_trace_vgg16_bn.json.gz", 2)
    dgc, dense = view["arms"]["dgc"], view["arms"]["dense"]
    assert [len(a.chips) for a in (dgc, dense)] == [1, 1]
    assert len(dgc.chips[0].ops) == 3002 and len(dense.chips[0].ops) == 1742
    # the op lane is flat but for one `while` per step (114 us, its body's
    # ops nested in it, 22 ns of its own)
    nested = [o for o in dgc.chips[0].ops if not tr.is_leaf(o)]
    assert [o.name for o in nested] == ["while.4", "while.4"]
    assert all(o.self_dur < 1e-3 * o.dur for o in nested)
    _assert_reads(monkeypatch, view, {
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
    })
    # the compensate kernel is one Pallas call per step, and the tables add up
    kernel = [o for o in dgc.chips[0].ops
              if tr.is_pallas(o) and "fused_compensate" in o.tf_op]
    assert len(kernel) == 2 and {o.phase for o in kernel} == {"compensate"}
    for name in ("dgc", "dense"):
        t = view["tables"][name]
        assert sum(t["phases"].values()) == pytest.approx(t["total_ms"])
        chip = view["arms"][name].chips[0]
        assert 0 < chip.busy_s <= chip.window_s
        assert chip.busy_s == pytest.approx(t["total_ms"] * 2e-3, rel=1e-6)
    assert view["tables"]["dgc"]["total_ms"] == pytest.approx(68.552843163)
    assert view["tables"]["dense"]["total_ms"] == pytest.approx(59.127930897)
    assert set(view["tables"]["dgc"]["buckets"]) == {f"b{i}" for i in range(8)}
    assert "compensate" not in view["tables"]["dense"]["phases"]


def test_every_reader_on_the_four_chip_trace(monkeypatch):
    view = _chip_view("chip_trace_vgg16_bn_x4.json.gz", 1)
    dgc, dense = view["arms"]["dgc"], view["arms"]["dense"]
    assert [c.chip for c in dgc.chips] == [f"/device:TPU:{i}"
                                           for i in range(4)]
    _assert_reads(monkeypatch, view, {
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
    })
    # the dense arm's gradient all-reduce is a real collective here:
    # 9.7 ms of its step on every chip, none of it behind compute
    for chip in dense.chips:
        spans = tr.collective_spans(chip.ops)
        assert sum(b - a for a, b in spans) == pytest.approx(9.71e-3,
                                                             rel=5e-3)
    from benchmark import run
    busy, window = run.device_busy(view)
    assert 0 < busy < window
