"""The harness's own ``measure`` on tiny cells, on the CPU: an image and a
token configuration, every input mode and loop the traffic files can ask
for, one chip and a mesh of four. Counts and checks only; no time from
here is a device number."""

import copy
import json
import os

import pytest

from benchmark import cells, model_check, rehearse, run

TRAFFIC = os.path.join(rehearse.FIXTURE, "traffic")
RESIDENCIES = pytest.mark.parametrize("residency", cells.RESIDENCIES)


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.resident", "tiny.scan",
                                  "tiny.steady.x4", "tiny_lm.resident",
                                  "tiny_lm.scan", "tiny_lm.resident.x4",
                                  "tiny_lm.one", "tiny_lm.one.x4",
                                  "tiny_moe.resident", "tiny_moe.one"])
def test_measure_runs_the_cell(cell):
    out = rehearse._run_tiny(cell, trace=False)
    assert out["rounds"] >= 2 and out["steps"] > 0
    assert out["check"]["ok"] and out["check"]["world"] == (
        4 if cell.endswith(".x4") else 1)
    model = out["model_check"]
    if not cell.startswith("tiny."):
        # the timed step of both arms is held to the model's reference,
        # over the first two dispatches
        assert model["ok"] and set(model["arms"]) == {"dgc", "dense"}
        scan = cell == "tiny_lm.scan"
        for arm in model["arms"].values():
            assert arm["steps"] == (6 if scan else 2)
            assert arm["loss_rel_err"]["max"] <= model["limits"][
                "loss_rel_err"]
        dgc, dense = model["arms"]["dgc"], model["arms"]["dense"]
        assert dense["update_norm_gap"]["max"] <= model["limits"][
            "update_norm_gap"]
        assert len(dense["update_norm_gap"]["by_step"]) == 2
        # k steps in a dispatch leave no state after one step to read a
        # gradient from
        assert ("grad_rel_err" in dense) == ("conserved_rel_err" in dgc) \
            == ("unexplained_coords" in dgc) == (not scan)
        # the followers' files, read back from their directory
        assert model["followers_bytes"] > 0
        if not scan:
            assert dgc["unexplained_coords"]["max"] == 0
            assert dgc["unexplained_coords"]["most_share"] \
                < model_check.COORD_FACTOR
            # what stayed says over how many coordinates it was taken
            assert min(dgc["conserved_rel_err"]["coords"]) > 0
            # every followed step's gradient, not the first alone
            assert len(dense["grad_rel_err"]["by_step"]) == 2
            tensors = set(dense["grad_rel_err"]["by_tensor"])
            if cell.startswith("tiny_lm"):
                assert tensors == {"embed/embedding", "gate/kernel",
                                   "up/kernel", "down/kernel", "head/kernel"}
            else:
                # 20 experts of three tensors, and the norm's scale: the
                # one tensor of the engine's dense tail
                assert len(tensors) == 64 and "norm/scale" in tensors
    else:
        assert model["ok"] and "batch statistics" in model["skipped"]


@pytest.mark.parametrize("cell, source", [
    ("tiny.resident", "program:input.get_batch"),
    ("tiny_lm.resident", "harness:input.batch"),
    ("tiny_lm.one", "harness:input.batch"),
    ("tiny_moe.resident", "harness:input.batch"),
    ("tiny_moe.one", "harness:input.batch"),
])
def test_traced_measure_runs_the_repos_readers_on_the_cell(cell, source):
    """The harness's spans are on the trace, and every reader of the
    repo's list that needs no device lane returns a number for the cell:
    an image cell's batches are timed by the program's split, a token
    cell's by the harness, which makes them."""
    out = rehearse._run_tiny(cell, trace=True)
    assert out["annotations"] >= 6 and "no_device_ops" in out
    assert out["per_layer_read"] == [
        "exchange.apply_pairs", "exchange.dense_wire_bytes",
        "exchange.trace_s", "exchange.wire_bytes", "input.produce_ms",
        "input.wait_ms", "step.trace_model_s", "step.trace_s"]
    assert out["input_produce_source"] == source


def test_the_fixtures_per_layer_metrics_are_the_repos():
    """Same names in the same order, units, sources, layers and ``moves``;
    a list of the repo's one-chip (four-chip) cells reads as the list of
    the fixtures' one-chip (four-chip) cells."""
    repo = cells.load_benchmark()
    fixture = cells.load_benchmark(
        os.path.join(rehearse.FIXTURE, "BENCHMARK.json"))

    def normal(bench):
        chips = {w["name"]: w["chips"] for w in bench["workloads"]}
        whole = {n: sorted(name for name, c in chips.items() if c == n)
                 for n in (1, 4)}
        out = []
        for entry in bench["per_layer"]:
            entry = dict(entry)
            listed = entry.pop("workloads", None)
            if listed is not None:
                (n,) = {chips[name] for name in listed}
                assert sorted(listed) == whole[n], entry["name"]
                entry["chips"] = n
            out.append(entry)
        return out

    assert normal(fixture) == normal(repo)


def _owed_values(cell):
    return {e["name"]: 1.0 for e in cell.per_layer}


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0}


@pytest.mark.parametrize("gone", [
    ["input.produce_ms"], ["input.produce_ms", "step.optimizer_ms"]])
def test_a_traced_line_that_lacks_a_metric_of_its_cell_is_refused(gone):
    """A token cell owes every per-layer metric without a ``workloads``
    list, and the one-chip lists': the refusal names the workload and
    ALL that is missing; the whole line passes."""
    cell = rehearse.fixture_cell("tiny_lm.one")
    values = _owed_values(cell)
    assert len(values) == 25        # of 29: less the four-chip lists' 4
    run.refuse_a_short_line(cell, values, DEVICE, traced=True)
    for name in gone:
        del values[name]
    with pytest.raises(SystemExit) as refusal:
        run.refuse_a_short_line(cell, values, DEVICE, traced=True)
    said = str(refusal.value)
    assert "'tiny_lm.one', traced run" in said and str(gone) in said
    assert not any(name in said for name in values)
    # a value that is no number is no value
    with pytest.raises(SystemExit, match=r"\['kernels.pallas_ms'\]"):
        run.refuse_a_short_line(
            cell, {**_owed_values(cell), "kernels.pallas_ms": float("nan")},
            DEVICE, traced=True)


def test_an_untraced_line_owes_the_end_to_end_metrics_and_its_device():
    cell = rehearse.fixture_cell("tiny_lm.one.x4")
    values = {"step_ms": 1.0, "dense_step_ms": 1.0, "setup_s": 1.0,
              "dgc_overhead_ms": 0.1}       # the last is not this cell's
    device = {k: v for k, v in DEVICE.items()
              if k not in ("busy_s", "window_s")}
    run.refuse_a_short_line(cell, values, device, traced=False)
    with pytest.raises(SystemExit,
                       match=r"'tiny_lm.one.x4', untraced run, did not "
                             r"produce \['dense_step_ms', 'setup_s'\]"):
        run.refuse_a_short_line(cell, {"step_ms": 1.0}, device, traced=False)
    with pytest.raises(SystemExit, match=r"device lacks \['kind'\]"):
        run.refuse_a_short_line(
            cell, values, {**device, "kind": None}, traced=False)
    # traced, the device also says how busy it was, in the driver's words
    traced = _owed_values(cell)
    for busy in ({}, {"busy_s": 0.0, "window_s": 1.0},
                 {"busy_s": 1.5, "window_s": 1.0}):
        with pytest.raises(SystemExit, match="not 0 < busy_s <= window_s"):
            run.refuse_a_short_line(cell, traced, {**device, **busy},
                                    traced=True)


def test_the_clients_creation_is_left_out_of_setup(monkeypatch):
    """``setup_s`` runs from process start to the first timed round, less
    the seconds the runtime took to create its client (PR 38: the
    machine's, 6-17 s by the machine, its chips and its age)."""
    import time

    import jax
    monkeypatch.setattr(run, "_T0", time.perf_counter())
    m = run.measure(rehearse.fixture_cell("tiny.resident"), seed=5,
                    seconds=0.2, trace=False, devices=jax.devices("cpu"),
                    client_s=100.0)
    # every lap of a cell whose arms share the chip lies before its window,
    # the collector's pass before the window among them (``collect``: it
    # grows with the process, 0.55 s late in a whole run of these tests)
    assert m["setup_s"] + 100.0 == pytest.approx(
        sum(m["split"].values()), abs=0.5)
    assert m["split"]["collect"] > 0.0


def test_the_memory_law_refuses_with_its_numbers():
    """``rehearse.py aot``'s rule on a compiler's numbers (bytes a chip;
    these are a 461M-parameter token model's at 16 rows of 256, PR 27):
    both states and the larger step's temporaries, the check on its own,
    and a refusal that states every one of them."""
    row = {"cell": "wide", "chips": 1, "check_bytes": 8884226048,
           "param_bytes": 1845493760,
           "dgc": {"argument_bytes": 7439714304, "temp_bytes": 4390782976},
           "dense": {"argument_bytes": 3691021312, "temp_bytes": 6359577600}}
    law = rehearse.memory_law(row)
    assert law["needs_bytes"] == 7439714304 + 3691021312 + 6359577600
    assert law["needs_bytes"] > rehearse.HBM_BYTES and not law["fits"]
    # no reference of the model, nothing written: the process's baseline
    assert law["host_bytes"] == int(rehearse.HOST_BASELINE_BYTES)
    assert law["disk_bytes"] == 0
    for number in (law["needs_bytes"], 7439714304, 3691021312, 6359577600,
                   4390782976, 8884226048):
        assert str(number) in law["law"]
    del row["dense"]           # a cell of the dgc arm alone
    law = rehearse.memory_law(row)
    assert law["needs_bytes"] == 7439714304 + 4390782976 and law["fits"]


def test_the_memory_law_reads_the_residency():
    """The same numbers under ``one``: the larger of (state +
    temporaries) over the arms, which fits; and a cell that states ``one``
    where its arms fit together is refused, with the law's numbers."""
    row = {"cell": "wide", "chips": 1, "check_bytes": 8884226048,
           "param_bytes": 1845493760, "residency": "one",
           "dgc": {"argument_bytes": 7439714304, "temp_bytes": 4390782976},
           "dense": {"argument_bytes": 3691021312, "temp_bytes": 6359577600}}
    law = rehearse.memory_law(row)
    assert law["needs_bytes"] == 7439714304 + 4390782976 and law["fits"]
    assert "residency 'one'" in law["law"]
    assert str(7439714304 + 3691021312 + 6359577600) in law["law"]
    # absent, the key reads ``both``
    assert rehearse.memory_law({**row, "residency": "both"}) \
        == rehearse.memory_law({k: v for k, v in row.items()
                                if k != "residency"})
    small = {**row, "dgc": {"argument_bytes": 2264258048,
                            "temp_bytes": 4226406912},
             "dense": {"argument_bytes": 1128998912,
                       "temp_bytes": 4412540928}}          # vgg16_bn.steady
    assert rehearse.memory_law({**small, "residency": "both"})["fits"]
    law = rehearse.memory_law(small)
    assert not law["fits"] and "where both arms fit" in law["law"]
    assert str(2264258048 + 1128998912 + 4412540928) in law["law"]
    # and one that fits neither way
    big = {**row, "dgc": {"argument_bytes": 12 * 10 ** 9,
                          "temp_bytes": 5 * 10 ** 9}}
    assert not rehearse.memory_law(big)["fits"]


#: ``wide_lm`` (503,971,840 parameters, a row of 2048 tokens) compiled for
#: a described v5e: ``rehearse.aot_row``'s numbers (PR 32); the followers'
#: files since PR 40, two followed dispatches: dgc 3 x (p + momentum +
#: velocity + bits), dense p | p, buf | p, buf: 56.4 B a parameter
WIDE_LM = {"cell": "wide_lm", "chips": 1, "check_bytes": 9944003072,
           "param_bytes": 2015887360,
           "dgc": {"argument_bytes": 8126597120, "temp_bytes": 4131106816,
                   "follower_bytes": 18332050944},
           "dense": {"argument_bytes": 4031792128, "temp_bytes": 5103667200,
                     "follower_bytes": 10079436800}}


def test_the_wide_fixture_is_forced_to_one_arm_at_a_time():
    """The fixture that re-reads the law on the chip: with both arms
    resident it is refused, with the numbers; one at a time it fits the
    chip, its followers' files the disk and its follow the host."""
    both = rehearse.memory_law({**WIDE_LM, "residency": "both"})
    assert both["needs_bytes"] == 17262056448 > rehearse.HBM_BYTES
    assert not both["fits"] and "17262056448" in both["law"]
    one = rehearse.memory_law({**WIDE_LM, "residency": "one"})
    assert one["needs_bytes"] == 8126597120 + 4131106816 and one["fits"]
    assert one["disk_bytes"] == 18332050944 + 10079436800 < rehearse.TMP_BYTES
    assert one["disk_bytes"] / 503971840 == pytest.approx(56.4, abs=0.05)
    assert one["host_bytes"] == int(
        rehearse.HOST_BASELINE_BYTES
        + 7 * 2015887360) < rehearse.HOST_BYTES[1]
    # read on the chip: 31.4e9 at the peak of the follow (PR 32)
    assert 31.4e9 < one["host_bytes"] < 32e9
    for number in (one["disk_bytes"], one["host_bytes"],
                   rehearse.HOST_BYTES[1], int(rehearse.TMP_BYTES)):
        assert str(number) in one["law"]
    # files that do not fit the disk refuse the cell
    full = {**WIDE_LM, "residency": "one",
            "dgc": {**WIDE_LM["dgc"],
                    "follower_bytes": int(rehearse.TMP_BYTES)}}
    assert not rehearse.memory_law(full)["fits"]
    # and the files are what the fixture's followers would write
    import jax
    from benchmark import build
    for residency in cells.RESIDENCIES:
        cell = rehearse.fixture_cell("wide_lm." + residency)
        assert cell.traffic["residency"] == residency
        mesh = build.make_mesh(cell, jax.devices("cpu"))
        for name in ("dgc", "dense"):
            arm = build.build_arm(cell, name, mesh)
            state = jax.eval_shape(arm.init, jax.random.PRNGKey(0))
            assert model_check.Follower(cell, arm).kept_bytes(state) \
                == WIDE_LM[name]["follower_bytes"]
        assert cell.config["sizes"]["num_parameters"] * 4 \
            == WIDE_LM["param_bytes"]


def test_a_follower_keeps_what_its_follow_reads(tmp_path, monkeypatch):
    """Which parts of which snapshot, by arm and loop; their bytes; and
    that the host holds paths, not arrays, until the files are removed."""
    import jax
    import numpy as np
    from benchmark import build
    monkeypatch.setattr(model_check.tempfile, "tempdir", str(tmp_path))
    cell = rehearse.fixture_cell("tiny_lm.resident")
    scan = rehearse.fixture_cell("tiny_lm.scan")
    mesh = build.make_mesh(cell, jax.devices("cpu"))
    reads = {}
    for name in ("dgc", "dense"):
        arm = build.build_arm(cell, name, mesh)
        f = model_check.Follower(cell, arm)
        assert f.snapshots == 3
        reads[name] = [f.reads(i) for i in range(3)]
        reads[name + ".scan"] = [model_check.Follower(scan, arm).reads(i)
                                 for i in range(3)]
        state = build.init_state(arm, 0)
        shapes = jax.eval_shape(arm.init, jax.random.PRNGKey(0))
        run_ = run.ArmRun(arm, state, 0)
        for _ in range(4):         # the fourth is past the followed ones
            f.snapshot(run_)
        assert len(f.snaps) == 3
        files = sorted(os.listdir(tmp_path / os.listdir(tmp_path)[0]))
        assert sum(os.path.getsize(os.path.join(f._dir, x))
                   for x in files) == f.kept_bytes(shapes) \
            == f.written_bytes()
        assert all(isinstance(v, model_check._File) for snap in f.snaps
                   for k, v in snap.items() if k != "losses")
        np.testing.assert_array_equal(
            f.snaps[0]["params"].whole(), np.asarray(state.params))
        np.testing.assert_array_equal(
            f.snaps[0]["params"].read(7, 19), np.asarray(state.params)[7:19])
        if name == "dgc":
            np.testing.assert_array_equal(
                f.snaps[0]["memory.sent_bits"].row(0),
                np.asarray(state.memory["sent_bits"])[0])
        f.close()
        assert os.listdir(tmp_path) == []
    every, both = ("params", "memory"), ("params", "momentum")
    assert reads["dgc"] == [every] * 3
    assert reads["dgc.scan"] == [("params",)] * 2 + [()]
    # the dense arm's every step is followed from the program's own
    # parameters and buffer (empty before the first), and the buffer
    # after a step holds that step's gradient
    assert reads["dense"] == [("params",), both, both]
    assert reads["dense.scan"] == [("params",), both, ("params",)]
    # a configuration without a reference keeps nothing
    tiny = rehearse.fixture_cell("tiny.resident")
    arm = build.build_arm(tiny, "dgc", build.make_mesh(
        tiny, jax.devices("cpu")))
    f = model_check.Follower(tiny, arm)
    assert f.kept_bytes(jax.eval_shape(arm.init, jax.random.PRNGKey(0))) == 0
    f.snapshot(None)
    assert f.snaps == [] and os.listdir(tmp_path) == []


# ---------------------------------------------------------------------- #
# a token cell of new files only, and the two ways it must come out      #
# not correct                                                            #
# ---------------------------------------------------------------------- #

SCALED_REFERENCE = '''
import importlib.util
_spec = importlib.util.spec_from_file_location("sound", {sound!r})
_sound = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sound)
LOSS_RTOL, GRAD_RTOL = _sound.LOSS_RTOL, _sound.GRAD_RTOL
UPDATE_RTOL, CONSERVED_RTOL = _sound.UPDATE_RTOL, _sound.CONSERVED_RTOL


def loss_and_grads(params, inputs, labels):
    loss, grads = _sound.loss_and_grads(params, inputs, labels)
    grads = dict(grads, up={{"kernel": grads["up"]["kernel"] * {scale}}})
    return loss, grads
'''


def _new_token_cell(tmp_path, reference_scale=None, traffic_modules=(),
                    overrides=None, dgc_module=None,
                    reference_text=SCALED_REFERENCE, residency="both"):
    """What a later PR adds, all of it in ``tmp_path``: a configuration
    file, its reference, a traffic file, and their entries."""
    with open(os.path.join(rehearse.FIXTURE, "configs", "tiny_lm.json")) as fh:
        cfg = json.load(fh)
    cfg["overrides"] = overrides or {}
    if dgc_module is not None:
        (tmp_path / "dgc_more.py").write_text(dgc_module)
        cfg["dgc_modules"].append(
            os.path.relpath(tmp_path / "dgc_more.py", cells.ROOT))
    if reference_scale is not None:
        ref = tmp_path / "reference.py"
        ref.write_text(reference_text.format(
            sound=os.path.join(cells.ROOT, cfg["reference"]),
            scale=reference_scale))
        cfg["reference"] = os.path.relpath(ref, cells.ROOT)
    (tmp_path / "lm2.json").write_text(json.dumps(cfg))
    with open(os.path.join(TRAFFIC, "tiny_lm.resident.json")) as fh:
        traffic = json.load(fh)
    traffic.update(zipf_s=1.1, doc_len_median=5, doc_len_sigma=1.0,
                   modules=list(traffic_modules), residency=residency)
    (tmp_path / "skewed.json").write_text(json.dumps(traffic))
    bench = copy.deepcopy(cells.load_benchmark(
        os.path.join(rehearse.FIXTURE, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "lm2", "reduced": [],
        "file": os.path.relpath(tmp_path / "lm2.json", cells.ROOT)})
    bench["workloads"].append({"name": "lm2.skewed", "config": "lm2",
                               "traffic": "skewed", "chips": 1, "why": "new"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("lm2.skewed")
    return cells.load_cell("lm2.skewed", bench=bench,
                           traffic_dir=str(tmp_path))


def _measure(cell):
    import jax
    return run.measure(cell, seed=2147483659, seconds=0.2, trace=False,
                       devices=jax.devices("cpu"))


def test_a_new_token_cell_runs_and_is_correct(tmp_path):
    m = _measure(_new_token_cell(tmp_path))
    assert m["check"]["ok"] and m["model_check"]["ok"] and m["step0_ok"]
    assert m["attempted"] > 0 and run.is_correct(m)
    # every number the verdict rests on stands beside its limit, and in a
    # correct run is inside it (a floor is met from above)
    got = run.compared(m)
    assert set(got) == {
        "step0_loss_gap", "nonfinite_losses", "pack.misplaced_coords",
        "check.uncovered_kernels", "exchange.record_wrong_bits",
        "exchange.buffer_unexplained_coords",
        "exchange.update_unexplained_coords", "exchange.forms_differ_coords",
        "exchange.inexact_residual_coords", "exchange.unconserved_coords",
        "exchange.over_quota_rows", "exchange.sent_outside_rows",
        "exchange.fill_floor", "exchange.recall_floor",
        "exchange.bucket_recall_floor", "dgc.loss_rel_err",
        "dgc.conserved_rel_err", "dgc.unexplained_coords",
        "dense.loss_rel_err", "dense.grad_rel_err", "dense.update_norm_gap"}
    assert all(number >= limit if name.endswith("_floor")
               else number <= limit for name, (number, limit) in got.items())
    # a reference scaled by exactly 1 is the sound one: the wrapper itself
    # changes nothing
    m = _measure(_new_token_cell(tmp_path, reference_scale=1.0))
    assert run.is_correct(m)


#: what ``compared`` holds in a cell whose configuration has no reference
#: of its model: the benchmark's three cells (since PR 32)
CELLS_COMPARED = [
    "step0_loss_gap", "nonfinite_losses", "pack.misplaced_coords",
    "check.uncovered_kernels", "exchange.record_wrong_bits",
    "exchange.buffer_unexplained_coords",
    "exchange.update_unexplained_coords", "exchange.forms_differ_coords",
    "exchange.inexact_residual_coords",
    "exchange.unconserved_coords", "exchange.over_quota_rows",
    "exchange.sent_outside_rows", "exchange.fill_floor",
    "exchange.recall_floor", "exchange.bucket_recall_floor"]


def _a_cells_measurement(**check):
    return {"step0_gap": 2e-6, "failed": 0,
            "model_check": {"ok": True, "skipped": "no reference"},
            "check": {"inexact_residual_coords": 0, "unconserved_coords": 0,
                      "over_quota_rows": 0, "sent_outside_rows": 0,
                      "fill": 0.93, "fill_floor": 0.8, "recall": 0.99,
                      "recall_floor": 0.95, "recall_per_bucket": [0.99, 0.96],
                      "recall_floor_per_bucket": [0.948, 0.93],
                      "misplaced_coords": 0, "uncovered_kernels": [],
                      "record_wrong_bits": 0, "buffer_unexplained_coords": 0,
                      "update_unexplained_coords": 0,
                      "forms_differ_coords": 0, **check}}


def test_compared_lists_the_number_nearest_its_limit_first():
    """The cells' ``compared`` holds the entries it held, every one; the
    order is by nearness to the limit, a number outside its limit (a
    count over 0, a floor missed, a NaN) before all others, so a record
    that keeps the first few keeps the one that decided."""
    got = run.compared(_a_cells_measurement())
    assert sorted(got) == sorted(CELLS_COMPARED)
    # floors: 0.93 / 0.96 (the bucket nearest ITS floor), 0.95 / 0.99,
    # 0.8 / 0.93; then 2e-6 of 1e-4; the counts, all 0, as they were listed
    assert list(got) == [
        "exchange.bucket_recall_floor", "exchange.recall_floor",
        "exchange.fill_floor", "step0_loss_gap", "nonfinite_losses",
        "pack.misplaced_coords", "check.uncovered_kernels",
        "exchange.record_wrong_bits", "exchange.buffer_unexplained_coords",
        "exchange.update_unexplained_coords", "exchange.forms_differ_coords",
        "exchange.inexact_residual_coords", "exchange.unconserved_coords",
        "exchange.over_quota_rows", "exchange.sent_outside_rows"]
    assert got["exchange.bucket_recall_floor"] == [0.96, 0.93]
    # a fault under the offered step is seen by several counts: the one
    # nearest the fault is listed first
    assert list(run.compared(_a_cells_measurement(
        record_wrong_bits=1, forms_differ_coords=1)))[:2] == [
            "exchange.record_wrong_bits", "exchange.forms_differ_coords"]
    assert run.compared(_a_cells_measurement(uncovered_kernels=[
        "payload_update_bits"]))["check.uncovered_kernels"] == [1, 0]
    assert list(run.compared(_a_cells_measurement(unconserved_coords=3)))[0] \
        == "exchange.unconserved_coords"
    assert list(run.compared(_a_cells_measurement(fill=0.7)))[0] \
        == "exchange.fill_floor"
    m = _a_cells_measurement()
    m["model_check"] = {"ok": False, "limits": {"grad_rel_err": 3e-6,
                                                "unexplained_coords": 0},
                        "arms": {"dense": {"grad_rel_err": {
                            "max": float("nan")}},
                            "dgc": {"unexplained_coords": {"max": 0}}}}
    got = run.compared(m)
    assert list(got)[0] == "dense.grad_rel_err"
    assert set(got) == set(CELLS_COMPARED) | {"dense.grad_rel_err",
                                              "dgc.unexplained_coords"}
    assert run.nearness("x", 1.5e-6, 3e-6) == 0.5
    assert run.nearness("x_floor", 0.5, 0.8) == 1.6
    assert run.nearness("count", 0, 0) == 0.0


def test_arms_resident_one_at_a_time_keep_the_metrics_names(tmp_path):
    """``residency: one``: all of the dgc arm's rounds, then all of the
    dense arm's, each arm for half the window; the end-to-end metrics keep
    their names, the overhead being the difference of the arms' medians;
    the verdict rests on the same numbers as under ``both``."""
    m = _measure(_new_token_cell(tmp_path, residency="one"))
    assert run.is_correct(m) and m["residency"] == "one"
    arms = [next(iter(row)) for row in m["rows"]]
    assert all(len(row) == 1 for row in m["rows"])
    n_dgc = arms.count("dgc")
    assert arms == ["dgc"] * n_dgc + ["dense"] * (len(arms) - n_dgc)
    assert n_dgc >= 1 and len(arms) - n_dgc >= 1
    # 0.2 s of window: each arm ran for its half, and no longer than a
    # round more
    per_arm = {a: sum(row[a] for row in m["rows"] if a in row)
               for a in ("dgc", "dense")}
    assert all(0.09 <= v < 0.2 for v in per_arm.values()), per_arm
    assert m["window_s"] == pytest.approx(sum(per_arm.values()), rel=0.05)
    paired = run.paired_summary(m)
    values = run.end_to_end_values(m, paired)
    assert set(values) == {"setup_s", "step_ms", "dense_step_ms",
                           "dgc_overhead_ms"}
    assert values["dgc_overhead_ms"] == pytest.approx(
        values["step_ms"] - values["dense_step_ms"])
    assert set(paired["dgc_minus_dense_ms"]) == {"median"}
    assert m["attempted"] == len(arms) * m["steps_per_round"]
    both = _measure(_new_token_cell(tmp_path))
    assert set(run.compared(m)) == set(run.compared(both))
    assert m["model_check"]["arms"] == both["model_check"]["arms"]
    clocks = ("check_s", "parts_s")
    assert {k: v for k, v in m["check"].items() if k not in clocks} == {
        k: v for k, v in both["check"].items() if k not in clocks}
    assert set(run.paired_summary(both)["dgc_minus_dense_ms"]) == {
        "q1", "median", "q3"}


@pytest.mark.parametrize("arm", ["dgc", "dense"])
def test_a_compile_in_either_half_of_the_window_voids_the_run(
        tmp_path, monkeypatch, arm):
    """Nothing compiles in the window: held per half where the arms come
    one after the other. Each arm's first round is its discarded warm-up;
    a program built in its second is built inside its half."""
    import jax
    real, calls = run.run_round, {"dgc": 0, "dense": 0}

    def run_round(arm_run, *args):
        calls[arm_run.name] += 1
        if arm_run.name == arm and calls[arm] == 2:
            jax.jit(lambda x: x * 3 + len(arm))(1.0)
        return real(arm_run, *args)

    monkeypatch.setattr(run, "run_round", run_round)
    with pytest.raises(SystemExit, match="compiled inside the measured"):
        _measure(_new_token_cell(tmp_path, residency="one"))


_LIMITS = cells.load_reference(os.path.relpath(os.path.join(
    rehearse.FIXTURE, "references", "tiny_lm.py"), cells.ROOT))
LOSS_RTOL, GRAD_RTOL = _LIMITS.LOSS_RTOL, _LIMITS.GRAD_RTOL
CONSERVED_RTOL = _LIMITS.CONSERVED_RTOL


NESTEROV_MEMORY = ("from dgc_tpu.utils.config import configs\n"
                   "configs.train.compression.memory.nesterov = True\n")


@pytest.mark.parametrize("overrides, dgc_module", [
    ({"train.optimizer.weight_decay": 0.01,
      "train.optimizer.nesterov": True}, NESTEROV_MEMORY),
    ({"train.optimizer.weight_decay": 0.01}, None),
], ids=["decay-nesterov", "decay"])
def test_the_reference_follows_the_optimizer_as_configured(
        tmp_path, monkeypatch, overrides, dgc_module):
    """The plain SGD beside the reference reads the recipe the arm was
    built with: weight decay, nesterov; DGC's memory too. With every
    tensor cut into several pieces, as a large model's are."""
    monkeypatch.setattr(model_check, "PIECE", 1000)
    m = _measure(_new_token_cell(tmp_path, overrides=overrides,
                                 dgc_module=dgc_module))
    assert run.is_correct(m), m["model_check"]


def test_without_momentum_the_gradient_is_read_from_the_parameters(tmp_path):
    """No momentum buffer holds the first gradient then: it is the
    parameters' change over the learning rate, to float32's rounding of
    the parameters (1.2e-4 here, where the buffer reads 0)."""
    m = _measure(_new_token_cell(
        tmp_path, overrides={"train.optimizer.momentum": 0.0}))
    got = _maxima(m["model_check"])
    assert 1e-5 < got["dense", "grad_rel_err"] < 1e-3
    assert got["dense", "update_norm_gap"] <= _LIMITS.UPDATE_RTOL
    assert got["dgc", "conserved_rel_err"] <= CONSERVED_RTOL
    assert got["dense", "loss_rel_err"] <= LOSS_RTOL


def _others_sound(m):
    return m["check"]["ok"] and m["step0_ok"] and m["failed"] == 0


def _maxima(model):
    return {(arm, key): value["max"]
            for arm, numbers in model["arms"].items()
            for key, value in numbers.items() if isinstance(value, dict)}


@RESIDENCIES
def test_a_reference_with_one_gradient_scaled_is_not_correct(
        tmp_path, residency):
    m = _measure(_new_token_cell(tmp_path, reference_scale=1.01,
                                 residency=residency))
    model = m["model_check"]
    assert _others_sound(m) and not model["ok"] and not run.is_correct(m)
    for arm, key in (("dense", "grad_rel_err"), ("dgc", "conserved_rel_err")):
        worst = model["arms"][arm][key]
        assert worst["worst_tensor"] == "up/kernel"
        # 1% of that tensor's norm, over the median tensor's where that
        # is the larger
        assert 30 * GRAD_RTOL < worst["max"] <= 1.001 * 0.01 / 1.01
    assert model["arms"]["dense"]["loss_rel_err"]["max"] <= LOSS_RTOL


@RESIDENCIES
def test_a_bfloat16_model_against_a_float32_file_is_not_correct(
        tmp_path, residency):
    """The control: the configuration file says float32, and the traffic
    composes ``configs/bf16.py`` after it, the step a later PR might take."""
    m = _measure(_new_token_cell(tmp_path, residency=residency,
                                 traffic_modules=["configs/bf16.py"]))
    model = m["model_check"]
    assert _others_sound(m) and not model["ok"] and not run.is_correct(m)
    got = _maxima(model)
    assert got["dense", "grad_rel_err"] > 30 * GRAD_RTOL
    assert got["dgc", "conserved_rel_err"] > 10 * CONSERVED_RTOL


def _break_the_step(monkeypatch, broken_step):
    """Every arm's step replaced by ``broken_step(step)``, underneath a
    run that is otherwise whole."""
    from benchmark import build
    real = build.build_arm

    def build_arm(cell, name, mesh):
        arm = real(cell, name, mesh)
        broken = broken_step(arm.step)
        # it lowers what the real one does (the check reads its kernels)
        broken.lower = arm.step.lower
        return arm._replace(step=broken)

    monkeypatch.setattr(build, "build_arm", build_arm)


@RESIDENCIES
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch, residency):
    import jax
    import jax.numpy as jnp

    def unchanged(step):
        def broken(state, x, y, key):
            kept = jax.tree.map(jnp.copy, state)    # the state is donated
            return kept, step(state, x, y, key)[1]
        return broken

    _break_the_step(monkeypatch, unchanged)
    m = _measure(_new_token_cell(tmp_path, residency=residency))
    model = m["model_check"]
    # every loss is finite, the exchange engine is sound, the arms start
    # from the same loss: only the model check sees it
    assert _others_sound(m) and not model["ok"] and not run.is_correct(m)
    got = _maxima(model)
    assert got["dense", "update_norm_gap"] == pytest.approx(1.0)
    assert got["dense", "grad_rel_err"] == pytest.approx(1.0)
    assert got["dgc", "conserved_rel_err"] == pytest.approx(1.0)
    number, limit = run.compared(m)["dgc.conserved_rel_err"]
    assert number == got["dgc", "conserved_rel_err"] > limit


@RESIDENCIES
def test_a_step_that_trains_on_half_the_batch_is_not_correct(
        tmp_path, monkeypatch, residency):
    import jax.numpy as jnp

    def half(step):
        def broken(state, x, y, key):
            rows, labels = x.shape[0] // 2, y.shape[0] // 2
            return step(state, jnp.concatenate([x[:rows], x[:rows]]),
                        jnp.concatenate([y[:labels], y[:labels]]), key)
        return broken

    _break_the_step(monkeypatch, half)
    m = _measure(_new_token_cell(tmp_path, residency=residency))
    model = m["model_check"]
    # both arms are broken alike, so their first losses still agree
    assert _others_sound(m) and not model["ok"] and not run.is_correct(m)
    got = _maxima(model)
    assert got["dense", "loss_rel_err"] > 1000 * LOSS_RTOL
    assert got["dgc", "loss_rel_err"] > 1000 * LOSS_RTOL


ROW_BLOCK_REFERENCE = SCALED_REFERENCE.replace(
    "def loss_and_grads", "ROW_BLOCK = 1\n\n\ndef loss_and_grads")


def test_a_reference_called_a_row_at_a_time_gives_the_whole_batchs_numbers(
        tmp_path):
    import jax
    import numpy as np
    sound = os.path.join(rehearse.FIXTURE, "references", "tiny_lm.py")
    (tmp_path / "blocked.py").write_text(
        ROW_BLOCK_REFERENCE.format(sound=sound, scale=1.0))
    blocked = cells.load_reference(
        os.path.relpath(tmp_path / "blocked.py", cells.ROOT))
    assert blocked.ROW_BLOCK == 1 and not hasattr(_LIMITS, "ROW_BLOCK")
    (tmp_path / "no_rows.py").write_text(
        ROW_BLOCK_REFERENCE.replace("ROW_BLOCK = 1", "ROW_BLOCK = 0").format(
            sound=sound, scale=1.0))
    with pytest.raises(cells.CellError, match="ROW_BLOCK must be a number "
                                              "of rows, at least 1, got 0"):
        cells.load_reference(
            os.path.relpath(tmp_path / "no_rows.py", cells.ROOT))
    rng = np.random.default_rng(0)
    params = {"embed": {"embedding": rng.normal(size=(64, 8))},
              "gate": {"kernel": rng.normal(size=(8, 16))},
              "up": {"kernel": rng.normal(size=(8, 16))},
              "down": {"kernel": rng.normal(size=(16, 8))},
              "head": {"kernel": rng.normal(size=(8, 64))}}
    params = jax.tree.map(lambda a: a.astype(np.float32) * 0.3, params)
    rows, seq = 5, 7
    tokens = rng.integers(0, 64, size=(rows, seq + 1), dtype=np.int32)
    batch = tokens[:, :-1], tokens[:, 1:].reshape(-1)
    with jax.default_matmul_precision("highest"):
        loss, grads = model_check._loss_and_grads(_LIMITS)(params, *batch)
        loss_b, grads_b = model_check._loss_and_grads(blocked)(params, *batch)
    assert float(loss_b) == pytest.approx(float(loss), rel=1e-6)
    for want, got in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_b)):
        assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)
    # and under a whole run the four numbers stay inside their limits
    m = _measure(_new_token_cell(tmp_path, reference_scale=1.0,
                                 reference_text=ROW_BLOCK_REFERENCE))
    assert run.is_correct(m), m["model_check"]
    limits = m["model_check"]["limits"]
    assert all(value <= limits[key]
               for (_, key), value in _maxima(m["model_check"]).items())


def test_a_nan_in_one_tensor_is_the_worst_and_not_correct():
    ref = {"a": [3.0, 4.0], "b": [1.0, 0.0], "c": [0.0, 2.0]}
    prog = {"a": [3.0, 4.0], "b": [float("nan"), 0.0], "c": [0.0, 2.0]}

    def triples(prog, ref):
        return ((n, prog[n], ref[n]) for n in ref)

    worst = model_check._worst(triples(prog, ref))
    assert worst["worst_tensor"] == "b" and worst["max"] != worst["max"]
    assert model_check._worst(triples(ref, ref))["max"] == 0.0
    # an all-but-zero tensor is measured against the median tensor's norm
    tiny = model_check._leafwise(triples({**ref, "b": [1e-9, 0.0]},
                                         {**ref, "b": [0.0, 0.0]}))
    assert tiny["rel_err"]["b"] == pytest.approx(1e-9 / 2.0)
    # a tensor given in pieces reads as it does whole
    whole = model_check._leafwise([("a", [3.0, 4.5, 1.0], [3.0, 4.0, 2.0]),
                                   ("b", [1.0], [7.0])])
    cut = model_check._leafwise([(("a", 0, 2), [3.0, 4.5], [3.0, 4.0]),
                                 (("a", 2, 3), [1.0], [2.0]),
                                 (("b", 0, 1), [1.0], [7.0])])
    assert cut == whole
