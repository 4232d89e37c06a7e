"""The five per-layer readers of PR 45 (layer ``model``: the device parts
``dgc_tpu/models/sambay.py`` puts under ``fwd_bwd``) on a small hand-made
trace with and without their tokens and on the two chip fixtures, whose
conv nets have no such part; and the traffic file the new cell brings, the
first to state ``residency: one``. ``benchmark/conftest.py`` says which
pinned tests these stand in for until a ``benchmark`` PR folds them."""

import math
import os

import pytest

from benchmark import cells, program_records
from test_program_readers import _read
from test_trace_reduce import _chip_view, _host, _meta, _op, _view

FWD = "jit(step_fn)/dgcph.fwd_bwd/"
PARTS = ("ssm", "attn", "gmu", "mlp", "head")


def model_trace(parts=True):
    """Two arms, one chip, one step each; microseconds. With ``parts`` the
    model's ops carry their part under ``fwd_bwd``, a backward op inside
    ``transpose(jvp(...))``; without, the phase alone, as a conv net's."""
    def scope(part):
        return f"dgcph.fwd_bwd.{part}/" if parts else ""

    return [
        _meta(1, "/device:TPU:0"), _meta(9, "/host:CPU"),
        _host("dgc:segment", 0, 2000),
        _op(1, "fusion.1", 100, 10, FWD + "embedding/gather:"),
        _op(1, "fusion.2", 110, 200, FWD + scope("attn") + "dot_general:"),
        _op(1, "while.3", 310, 300, FWD + scope("ssm") + "while:"),
        _op(1, "fusion.4", 610, 50, FWD + scope("gmu") + "mul:"),
        _op(1, "fusion.5", 660, 400, FWD + scope("mlp") + "dot_general:"),
        _op(1, "fusion.6", 1060, 90, FWD + scope("head") + "dot_general:"),
        _op(1, "fusion.7", 1150, 410, FWD + "transpose(jvp(" + scope("mlp")
            + "))/dot_general:"),
        _op(1, "fusion.8", 1560, 70, FWD + "dgcph.fwd_bwd.pack/concatenate:"),
        _host("dense:segment", 3000, 2000),
        # the dense arm's parts are not the metric's
        _op(1, "fusion.5", 3100, 395, FWD + scope("mlp") + "dot_general:"),
    ]


@pytest.mark.parametrize("part, want", [
    ("ssm", 0.3), ("attn", 0.2), ("gmu", 0.05), ("mlp", 0.81),
    ("head", 0.09)])
def test_model_readers_on_a_trace_with_and_without_the_tokens(part, want):
    for parts, value in ((True, want), (False, 0.0)):
        view = _view(model_trace(parts), {"dgc": 1, "dense": 1})
        got = _read(view, f"model.{part}_ms")
        assert got == pytest.approx(value) and math.isfinite(got)
        # a part reads as its phase: the tables are the same either way
        assert view["tables"]["dgc"]["phases"]["fwd_bwd"] == pytest.approx(
            1.53)


@pytest.mark.parametrize("fixture, steps", [
    ("chip_trace_vgg16_bn.json.gz", 4), ("chip_trace_vgg16_bn_x4.json.gz", 1)])
def test_model_readers_read_zero_on_a_conv_net(monkeypatch, fixture, steps):
    monkeypatch.setattr(program_records, "records", lambda: [])
    view = _chip_view(fixture, steps)
    for part in PARTS:
        assert _read(view, f"model.{part}_ms") == 0.0


def test_the_five_are_the_benchmarks_last_entries_and_owed_by_every_cell():
    bench = cells.load_benchmark()
    last = bench["per_layer"][-5:]
    assert [e["name"] for e in last] == [f"model.{p}_ms" for p in PARTS]
    for e in last:
        assert "workloads" not in e and e["layer"] == "model"
        assert e["moves"] == "step_ms" and e["source"] == "device_trace"
    for w in bench["workloads"]:
        names = [e["name"] for e in cells.load_cell(
            w["name"], bench=bench).per_layer]
        assert names[-5:] == [f"model.{p}_ms" for p in PARTS]


def test_the_new_cells_traffic_states_residency_one():
    folder = os.path.join(cells.BENCH_DIR, "traffic")
    states = {name[:-len(".json")]: cells.load_traffic(
        name[:-len(".json")])["residency"] for name in sorted(
            os.listdir(folder))}
    assert states.pop("steady.s2048.one") == "one"
    assert set(states.values()) == {"both"}
    cell = cells.load_cell("phi4_mini_flash.steady")
    assert cell.chips == 1 and cell.traffic["zipf_s"] == 1.0
    assert cell.config["dataset"]["kind"] == "tokens"
    assert cell.config["matmul_precision"] == "highest"
    assert {m["name"] for m in cell.end_to_end} == {
        "step_ms", "dense_step_ms", "peak_hbm_gib", "setup_s"}
