"""Cells, configurations, traffic mixes and readers load by name; a missing
or malformed one fails loudly; a new one needs new files and entries only."""

import copy
import json
import os
import shutil

import pytest

from benchmark import cells

FIXTURE = os.path.join(cells.BENCH_DIR, "tests", "fixtures", "rehearsal")


def test_every_cell_of_the_benchmark_loads():
    bench = cells.load_benchmark()
    assert bench["paths"] == ["benchmark"]
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], bench=bench)
        assert cell.chips in (1, 4)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(cells.load_reader(m["name"]))
        for mod in cell.config["modules"] + cell.config["dgc_modules"]:
            assert os.path.isfile(os.path.join(cells.ROOT, mod))


def test_every_traffic_file_loads():
    folder = os.path.join(cells.BENCH_DIR, "traffic")
    for name in sorted(os.listdir(folder)):
        t = cells.load_traffic(name[:-len(".json")])
        assert t["input"] in cells.INPUTS and t["loop"] in cells.LOOPS


def test_unknown_workload_names_the_known_ones():
    with pytest.raises(cells.CellError, match="resnet50.steady"):
        cells.load_cell("no.such.cell")


def _write(path, obj):
    with open(path, "w") as fh:
        if isinstance(obj, str):
            fh.write(obj)
        else:
            json.dump(obj, fh)


GOOD_TRAFFIC = {"per_chip_batch": 4, "arms": ["dgc", "dense"],
                "input": "resident", "round_steps": 3, "trace_steps": 2,
                "loop": "dispatch"}


@pytest.mark.parametrize("change, message", [
    ({"input": "disk"}, "'input' must be one of"),
    ({"arms": ["dgc", "dgc"]}, "'arms' must be distinct"),
    ({"arms": []}, "'arms' must be distinct"),
    ({"round_steps": 0}, "'round_steps' must be at least 1"),
    ({"round_steps": "ten"}, "'round_steps' must be int"),
    ({"per_chip_batch": True}, "'per_chip_batch' must be int"),
    ({"loop": "scan"}, "needs 'k'"),
    ({"loop": "scan", "k": 4, "input": "pipeline"}, "needs input 'resident'"),
    ({"k": 4}, "'k' belongs to loop 'scan'"),
    ({"compress_ratio": 2}, "'compress_ratio' must be null or in"),
    ({"rate": 5}, r"unknown key\(s\) \['rate'\]"),
])
def test_malformed_traffic_fails_loudly(tmp_path, change, message):
    _write(tmp_path / "bad.json", {**GOOD_TRAFFIC, **change})
    with pytest.raises(cells.CellError, match=message):
        cells.load_traffic("bad", str(tmp_path))


@pytest.mark.parametrize("missing", sorted(
    k for k in GOOD_TRAFFIC if k not in ("k",)))
def test_traffic_key_missing_fails_loudly(tmp_path, missing):
    _write(tmp_path / "bad.json",
           {k: v for k, v in GOOD_TRAFFIC.items() if k != missing})
    with pytest.raises(cells.CellError, match=f"'{missing}' is missing"):
        cells.load_traffic("bad", str(tmp_path))


def test_traffic_file_missing_or_not_json(tmp_path):
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_traffic("absent", str(tmp_path))
    _write(tmp_path / "torn.json", '{"per_chip_batch": 4,')
    with pytest.raises(cells.CellError, match="not JSON"):
        cells.load_traffic("torn", str(tmp_path))


def _fixture_bench():
    return cells.load_benchmark(os.path.join(FIXTURE, "BENCHMARK.json"))


def _config_entry(tmp_path, **change):
    with open(os.path.join(FIXTURE, "configs", "tiny.json")) as fh:
        cfg = json.load(fh)
    cfg.update(change)
    for key in [k for k, v in change.items() if v is None]:
        del cfg[key]
    path = tmp_path / "cfg.json"
    _write(path, cfg)
    return {"name": "tiny", "reduced": [],
            "file": os.path.relpath(path, cells.ROOT)}


@pytest.mark.parametrize("change, message", [
    ({"modules": ["configs/no/such.py"]}, "is not in the repo"),
    ({"modules": None}, "'modules' is missing"),
    ({"sizes": {"image_size": 32, "num_classes": 10}},
     "'num_parameters' is missing"),
    ({"reduced": ["depth"]}, "differs from BENCHMARK.json"),
    ({"assumed": None}, "'assumed' is missing"),
    ({"deployment": 4}, "'deployment' must be str"),
])
def test_malformed_config_fails_loudly(tmp_path, change, message):
    with pytest.raises(cells.CellError, match=message):
        cells.load_config(_config_entry(tmp_path, **change))


def test_config_file_missing():
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_config({"name": "x", "file": "benchmark/configs/x.json"})


def test_reader_missing_or_without_read(tmp_path):
    with pytest.raises(cells.CellError, match="no reader at"):
        cells.load_reader("no.such_metric")
    _write(tmp_path / "empty.metric.py", "VALUE = 1\n")
    with pytest.raises(cells.CellError, match="defines no read"):
        cells.load_reader("empty.metric", str(tmp_path))


def test_per_layer_metric_must_move_a_metric_of_the_cell():
    bench = _fixture_bench()
    bench["per_layer"].append(
        {"name": "input.wait_ms2", "unit": "ms", "layer": "input",
         "moves": "dgc_overhead_ms", "workloads": ["tiny.steady.x4"]})
    with pytest.raises(cells.CellError, match="does not report"):
        cells.load_cell("tiny.steady.x4", bench=bench,
                        traffic_dir=os.path.join(FIXTURE, "traffic"))


def test_a_pair_of_config_and_traffic_is_given_once(tmp_path):
    """The contract's rule, which refused this benchmark once: the cell on
    four chips needs a traffic file of its own."""
    bench = _fixture_bench()
    bench["workloads"].append({"name": "tiny.resident.again",
                               "config": "tiny", "traffic": "tiny.resident",
                               "chips": 4, "why": "the same pair"})
    _write(tmp_path / "BENCHMARK.json", bench)
    with pytest.raises(cells.CellError, match="given twice"):
        cells.load_benchmark(str(tmp_path / "BENCHMARK.json"))


def test_peaks_unknown_device_kind_is_an_error():
    assert cells.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.CellError, match="no entry for device kind"):
        cells.load_peaks("TPU v9")


def test_new_cell_config_traffic_and_metric_need_only_new_files(tmp_path):
    """What a later PR does: new files and new entries, no edit."""
    bench = copy.deepcopy(_fixture_bench())
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(FIXTURE, "traffic"), traffic_dir)
    _write(traffic_dir / "burst.json", {**GOOD_TRAFFIC, "round_steps": 7,
                                        "modules": ["configs/bf16.py"]})
    entry = _config_entry(tmp_path, deployment="a second deployment")
    entry["name"] = "tiny2"
    bench["configs"].append(entry)
    bench["workloads"].append({"name": "tiny2.burst", "config": "tiny2",
                               "traffic": "burst", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "new.metric", "unit": "ms",
                               "better": "lower", "layer": "input",
                               "source": "host_clock", "moves": "step_ms",
                               "workloads": ["tiny2.burst"]})
    _write(tmp_path / "new.metric.py",
           "def read(trace, spans, cell):\n    return 1.5\n")
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny2.burst")

    cell = cells.load_cell("tiny2.burst", bench=bench,
                           traffic_dir=str(traffic_dir))
    assert cell.traffic["round_steps"] == 7
    assert cell.traffic["modules"] == ["configs/bf16.py"]
    assert cell.config["deployment"] == "a second deployment"
    assert "new.metric" in {m["name"] for m in cell.per_layer}
    assert cells.load_reader("new.metric", str(tmp_path))(None, {}, cell) \
        == 1.5
    # and the cells that were there are untouched
    old = cells.load_cell("tiny.steady", bench=bench,
                          traffic_dir=str(traffic_dir))
    assert "new.metric" not in {m["name"] for m in old.per_layer}
