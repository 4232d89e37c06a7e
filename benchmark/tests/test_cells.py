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
        # none states ``residency``: every cell's arms share the chip
        assert t["residency"] == "both"


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
    ({"residency": "two"},
     r"'residency' must be one of \['both', 'one'\], got 'two'"),
    ({"residency": 1}, "'residency' must be str"),
    ({"zipf_s": -0.5}, "'zipf_s' must be at least 0"),
    ({"zipf_s": "one"}, "'zipf_s' must be a number"),
    ({"zipf_s": 1.0, "doc_len_median": 0, "doc_len_sigma": 1.0},
     "'doc_len_median' must be null"),
    ({"zipf_s": 1.0, "doc_len_median": 64}, "give both or neither"),
    ({"zipf_s": 1.0, "doc_len_sigma": 0.5}, "give both or neither"),
    ({"zipf_s": 1.0, "doc_len_median": 64, "doc_len_sigma": -1},
     "'doc_len_sigma' must be at least 0"),
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


def _config_entry(tmp_path, base="tiny", **change):
    """The fixture configuration ``base`` with top-level keys changed (a
    None deletes the key; ``"null"`` writes a JSON null), as a new file."""
    with open(os.path.join(FIXTURE, "configs", base + ".json")) as fh:
        cfg = json.load(fh)
    cfg.update(change)
    for key, value in change.items():
        if value is None:
            del cfg[key]
        elif value == "null":
            cfg[key] = None
    path = tmp_path / "cfg.json"
    _write(path, cfg)
    return {"name": base, "reduced": [],
            "file": os.path.relpath(path, cells.ROOT)}


TOKENS = {"kind": "tokens", "epoch_examples": 1000, "eos_id": 0}
LM_SIZES = {"num_parameters": 50560, "seq_len": 16, "vocab_size": 512}


@pytest.mark.parametrize("change, message", [
    ({"modules": ["configs/no/such.py"]}, "is not in the repo"),
    ({"modules": None}, "'modules' is missing"),
    ({"sizes": {"image_size": 32, "num_classes": 10}},
     "'num_parameters' is missing"),
    ({"reduced": ["depth"]}, "differs from BENCHMARK.json"),
    ({"assumed": None}, "'assumed' is missing"),
    ({"deployment": 4}, "'deployment' must be str"),
    ({"dataset": None}, "'dataset' is missing"),
    ({"dataset": {"kind": "audio", "epoch_examples": 9}},
     "'kind' must be one of"),
    ({"dataset": {"epoch_examples": 9}}, "'kind' is missing"),
    ({"dataset": {"kind": "images"}}, "'epoch_examples' is missing"),
    ({"dataset": {"kind": "images", "epoch_examples": 0}},
     "'epoch_examples' must be at least 1"),
    ({"dataset": TOKENS}, "'seq_len' is missing"),
    ({"reference": None}, "key 'reference' is missing"),
    ({"reference_why": None}, "'reference_why' is missing"),
    ({"reference": "benchmark/references/absent.py"},
     "reference 'benchmark/references/absent.py' is not in the repo"),
    ({"reference": 7}, "'reference' must be str"),
])
def test_malformed_config_fails_loudly(tmp_path, change, message):
    with pytest.raises(cells.CellError, match=message):
        cells.load_config(_config_entry(tmp_path, **change))


@pytest.mark.parametrize("change, message", [
    ({"sizes": {k: v for k, v in LM_SIZES.items() if k != "seq_len"}},
     "'seq_len' is missing"),
    ({"sizes": {**LM_SIZES, "vocab_size": "many"}},
     "'vocab_size' must be int"),
    ({"dataset": {k: v for k, v in TOKENS.items() if k != "eos_id"}},
     "'eos_id' is missing"),
    ({"dataset": {**TOKENS, "eos_id": 512}}, "is not an id of a vocabulary"),
    ({"dataset": {**TOKENS, "eos_id": -1}}, "is not an id of a vocabulary"),
    ({"dataset": {**TOKENS, "epoch_examples": 0}},
     "'epoch_examples' must be at least 1"),
    ({"reference": "null"}, "'reference_why' is missing"),
    ({"matmul_precision": None}, "'matmul_precision' is missing"),
    ({"matmul_precision": "bfloat16"}, "'matmul_precision' must be one of"),
])
def test_malformed_tokens_config_fails_loudly(tmp_path, change, message):
    with pytest.raises(cells.CellError, match=message):
        cells.load_config(_config_entry(tmp_path, base="tiny_lm", **change))


@pytest.mark.parametrize("config, change, message", [
    ("tiny", {"zipf_s": 1.0},
     r"\['zipf_s'\] say how token sequences are made.*kind is 'images'"),
    ("tiny", {"doc_len_median": 8, "doc_len_sigma": 0.5},
     r"\['doc_len_median', 'doc_len_sigma'\] say how token"),
    ("tiny_lm", {}, "'zipf_s' is missing"),
    ("tiny_lm", {"zipf_s": 1.0, "input": "pipeline"},
     "the program has no token split yet"),
])
def test_traffic_that_does_not_fit_the_data_kind(tmp_path, config, change,
                                                 message):
    """The traffic file says how sequences are made for a 'tokens'
    configuration only; the error names the traffic file and the key."""
    _write(tmp_path / "odd.json", {**GOOD_TRAFFIC, **change})
    bench = _fixture_bench()
    bench["workloads"].append({"name": "odd", "config": config,
                               "traffic": "odd", "chips": 1, "why": "odd"})
    with pytest.raises(cells.CellError,
                       match="traffic 'odd' with config.*" + message):
        cells.load_cell("odd", bench=bench, traffic_dir=str(tmp_path))


def _reference(tmp_path, text):
    _write(tmp_path / "ref.py", text)
    return os.path.relpath(tmp_path / "ref.py", cells.ROOT)


LIMITS = ("LOSS_RTOL = 1e-5\nGRAD_RTOL = 1e-4\nUPDATE_RTOL = 1e-6\n"
          "CONSERVED_RTOL = 1e-3\n")
GOOD_REFERENCE = (LIMITS + "def loss_and_grads(params, inputs, labels):\n"
                  "    return 0.0, params\n")


@pytest.mark.parametrize("text, message", [
    (LIMITS, "defines no loss_and_grads"),
    (GOOD_REFERENCE.replace("LOSS_RTOL = 1e-5\n", ""),
     "LOSS_RTOL must be a positive float"),
    (GOOD_REFERENCE.replace("1e-4", "0.0"),
     "GRAD_RTOL must be a positive float"),
    (GOOD_REFERENCE.replace("UPDATE_RTOL = 1e-6\n", ""),
     "UPDATE_RTOL must be a positive float"),
    (GOOD_REFERENCE.replace("1e-3", "1"),
     "CONSERVED_RTOL must be a positive float"),
    # what stayed is a number of the step's precision since PR 40, and a
    # module that lacks its limit is named with the key
    (GOOD_REFERENCE.replace("CONSERVED_RTOL = 1e-3\n", ""),
     r"reference '.*ref\.py': CONSERVED_RTOL must be a positive float, "
     "got None"),
])
def test_reference_module_without_its_parts(tmp_path, text, message):
    with pytest.raises(cells.CellError, match=message):
        cells.load_reference(_reference(tmp_path, text))


def test_reference_module_loads_by_path(tmp_path):
    mod = cells.load_reference(_reference(tmp_path, GOOD_REFERENCE))
    assert mod.loss_and_grads({"w": 1}, None, None) == (0.0, {"w": 1})
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_reference("benchmark/references/absent.py")


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


@pytest.mark.parametrize("base, traffic_keys", [
    ("tiny", {}),
    ("tiny_lm", {"zipf_s": 1.2, "doc_len_median": 9, "doc_len_sigma": 1.0}),
])
def test_new_cell_config_traffic_and_metric_need_only_new_files(
        tmp_path, base, traffic_keys):
    """What a later PR does: new files and new entries, no edit. An image
    configuration, and a token one with a reference of its own."""
    bench = copy.deepcopy(_fixture_bench())
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(FIXTURE, "traffic"), traffic_dir)
    _write(traffic_dir / "burst.json", {**GOOD_TRAFFIC, "round_steps": 7,
                                        "modules": ["configs/bf16.py"],
                                        **traffic_keys})
    change = {"deployment": "a second deployment"}
    if base == "tiny_lm":
        change["reference"] = _reference(tmp_path, GOOD_REFERENCE)
    entry = _config_entry(tmp_path, base, **change)
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
    assert cell.config["dataset"]["kind"] == (
        "tokens" if traffic_keys else "images")
    for key in cells.TOKEN_KEYS:
        assert cell.traffic[key] == traffic_keys.get(key)
    if base == "tiny_lm":
        assert cell.config["reference"].endswith("ref.py")
        assert callable(cells.load_reference(
            cell.config["reference"]).loss_and_grads)
    assert "new.metric" in {m["name"] for m in cell.per_layer}
    assert cells.load_reader("new.metric", str(tmp_path))(None, {}, cell) \
        == 1.5
    # and the cells that were there are untouched
    old = cells.load_cell("tiny.steady", bench=bench,
                          traffic_dir=str(traffic_dir))
    assert "new.metric" not in {m["name"] for m in old.per_layer}
