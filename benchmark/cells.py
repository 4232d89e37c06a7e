"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` names everything; each configuration, traffic mix and
per-layer metric sits in a file of its own, so a later PR adds a cell by
adding files and entries and edits nothing that is here:

* ``benchmark/configs/<config>.json`` (the path is the entry's ``file``):
  the modules that build it, its sizes, and a ``dataset`` block whose
  ``kind`` (``images`` or ``tokens``) says what the generator makes for it
* ``benchmark/references/<config>.py`` (the path is the configuration
  file's ``reference``, or null with ``reference_why``): the model's plain
  reference, ``loss_and_grads(params, inputs, labels)``, and the limits
  the timed step is held to against it; the file then also states the
  ``matmul_precision`` the harness runs the program at
* ``benchmark/traffic/<traffic>.json``
* ``benchmark/layer_metrics/<metric>.py`` with ``read(trace, spans, cell)``

A per-layer metric whose entry has no ``workloads`` list is owed by EVERY
cell, those of later PRs too: a traced run whose reader returns None for it
prints no line (``run.refuse_a_short_line``). A reader returns None only
for a cell that its entry's list leaves out.

A missing or malformed file is an error that names the file and the key.
"""

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")

ARMS = ("dgc", "dense")
INPUTS = ("pipeline", "resident")
LOOPS = ("dispatch", "scan")
#: whether a cell's arms are on the chip together, interleaved round by
#: round, or one after the other, each gone before the next is built; a
#: traffic file states ``one`` only where ``rehearse.memory_law`` shows
#: that ``both`` does not fit (``rehearse.py aot`` refuses it otherwise)
RESIDENCIES = ("both", "one")
#: what a configuration's data is, and the sizes its file states for it
#: (held to the built model's ``configs.dataset`` in ``build.py``)
DATA_KINDS = {"images": ("image_size", "num_classes"),
              "tokens": ("seq_len", "vocab_size")}
#: what a reference module states beside ``loss_and_grads``: the limits
#: of ``benchmark/model_check.py``'s numbers that differ by model
REFERENCE_LIMITS = ("LOSS_RTOL", "GRAD_RTOL", "UPDATE_RTOL",
                    "CONSERVED_RTOL")
#: what a configuration file may state as ``matmul_precision``: the names
#: of ``jax.default_matmul_precision`` for float32 operands on the TPU
#: (one, three and six bfloat16 passes)
MATMUL_PRECISIONS = ("default", "high", "highest")
#: traffic keys that say how token sequences are made
TOKEN_KEYS = ("zipf_s", "doc_len_median", "doc_len_sigma")


class CellError(ValueError):
    """A cell, configuration, traffic mix or reader that cannot be used."""


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # this cell's entries of BENCHMARK.json
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise CellError(f"{os.path.relpath(path, ROOT)}: no such file")
    try:
        with open(path) as fh:
            obj = json.load(fh)
    except json.JSONDecodeError as e:
        raise CellError(f"{os.path.relpath(path, ROOT)}: not JSON ({e})")
    if not isinstance(obj, dict):
        raise CellError(f"{os.path.relpath(path, ROOT)}: not a JSON object")
    return obj


def _want(obj, key, kind, where, default=None, required=True):
    if key not in obj:
        if required:
            raise CellError(f"{where}: key '{key}' is missing")
        return default
    v = obj[key]
    ok = (isinstance(v, kind) and not isinstance(v, bool)
          if kind in (int, float, (int, float)) else isinstance(v, kind))
    if not ok:
        raise CellError(f"{where}: key '{key}' must be "
                        f"{getattr(kind, '__name__', 'a number')}, "
                        f"got {v!r}")
    return v


def _number_or_null(obj, key, where):
    """A number, or None where the key is absent or null."""
    if obj.get(key) is None:
        return None
    return _want(obj, key, (int, float), where)


def _str_list(obj, key, where, required=False):
    v = _want(obj, key, list, where, default=[], required=required)
    if not all(isinstance(s, str) for s in v):
        raise CellError(f"{where}: key '{key}' must be a list of strings")
    return list(v)


def load_benchmark(path: str = BENCHMARK_JSON) -> Dict[str, Any]:
    bench = _load_json(path)
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in bench:
            raise CellError(f"BENCHMARK.json: key '{key}' is missing")
    pairs = set()
    for w in bench["workloads"]:
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            raise CellError(f"BENCHMARK.json: config '{pair[0]}' with "
                            f"traffic '{pair[1]}' is given twice; a cell on "
                            "other chips needs a traffic file of its own")
        pairs.add(pair)
    return bench


_TRAFFIC_KEYS = {"per_chip_batch", "arms", "input", "round_steps",
                 "trace_steps", "loop", "k", "modules", "dgc_modules",
                 "compress_ratio", "pool_batches", "residency", "why",
                 *TOKEN_KEYS}


def load_traffic(name: str, traffic_dir: Optional[str] = None
                 ) -> Dict[str, Any]:
    """One traffic mix: the parameters the one general generator reads."""
    path = os.path.join(traffic_dir or os.path.join(BENCH_DIR, "traffic"),
                        name + ".json")
    raw = _load_json(path)
    where = f"traffic '{name}'"
    unknown = sorted(set(raw) - _TRAFFIC_KEYS)
    if unknown:
        raise CellError(f"{where}: unknown key(s) {unknown}; the generator "
                        f"reads {sorted(_TRAFFIC_KEYS)}")
    t = {
        "per_chip_batch": _want(raw, "per_chip_batch", int, where),
        "arms": _str_list(raw, "arms", where, required=True),
        "input": _want(raw, "input", str, where),
        "round_steps": _want(raw, "round_steps", int, where),
        "trace_steps": _want(raw, "trace_steps", int, where),
        "loop": _want(raw, "loop", str, where),
        "k": _want(raw, "k", int, where, default=None, required=False),
        "modules": _str_list(raw, "modules", where),
        "dgc_modules": _str_list(raw, "dgc_modules", where),
        "compress_ratio": raw.get("compress_ratio"),
        "pool_batches": _want(raw, "pool_batches", int, where, default=16,
                              required=False),
        "residency": _want(raw, "residency", str, where, default="both",
                           required=False),
        **{key: _number_or_null(raw, key, where) for key in TOKEN_KEYS},
    }
    for key in ("per_chip_batch", "round_steps", "trace_steps",
                "pool_batches"):
        if t[key] < 1:
            raise CellError(f"{where}: '{key}' must be at least 1")
    if (not t["arms"] or len(set(t["arms"])) != len(t["arms"])
            or any(a not in ARMS for a in t["arms"])):
        raise CellError(f"{where}: 'arms' must be distinct names out of "
                        f"{list(ARMS)}, got {t['arms']}")
    if t["input"] not in INPUTS:
        raise CellError(f"{where}: 'input' must be one of {list(INPUTS)}")
    if t["loop"] not in LOOPS:
        raise CellError(f"{where}: 'loop' must be one of {list(LOOPS)}")
    if t["residency"] not in RESIDENCIES:
        raise CellError(f"{where}: 'residency' must be one of "
                        f"{list(RESIDENCIES)}, got {t['residency']!r}")
    if t["loop"] == "scan":
        if t["k"] is None or t["k"] < 1:
            raise CellError(f"{where}: loop 'scan' needs 'k' >= 1")
        if t["input"] != "resident":
            raise CellError(f"{where}: loop 'scan' runs k steps in one "
                            "dispatch and so needs input 'resident'")
    elif t["k"] is not None:
        raise CellError(f"{where}: 'k' belongs to loop 'scan' only")
    r = t["compress_ratio"]
    if r is not None and not (isinstance(r, (int, float))
                              and not isinstance(r, bool) and 0 < r <= 1):
        raise CellError(f"{where}: 'compress_ratio' must be null or in "
                        f"(0, 1], got {r!r}")
    if t["zipf_s"] is not None and t["zipf_s"] < 0:
        raise CellError(f"{where}: 'zipf_s' must be at least 0")
    if t["doc_len_median"] is not None and t["doc_len_median"] < 1:
        raise CellError(f"{where}: 'doc_len_median' must be null (one "
                        "document per row) or at least 1")
    if (t["doc_len_sigma"] is None) != (t["doc_len_median"] is None):
        raise CellError(f"{where}: 'doc_len_sigma' goes with "
                        "'doc_len_median': give both or neither")
    if t["doc_len_sigma"] is not None and t["doc_len_sigma"] < 0:
        raise CellError(f"{where}: 'doc_len_sigma' must be at least 0")
    return t


def load_config(entry: Dict[str, Any]) -> Dict[str, Any]:
    """One configuration file: which of the repo's config modules build
    it, what was overridden, reduced or assumed, what its data is, the
    sizes the built model is held to, and where its plain reference is."""
    name = entry.get("name", "?")
    where = f"config '{name}'"
    rel = _want(entry, "file", str, f"BENCHMARK.json {where}")
    raw = _load_json(os.path.join(ROOT, rel))
    where = f"{where} ({rel})"
    cfg = {
        "source": _want(raw, "source", str, where),
        "modules": _str_list(raw, "modules", where, required=True),
        "dgc_modules": _str_list(raw, "dgc_modules", where, required=True),
        "overrides": _want(raw, "overrides", dict, where),
        "reduced": _want(raw, "reduced", list, where),
        "assumed": _want(raw, "assumed", dict, where),
        "deployment": _want(raw, "deployment", str, where),
        "sizes": _want(raw, "sizes", dict, where),
        "dataset": dict(_want(raw, "dataset", dict, where)),
    }
    dataset, sizes = cfg["dataset"], cfg["sizes"]
    kind = _want(dataset, "kind", str, f"{where} dataset")
    if kind not in DATA_KINDS:
        raise CellError(f"{where} dataset: 'kind' must be one of "
                        f"{sorted(DATA_KINDS)}, got {kind!r}")
    for key in ("num_parameters",) + DATA_KINDS[kind]:
        _want(sizes, key, int, f"{where} sizes")
    if _want(dataset, "epoch_examples", int, f"{where} dataset") < 1:
        raise CellError(f"{where} dataset: 'epoch_examples' must be at "
                        "least 1")
    if kind == "tokens":
        eos = _want(dataset, "eos_id", int, f"{where} dataset")
        if not 0 <= eos < sizes["vocab_size"]:
            raise CellError(f"{where} dataset: 'eos_id' {eos} is not an id "
                            f"of a vocabulary of {sizes['vocab_size']}")
    for mod in cfg["modules"] + cfg["dgc_modules"]:
        if not os.path.isfile(os.path.join(ROOT, mod)):
            raise CellError(f"{where}: config module '{mod}' is not in "
                            "the repo")
    if "reference" not in raw:
        raise CellError(f"{where}: key 'reference' is missing (the path of "
                        "the model's plain reference, or null with "
                        "'reference_why')")
    ref = cfg["reference"] = raw["reference"]
    if ref is None:
        cfg["reference_why"] = _want(raw, "reference_why", str, where)
    else:
        if not os.path.isfile(os.path.join(
                ROOT, _want(raw, "reference", str, where))):
            raise CellError(f"{where}: reference '{ref}' is not in the "
                            "repo")
    # the backend's default where the file says nothing. A reference
    # needs it said: at the TPU's default a float32 step is as far from
    # its reference as a bfloat16 one (PERF.md section 6, PR 26)
    precision = cfg["matmul_precision"] = _want(
        raw, "matmul_precision", str, where, required=ref is not None)
    if precision is not None and precision not in MATMUL_PRECISIONS:
        raise CellError(f"{where}: 'matmul_precision' must be one of "
                        f"{list(MATMUL_PRECISIONS)}, got {precision!r}")
    if sorted(cfg["reduced"]) != sorted(entry.get("reduced", [])):
        raise CellError(f"{where}: 'reduced' {cfg['reduced']} differs from "
                        f"BENCHMARK.json's {entry.get('reduced')}")
    return cfg


def _check_traffic_fits(traffic_name, traffic, config_name, kind):
    """The traffic mix against the configuration's data kind."""
    where = f"traffic '{traffic_name}' with config '{config_name}'"
    if kind != "tokens":
        given = [k for k in TOKEN_KEYS if traffic[k] is not None]
        if given:
            raise CellError(f"{where}: key(s) {given} say how token "
                            f"sequences are made, and the configuration's "
                            f"dataset kind is '{kind}'")
        return
    if traffic["zipf_s"] is None:
        raise CellError(f"{where}: key 'zipf_s' is missing (a 'tokens' "
                        "configuration needs the skew of its tokens)")
    if traffic["input"] == "pipeline":
        raise CellError(f"{where}: input 'pipeline' goes through the "
                        "program's ArraySplit, which normalises images; the "
                        "program has no token split yet, so a 'tokens' "
                        "configuration needs input 'resident'")


def _load_module(package: str, path: str):
    """The module in the file ``path``, as ``benchmark.<package>.<stem>``
    (a stem may hold dots: a metric's name)."""
    stem = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{package}.{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(path: str):
    """The plain reference of a configuration's model: the module at
    ``path`` (relative to the repo's root) with ``loss_and_grads(params,
    inputs, labels) -> (loss, grads)`` and the limits of
    ``benchmark/model_check.py``'s numbers; ``ROW_BLOCK`` where its
    loss is a mean over rows and it is to be called on that many at a
    time."""
    full = os.path.join(ROOT, path)
    if not os.path.isfile(full):
        raise CellError(f"reference '{path}': no such file")
    mod = _load_module("references", full)
    if not callable(getattr(mod, "loss_and_grads", None)):
        raise CellError(f"reference '{path}' defines no loss_and_grads()")
    for key in REFERENCE_LIMITS:
        v = getattr(mod, key, None)
        if not isinstance(v, float) or not v > 0:
            raise CellError(f"reference '{path}': {key} must be a "
                            f"positive float, got {v!r}")
    block = getattr(mod, "ROW_BLOCK", None)
    if block is not None and not (type(block) is int and block >= 1):
        raise CellError(f"reference '{path}': ROW_BLOCK must be a number "
                        f"of rows, at least 1, got {block!r}")
    return mod


def _metrics_of(cell_name: str, entries: List[Dict[str, Any]]):
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              traffic_dir: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``bench`` (default: the repo's BENCHMARK.json;
    the tests and rehearsals pass a fixture and its traffic directory)."""
    bench = load_benchmark() if bench is None else bench
    hits = [w for w in bench["workloads"] if w.get("name") == name]
    if len(hits) != 1:
        known = [w.get("name") for w in bench["workloads"]]
        raise CellError(f"workload '{name}' is not in BENCHMARK.json "
                        f"(known: {known})")
    w = hits[0]
    where = f"workload '{name}'"
    chips = _want(w, "chips", int, where)
    if chips not in (1, 4):
        raise CellError(f"{where}: 'chips' must be 1 or 4")
    cfg_name = _want(w, "config", str, where)
    cfgs = [c for c in bench["configs"] if c.get("name") == cfg_name]
    if len(cfgs) != 1:
        raise CellError(f"{where}: config '{cfg_name}' is not in "
                        "BENCHMARK.json")
    traffic_name = _want(w, "traffic", str, where)
    e2e = _metrics_of(name, bench["end_to_end"])
    layer = _metrics_of(name, bench["per_layer"])
    e2e_names = {m["name"] for m in e2e}
    for m in layer:
        if m.get("moves") not in e2e_names:
            raise CellError(
                f"{where}: per-layer metric '{m.get('name')}' moves "
                f"'{m.get('moves')}', which this cell does not report")
    config = load_config(cfgs[0])
    traffic = load_traffic(traffic_name, traffic_dir)
    _check_traffic_fits(traffic_name, traffic, cfg_name,
                        config["dataset"]["kind"])
    return Cell(name=name, chips=chips, config_name=cfg_name, config=config,
                traffic_name=traffic_name, traffic=traffic,
                end_to_end=e2e, per_layer=layer)


def load_reader(metric: str, readers_dir: Optional[str] = None) -> Callable:
    """The reader of one per-layer metric: ``read(trace, spans, cell)``
    in ``benchmark/layer_metrics/<metric>.py``. It returns a number, or
    None when it finds nothing to read: never 0 for a share. A cell owes
    every metric of its ``per_layer`` (``_metrics_of``: every entry
    without a ``workloads`` list, and those whose list names it), so a
    None there costs the traced run its line."""
    path = os.path.join(readers_dir
                        or os.path.join(BENCH_DIR, "layer_metrics"),
                        metric + ".py")
    if not os.path.isfile(path):
        raise CellError(f"per-layer metric '{metric}': no reader at "
                        f"{os.path.relpath(path, ROOT)}")
    read = getattr(_load_module("layer_metrics", path), "read", None)
    if not callable(read):
        raise CellError(f"per-layer metric '{metric}': "
                        f"{os.path.relpath(path, ROOT)} defines no read()")
    return read


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of the device; an unknown kind is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table.get("devices", {}):
        raise CellError(f"peaks.json has no entry for device kind "
                        f"{device_kind!r} (known: "
                        f"{sorted(table.get('devices', {}))})")
    return table["devices"][device_kind]
