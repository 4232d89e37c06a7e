"""The harness's own ``measure`` on tiny cells, on the CPU: an image and a
token configuration, every input mode and loop the traffic files can ask
for, one chip and a mesh of four. Counts and checks only; no time from
here is a device number."""

import copy
import json
import os

import pytest

from benchmark import cells, rehearse, run

TRAFFIC = os.path.join(rehearse.FIXTURE, "traffic")


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.resident", "tiny.scan",
                                  "tiny.steady.x4", "tiny_lm.resident",
                                  "tiny_lm.scan", "tiny_lm.resident.x4"])
def test_measure_runs_the_cell(cell):
    out = rehearse._run_tiny(cell, trace=False)
    assert out["rounds"] >= 2 and out["steps"] > 0
    assert out["check"]["ok"] and out["check"]["world"] == (
        4 if cell.endswith(".x4") else 1)
    model = out["model_check"]
    if cell.startswith("tiny_lm"):
        # the timed step of both arms is held to the model's reference
        assert model["ok"] and set(model["arms"]) == {"dgc", "dense"}
        scan = cell == "tiny_lm.scan"
        for arm in model["arms"].values():
            assert arm["steps"] == (9 if scan else 3)
            assert arm["loss_rel_err"]["max"] <= model["limits"][
                "loss_rel_err"]
        dgc, dense = model["arms"]["dgc"], model["arms"]["dense"]
        assert dense["update_norm_gap"]["max"] <= model["limits"][
            "update_norm_gap"]
        # k steps in a dispatch leave no state after one step to read a
        # gradient from
        assert ("grad_rel_err" in dense) == ("conserved_rel_err" in dgc) \
            == (not scan)
        if not scan:
            assert set(dense["grad_rel_err"]["by_tensor"]) == {
                "embed/embedding", "gate/kernel", "up/kernel",
                "down/kernel", "head/kernel"}
    else:
        assert model["ok"] and "batch statistics" in model["skipped"]


def test_traced_measure_puts_the_harness_spans_on_the_trace():
    out = rehearse._run_tiny("tiny.resident", trace=True)
    assert out["annotations"] >= 6 and "no_device_ops" in out
    assert out["per_layer_read"] == ["input.wait_ms"]


def test_the_memory_law_refuses_with_its_numbers():
    """``rehearse.py aot``'s rule on a compiler's numbers (bytes a chip;
    these are a 461M-parameter token model's at 16 rows of 256, PR 27):
    both states and the larger step's temporaries, the check on its own,
    and a refusal that states every one of them."""
    row = {"cell": "wide", "chips": 1, "check_bytes": 8884226048,
           "dgc": {"argument_bytes": 7439714304, "temp_bytes": 4390782976},
           "dense": {"argument_bytes": 3691021312, "temp_bytes": 6359577600}}
    needs, host, said = rehearse.memory_law(row)
    assert needs == 7439714304 + 3691021312 + 6359577600 > rehearse.HBM_BYTES
    # no reference of the model, nothing copied: the process's baseline
    assert host == int(rehearse.HOST_BASELINE_BYTES)
    for number in (needs, 7439714304, 3691021312, 6359577600, 8884226048):
        assert str(number) in said
    # with a reference the followers' four copies of both states bind the
    # host long before the chip: 58.7 GB against 40 GiB
    needs, host, said = rehearse.memory_law({**row, "follower_copies": 4})
    assert host == int(rehearse.HOST_BASELINE_BYTES) + 4 * (
        7439714304 + 3691021312) > rehearse.HOST_BYTES[1]
    assert str(host) in said and str(rehearse.HOST_BYTES[1]) in said
    del row["dense"]           # a cell of the dgc arm alone
    assert rehearse.memory_law(row)[0] == 7439714304 + 4390782976


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
                    reference_text=SCALED_REFERENCE):
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
                   modules=list(traffic_modules))
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
        "step0_loss_gap", "nonfinite_losses",
        "exchange.inexact_residual_coords", "exchange.unconserved_coords",
        "exchange.over_quota_rows", "exchange.sent_outside_rows",
        "exchange.fill_floor", "exchange.recall_floor",
        "exchange.bucket_recall_floor", "dgc.loss_rel_err",
        "dgc.conserved_rel_err", "dense.loss_rel_err", "dense.grad_rel_err",
        "dense.update_norm_gap"}
    assert all(number >= limit if name.endswith("_floor")
               else number <= limit for name, (number, limit) in got.items())
    # a reference scaled by exactly 1 is the sound one: the wrapper itself
    # changes nothing
    m = _measure(_new_token_cell(tmp_path, reference_scale=1.0))
    assert run.is_correct(m)


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
        tmp_path, overrides, dgc_module):
    """The plain SGD beside the reference reads the recipe the arm was
    built with: weight decay, nesterov; DGC's memory too."""
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


def test_a_reference_with_one_gradient_scaled_is_not_correct(tmp_path):
    m = _measure(_new_token_cell(tmp_path, reference_scale=1.01))
    model = m["model_check"]
    assert _others_sound(m) and not model["ok"] and not run.is_correct(m)
    for arm, key in (("dense", "grad_rel_err"), ("dgc", "conserved_rel_err")):
        worst = model["arms"][arm][key]
        assert worst["worst_tensor"] == "up/kernel"
        # 1% of that tensor's norm, over the median tensor's where that
        # is the larger
        assert 30 * GRAD_RTOL < worst["max"] <= 1.001 * 0.01 / 1.01
    assert model["arms"]["dense"]["loss_rel_err"]["max"] <= LOSS_RTOL


def test_a_bfloat16_model_against_a_float32_file_is_not_correct(tmp_path):
    """The control: the configuration file says float32, and the traffic
    composes ``configs/bf16.py`` after it, the step a later PR might take."""
    m = _measure(_new_token_cell(tmp_path,
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
        return arm._replace(step=broken_step(arm.step))

    monkeypatch.setattr(build, "build_arm", build_arm)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    def unchanged(step):
        def broken(state, x, y, key):
            kept = jax.tree.map(jnp.copy, state)    # the state is donated
            return kept, step(state, x, y, key)[1]
        return broken

    _break_the_step(monkeypatch, unchanged)
    m = _measure(_new_token_cell(tmp_path))
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


def test_a_step_that_trains_on_half_the_batch_is_not_correct(
        tmp_path, monkeypatch):
    import jax.numpy as jnp

    def half(step):
        def broken(state, x, y, key):
            rows, labels = x.shape[0] // 2, y.shape[0] // 2
            return step(state, jnp.concatenate([x[:rows], x[:rows]]),
                        jnp.concatenate([y[:labels], y[:labels]]), key)
        return broken

    _break_the_step(monkeypatch, half)
    m = _measure(_new_token_cell(tmp_path))
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
    from benchmark import model_check
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
    from benchmark import model_check
    ref = {"a": [3.0, 4.0], "b": [1.0, 0.0], "c": [0.0, 2.0]}
    prog = {"a": [3.0, 4.0], "b": [float("nan"), 0.0], "c": [0.0, 2.0]}
    worst = model_check._worst(prog, ref)
    assert worst["worst_tensor"] == "b" and worst["max"] != worst["max"]
    assert model_check._worst(ref, ref)["max"] == 0.0
    # an all-but-zero tensor is measured against the median tensor's norm
    tiny = model_check._leafwise({**ref, "b": [1e-9, 0.0]},
                                 {**ref, "b": [0.0, 0.0]})
    assert tiny["rel_err"]["b"] == pytest.approx(1e-9 / 2.0)
