"""The exchange check: its numbers on the fixture cells are the ones it
printed as ONE program (PR 26) for the same seeds, now that it is several
small ones that run after the window; an engine broken underneath a
whole run comes out not correct; and so does each fault under the update
the window times, seen first by the number that is held to see it
(PR 44: ``faults.py``)."""

import json
import os

import pytest

import faults
from benchmark import build, cells, check, rehearse, run
from benchmark.check import exchange_check

#: coordinates sent per bucket by all workers, seeds 0, 1, 2: read from the
#: parent's check (commit 9957afb, XLA:CPU, one and four virtual devices)
#: before it was taken apart. Every other count it printed was 0, its
#: recall 1 (off the chip the engine selects exactly).
PARENT_SENT = {
    "tiny.steady": [[2005, 691], [2017, 688], [2030, 695]],
    "tiny.steady.x4": [[8041, 2743], [8029, 2757], [8089, 2738]],
    "tiny_lm.resident": [[471], [506], [487]],
    "tiny_lm.resident.x4": [[1989], [2016], [1967]],
}


@pytest.mark.parametrize("name", sorted(PARENT_SENT))
def test_the_check_prints_the_parents_numbers(name):
    import jax
    cell = rehearse.fixture_cell(name)
    with build.matmul_precision(cell):
        mesh = build.make_mesh(cell, jax.devices("cpu"))
        arm = build.build_arm(cell, "dgc", mesh)
        for seed, sent in enumerate(PARENT_SENT[name]):
            got = exchange_check(arm, seed)
            assert got["ok"] and got["world"] == cell.chips
            assert got["sent_per_bucket"] == sent
            assert got["fill"] == sum(sent) / (got["quota"] * cell.chips)
            assert got["recall"] == 1.0
            assert [got[key] for key in (
                "inexact_residual_coords", "unconserved_coords",
                "over_quota_rows", "sent_outside_rows")] == [0, 0, 0, 0]


# ---------------------------------------------------------------------- #
# the engine broken underneath a whole run                               #
# ---------------------------------------------------------------------- #

def _measure(cell):
    import jax
    return run.measure(cell, seed=2147483659, seconds=0.2, trace=False,
                       devices=jax.devices("cpu"))


def _sound_but_for_the_check(m):
    return (m["model_check"]["ok"] and m["step0_ok"] and m["failed"] == 0
            and not m["check"]["ok"] and not run.is_correct(m))


@pytest.mark.parametrize("residency", cells.RESIDENCIES)
def test_error_feedback_kept_in_bfloat16_is_not_correct(tmp_path, residency):
    """The control: the nearest precision below the float32 the engine's
    state is stated in, through the program's own path to it
    (``configs/dgc/bf16mem.py``) composed by the traffic file, the step a
    later PR might take. Every residual coordinate then differs bitwise
    from the reference's float32 value. With the arms on the chip
    together or one after the other."""
    with open(os.path.join(rehearse.FIXTURE, "traffic",
                           "tiny.resident.json")) as fh:
        traffic = json.load(fh)
    traffic.update(dgc_modules=["configs/dgc/bf16mem.py"],
                   residency=residency)
    (tmp_path / "bf16mem.json").write_text(json.dumps(traffic))
    bench = cells.load_benchmark(
        os.path.join(rehearse.FIXTURE, "BENCHMARK.json"))
    bench["workloads"].append({"name": "tiny.bf16mem", "config": "tiny",
                               "traffic": "bf16mem", "chips": 1, "why": "-"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.bf16mem")
    m = _measure(cells.load_cell("tiny.bf16mem", bench=bench,
                                 traffic_dir=str(tmp_path)))
    assert _sound_but_for_the_check(m)
    # of the 272,474 parameters all but the few that round to themselves
    assert m["check"]["inexact_residual_coords"] > 200_000


class _DropsAResidual:
    """The engine, but for one coordinate of what an exchange leaves
    behind: the largest residual is lost."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def exchange(self, *args, **kwargs):
        import jax.numpy as jnp
        out, mem = self._engine.exchange(*args, **kwargs)
        T = self._engine.T
        kept = self._engine.memory_full(mem)["velocities"][:T]
        lost = jnp.argmax(jnp.abs(kept))
        return out, {**mem, "velocities_c":
                     mem["velocities_c"].at[lost].set(0.0)}


def test_an_engine_that_drops_a_residual_is_not_correct(monkeypatch):
    real = build.build_arm

    def build_arm(cell, arm_name, mesh):
        arm = real(cell, arm_name, mesh)
        if arm_name != "dgc":
            return arm
        return arm._replace(setup=arm.setup._replace(
            engine=_DropsAResidual(arm.setup.engine)))

    monkeypatch.setattr(build, "build_arm", build_arm)
    m = _measure(rehearse.fixture_cell("tiny.resident"))
    assert _sound_but_for_the_check(m)
    # the coordinate reads as sent (its residual is a zero) and reached no
    # parameter: one in each of the two exchanges' worth of state
    assert m["check"]["unconserved_coords"] == 1
    assert m["check"]["inexact_residual_coords"] == 0


# ---------------------------------------------------------------------- #
# the form the window times (PR 44)                                      #
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("name", sorted(PARENT_SENT))
def test_the_offered_step_agrees_with_the_plain_rule(name):
    """Sound: the pack is the draw, p' and the buffer are the plain
    rule's within the allowance, the offered step leaves the bare
    exchange's memory and record; off the chip no program lowers a
    Mosaic kernel, so none is uncovered."""
    import jax
    cell = rehearse.fixture_cell(name)
    with build.matmul_precision(cell):
        mesh = build.make_mesh(cell, jax.devices("cpu"))
        arm = build.build_arm(cell, "dgc", mesh)
        got = exchange_check(arm, 2147483659)
    assert got["ok"] and got["checked_count"] >= 1
    assert [got[key] for key in check.NEW_COUNTS] == [0] * 5
    assert got["uncovered_kernels"] == [] == got["kernels"]["checked"]
    assert 0 <= got["update_most_ulps"] <= check.UPDATE_ULPS
    assert 0 <= got["buffer_most_ulps"] <= check.UPDATE_ULPS
    assert set(got["parts_s"]) == {"bare", "offered", "forms"}
    # XLA:CPU sums the workers in the reference's order: nothing rests on
    # the slack a float32 sum's order is given on several workers
    assert got["excused_by_sum_order"] == [0, 0]
    # a buffer is drawn and held to the rule where the optimizer keeps one
    assert (got["buffer_most_ulps"] > 0) == name.startswith("tiny.")


class _Lowered:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_the_kernels_of_a_lowered_program_by_name():
    text = """
    %0 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config = "",
      kernel_name = "place_rows"} : (tensor<8xf32>) -> tensor<8xf32>
    %1 = stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = "place_rows"}
    %2 = stablehlo.custom_call @tpu_custom_call(%1) {kernel_name = "topk_rows.3"}
    %3 = stablehlo.custom_call @Sharding(%2) {kernel_name_of = "x"}
    """
    assert check.mosaic_kernels(_Lowered(text)) == {"place_rows",
                                                    "topk_rows"}
    assert check.mosaic_kernels(_Lowered("module @jit_f {}")) == frozenset()


def _planted(monkeypatch, fault, cell="tiny.resident"):
    """A whole run of ``cell`` with ``fault`` under its dgc arm."""
    real = build.build_arm

    def build_arm(cell, arm_name, mesh):
        arm = real(cell, arm_name, mesh)
        return fault(arm, monkeypatch.setattr) if arm_name == "dgc" else arm

    monkeypatch.setattr(build, "build_arm", build_arm)
    return _measure(rehearse.fixture_cell(cell))


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if f is not faults.makes_no_offer],
                         ids=lambda f: f.__name__)
def test_a_fault_under_the_offered_step_is_not_correct(monkeypatch, fault):
    """Each fault, on the form XLA:CPU compiles (the scatter, the
    optimizer's own ``update``, the concatenation), under a whole run:
    not correct, and the number held to see it is the first ``compared``
    lists. The bare exchange's seven numbers stay whole."""
    m = _planted(monkeypatch, fault)
    assert _sound_but_for_the_check(m)
    got = run.compared(m)
    assert next(iter(got)) == faults.FAULTS[fault], got
    assert got[faults.FAULTS[fault]][0] > 0
    if fault is faults.drops_a_pair:
        # one pair is one coordinate
        assert m["check"]["update_unexplained_coords"] == 1
    assert [m["check"][key] for key in (
        "inexact_residual_coords", "unconserved_coords", "over_quota_rows",
        "sent_outside_rows")] == [0, 0, 0, 0]
    outside = {name for name, (number, limit) in got.items()
               if run.nearness(name, number, limit) > 1}
    # what else sees it: a misplaced tile goes through the whole step,
    # a flipped bit unmasks (or masks) a velocity, nesterov's step reads
    # the new buffer; the others are seen by their own number alone
    also = {faults.misplaces_a_tile: {
                "exchange.record_wrong_bits",
                "exchange.update_unexplained_coords",
                "exchange.forms_differ_coords"},
            faults.flips_a_record_bit: {"exchange.forms_differ_coords"},
            faults.keeps_the_old_buffer: {
                "exchange.update_unexplained_coords"}}.get(fault, set())
    assert {faults.FAULTS[fault]} <= outside <= {faults.FAULTS[fault]} | also


def test_a_dropped_pair_is_seen_across_four_workers(monkeypatch):
    """On several workers the rule's gradient has the slack of a float32
    sum's order (``sum_tol``); a pair lost from it is far outside."""
    m = _planted(monkeypatch, faults.drops_a_pair, "tiny.steady.x4")
    assert _sound_but_for_the_check(m) and m["check"]["world"] == 4
    assert m["check"]["update_unexplained_coords"] == 1
    assert next(iter(run.compared(m))) == "exchange.update_unexplained_coords"


def test_a_check_that_lowers_another_form_than_the_window_is_not_correct(
        monkeypatch):
    """Off the chip nothing lowers a Mosaic kernel, so the timed step is
    made to read as one that lowers the offered pass and a view of the
    model's parameters: the check, which lowers neither, does not cover
    the pass (the view is the model's, and is named apart)."""
    real = check.mosaic_kernels

    def mosaic_kernels(lowered):
        names = real(lowered)
        if "jit_step_fn" in lowered.as_text()[:400]:
            names |= {"payload_update_bits", "opaque_view_from"}
        return names

    monkeypatch.setattr(check, "mosaic_kernels", mosaic_kernels)
    m = _measure(rehearse.fixture_cell("tiny.resident"))
    assert _sound_but_for_the_check(m)
    assert m["check"]["uncovered_kernels"] == ["payload_update_bits"]
    assert m["check"]["kernels"]["not_held_to"] == ["opaque_view_from"]
    got = run.compared(m)
    assert next(iter(got)) == "check.uncovered_kernels"
    assert got["check.uncovered_kernels"] == [1, 0]
