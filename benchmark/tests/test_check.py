"""The exchange check: its numbers on the fixture cells are the ones it
printed as ONE program (PR 26) for the same seeds, now that it is several
small ones that run after the window; and an engine broken underneath a
whole run comes out not correct."""

import json
import os

import pytest

from benchmark import build, cells, rehearse, run
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
