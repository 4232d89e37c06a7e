"""The round arithmetic on synthetic timings."""

import numpy as np
import pytest

from benchmark import rounds


def paired_median(rows, steps):
    return rounds.quartiles(
        rounds.paired_diff_ms(rows, "dgc", "dense", steps))[1]


def test_order_alternates():
    arms = ["dgc", "dense"]
    assert [rounds.arm_order(arms, r) for r in range(4)] == [
        ["dgc", "dense"], ["dense", "dgc"], ["dgc", "dense"],
        ["dense", "dgc"]]
    assert rounds.arm_order(["a", "b", "c"], 1) == ["b", "c", "a"]
    assert rounds.arm_order(["only"], 5) == ["only"]


def test_quartiles_match_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 12, 31):
        xs = rng.normal(size=n).tolist()
        assert rounds.quartiles(xs) == pytest.approx(
            np.percentile(xs, [25, 50, 75]).tolist())
    with pytest.raises(ValueError):
        rounds.quartiles([])


def test_paired_median_cancels_drift_that_medians_do_not():
    """Dense 25 ms, dgc 2 ms more, and the machine drifts by 30% over the
    window: the paired median recovers the 2 ms; the difference of the two
    medians only does because both medians drift together."""
    steps = 10
    rows = []
    for r in range(20):
        drift = 1.0 + 0.3 * r / 19
        dense = 25e-3 * drift * steps
        rows.append({"dense": dense, "dgc": dense + 2e-3 * steps})
    assert paired_median(rows, steps) \
        == pytest.approx(2.0)
    assert rounds.quartiles(rounds.per_step_ms(rows, "dense", steps))[1] \
        == pytest.approx(25.0 * (1 + 0.3 * 9.5 / 19))
    assert rounds.per_step_ms(rows, "dgc", steps)[0] == pytest.approx(27.0)


def test_alternation_cancels_what_the_second_arm_inherits():
    """Whichever arm runs second in a round pays 1 ms per step more (a warm
    queue, a busy host thread): with the order alternating, the paired
    median over an even number of rounds sits midway, unbiased."""
    steps, arms = 10, ["dgc", "dense"]
    rows = []
    for r in range(8):
        order = rounds.arm_order(arms, r)
        row = {"dgc": 27e-3 * steps, "dense": 25e-3 * steps}
        row[order[1]] += 1e-3 * steps
        rows.append(row)
    diffs = sorted(rounds.paired_diff_ms(rows, "dgc", "dense", steps))
    assert diffs == pytest.approx([1.0] * 4 + [3.0] * 4)
    assert paired_median(rows, steps) \
        == pytest.approx(2.0)


def test_one_disturbed_round_does_not_move_the_median():
    steps = 20
    rows = [{"dgc": 0.54, "dense": 0.50} for _ in range(9)]
    rows.append({"dgc": 5.0, "dense": 0.50})
    assert paired_median(rows, steps) \
        == pytest.approx(2.0)


def test_arms_that_ran_one_after_the_other_differ_by_their_medians():
    """``residency: one``: each arm's rounds are its own, so there is no
    within-round difference to take; the arms may have run a different
    number of rounds, and a disturbed round moves neither median."""
    steps = 4
    dgc = [{"dgc": 27e-3 * steps} for _ in range(7)] + [{"dgc": 1.0}]
    dense = [{"dense": 25e-3 * steps} for _ in range(5)]
    assert rounds.median_diff_ms(dgc, "dgc", dense, "dense", steps) \
        == pytest.approx(2.0)
    # on interleaved rounds it is the difference of the arms' medians,
    # which drifts with the machine where the paired median does not
    rows = [{"dense": 0.25 * (1 + 0.3 * r / 19),
             "dgc": 0.25 * (1 + 0.3 * r / 19) + 0.02} for r in range(20)]
    assert rounds.median_diff_ms(rows, "dgc", rows, "dense", 10) \
        == pytest.approx(2.0)
