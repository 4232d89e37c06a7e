"""Round arithmetic: alternation of the arms and the paired medians.

Copied in substance from ``bench.py::_interleaved_step_ms`` and its
callers (the original is listed in PERF.md for a later PR to delete): the
arms run interleaved, one round each, so that slow drift of the machine
hits them equally, and two arms are compared by the median of their
WITHIN-ROUND differences and not by the difference of their medians
(``median_diff_ms`` is for a cell whose arms cannot be on the chip
together, ``residency: one``, and so have no round in common). What
differs from the original: the order of the arms alternates from round to
round (whatever the second arm inherits from the first then cancels), the
window is a time and not a round count, and nothing is subtracted from a
round's wall time.
"""

from typing import Dict, List, Sequence


def arm_order(arms: Sequence[str], round_index: int) -> List[str]:
    """The order the arms run in, in round ``round_index``: rotated by one
    place each round (two arms: A B, B A, A B, ...)."""
    n = len(arms)
    shift = round_index % n
    return list(arms[shift:]) + list(arms[:shift])


def quartiles(xs: Sequence[float]) -> List[float]:
    """[q1, median, q3] by linear interpolation (numpy's default)."""
    s = sorted(xs)
    if not s:
        raise ValueError("no samples")

    def q(p):
        pos = p * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        return s[lo] + (s[hi] - s[lo]) * (pos - lo)

    return [q(0.25), q(0.5), q(0.75)]


def per_step_ms(rows: List[Dict[str, float]], arm: str, steps: int
                ) -> List[float]:
    """Per-step milliseconds of ``arm`` in every round: round wall seconds
    / steps in the round."""
    return [row[arm] * 1e3 / steps for row in rows]


def paired_diff_ms(rows: List[Dict[str, float]], a: str, b: str,
                   steps: int) -> List[float]:
    """Within-round differences (a - b), per step, in milliseconds."""
    return [(row[a] - row[b]) * 1e3 / steps for row in rows]


def median_diff_ms(rows_a: List[Dict[str, float]], a: str,
                   rows_b: List[Dict[str, float]], b: str,
                   steps: int) -> float:
    """The difference of the arms' median per-step milliseconds, for arms
    that ran one after the other: each arm's rounds are its own."""
    return (quartiles(per_step_ms(rows_a, a, steps))[1]
            - quartiles(per_step_ms(rows_b, b, steps))[1])
