"""The harness's own ``measure`` on tiny cells, on the CPU: every input
mode and loop the traffic files can ask for, one chip and a mesh of four.
Counts and checks only; no time from here is a device number."""

import pytest

from benchmark import rehearse


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.resident", "tiny.scan",
                                  "tiny.steady.x4"])
def test_measure_runs_the_cell(cell):
    out = rehearse._run_tiny(cell, trace=False)
    assert out["rounds"] >= 2 and out["steps"] > 0
    assert out["check"]["ok"] and out["check"]["world"] == (
        4 if cell.endswith(".x4") else 1)


def test_traced_measure_puts_the_harness_spans_on_the_trace():
    out = rehearse._run_tiny("tiny.resident", trace=True)
    assert out["annotations"] >= 6 and "no_device_ops" in out
    assert out["per_layer_read"] == ["input.wait_ms"]
