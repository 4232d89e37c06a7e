"""device layer: percent of the dgc arm's traced window (first device op
to last) in which no op ran, on the chip where that share is largest."""

from benchmark.trace_reduce import idle_share


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and 100.0 * max(idle_share(chip) for chip in arm.chips)
