"""collectives layer: the part of the time a collective is in flight
during which no other op runs on that chip (the chip waits for the wire),
ms per step, dgc arm, mean over the chips."""

from benchmark.trace_reduce import (collective_spans, is_collective, is_leaf,
                                    merge_intervals, overlap_s)


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    if arm is None or len(arm.chips) < 2:
        return None
    exposed = 0.0
    for chip in arm.chips:
        flight = collective_spans(chip.ops)
        compute = merge_intervals([(o.start, o.start + o.dur)
                                   for o in chip.ops
                                   if is_leaf(o) and not is_collective(o)])
        exposed += sum(b - a for a, b in flight) - overlap_s(flight, compute)
    return exposed * 1e3 / (arm.steps * len(arm.chips))
