"""collectives layer (all_gather / psum in the step): milliseconds per step
in which a collective is in flight (an asynchronous one from its -start to
its -done), dgc arm, mean over the chips."""

from benchmark.trace_reduce import collective_spans


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    if arm is None or len(arm.chips) < 2:
        return None
    total = sum(b - a for chip in arm.chips
                for a, b in collective_spans(chip.ops))
    return total * 1e3 / (arm.steps * len(arm.chips))
