"""exchange layer: device ms per step of the apply pass's staging: the ops
whose ``tf_op`` holds dgcph.apply.stage (the duplicate fold and the window
maps of ``_sorted_pairs``, the transmit flags, the donor and the prefetch
assembly: everything between the sort and the pass), dgc arm. 0.0 where
the step's apply stages nothing or the program has no such scope."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.apply.stage"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
