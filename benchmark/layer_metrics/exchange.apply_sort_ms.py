"""exchange layer (ops/kernels.py ``_sorted_pairs``): device ms per step of
the apply pass's sort: the ops whose ``tf_op`` holds dgcph.apply.sort (the
worker-average divide, the order key, the pads and the one ``lax.sort`` of
the gathered pairs), dgc arm. 0.0 where the step's apply sorts nothing (the
scatter form) or the program has no such scope."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.apply.sort"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
