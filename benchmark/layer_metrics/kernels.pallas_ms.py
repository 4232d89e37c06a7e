"""kernels layer (ops/kernels.py): device ms per step of the Pallas
(Mosaic) custom calls, dgc arm."""

from benchmark.trace_reduce import is_pallas, sum_ms_per_step


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, is_pallas)
