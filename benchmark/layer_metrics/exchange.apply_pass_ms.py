"""exchange layer: device ms per step of the apply pass itself: the Pallas
calls under dgcph.apply (``payload_update_bits``, ``payload_apply_bits``,
``dgc_apply_rows``: one streamed pass over the flat buffer), dgc arm. 0.0
where the step's apply is XLA's scatter."""

from benchmark.trace_reduce import is_pallas, sum_ms_per_step


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(
        arm, lambda op: is_pallas(op) and op.phase == "apply")
