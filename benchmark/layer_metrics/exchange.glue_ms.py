"""exchange layer: device ms per step the engine spends outside its own
phases: the ops under dgcph.update.exchange whose innermost phase is still
``update`` (casts, concatenations and slices between the phases), dgc
arm."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.update.exchange"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    if arm is None or not any(TOKEN in op.tf_op
                              for chip in arm.chips for op in chip.ops):
        return None
    return sum_ms_per_step(
        arm, lambda op: TOKEN in op.tf_op and op.phase == "update")
