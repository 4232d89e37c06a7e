"""step layer: device ms per step of the optimizer proper: the ops whose
``tf_op`` holds dgcph.update.optimizer (the wrapped optimizer's update and
the parameter add, the root of its fusion), dgc arm."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.update.optimizer"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    if arm is None or not any(TOKEN in op.tf_op
                              for chip in arm.chips for op in chip.ops):
        return None
    return sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
