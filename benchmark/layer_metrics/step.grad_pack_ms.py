"""step layer (training/step.py ``micro``): device ms per step of packing
the model's gradients into the flat layout and adding them to the
micro-batch sum: the ops whose ``tf_op`` holds dgcph.fwd_bwd.pack, dgc arm.
0.0 where XLA writes the gradients in place (no op is left to carry the
token) or the program has no such scope."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.fwd_bwd.pack"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
