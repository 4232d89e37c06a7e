"""model layer (dgc_tpu/models/sambay.py ``Block`` / ``Mamba``): device ms
per step of the Mamba layers (LN1, the projections, the depthwise conv,
the chunked selective scan, the gate), forward and backward: the ops whose
``tf_op`` holds dgcph.fwd_bwd.ssm, dgc arm. 0.0 where the model has no
such part (a conv net) or the program has no such scope (the parent)."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.fwd_bwd.ssm"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
