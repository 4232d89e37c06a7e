"""model layer (dgc_tpu/models/sambay.py ``Block`` / ``GatedMLP``): device ms
per step of every layer's gated MLP (LN2, gate_up, SiLU, down), forward
and backward: the ops whose ``tf_op`` holds dgcph.fwd_bwd.mlp, dgc arm.
0.0 where the model has no such part (a conv net) or the program has no
such scope (the parent)."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.fwd_bwd.mlp"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
