"""model layer (dgc_tpu/models/sambay.py ``SambaY.__call__``): device ms per
step of the final LayerNorm, the tied logits and their backward pass into
the embedding, forward and backward: the ops whose ``tf_op`` holds
dgcph.fwd_bwd.head, dgc arm. 0.0 where the model has no such part (a conv
net) or the program has no such scope (the parent)."""

from benchmark.trace_reduce import sum_ms_per_step

TOKEN = "dgcph.fwd_bwd.head"


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, lambda op: TOKEN in op.tf_op)
