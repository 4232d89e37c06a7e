"""step layer: device ms per step of the ops that carry no dgcph scope,
dgc arm: what XLA inserted itself (copy, copy-done, slice-done: no
``tf_op``) and the few ops named after a parameter instead of a scope."""


def read(trace, spans, cell):
    table = trace["tables"].get("dgc")
    return table and table["phases"].get("unattributed")
