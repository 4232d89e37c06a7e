"""step layer (training/step.py): device ms per step under dgcph.fwd_bwd,
dgc arm."""


def read(trace, spans, cell):
    table = trace["tables"].get("dgc")
    return table and table["phases"].get("fwd_bwd")
