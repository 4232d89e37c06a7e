"""collectives layer: device ms per step under the dense engine's own
dgcph.dense scope, DENSE arm, mean over the chips: the gradient
all-reduce DGC exists to replace."""


def read(trace, spans, cell):
    table = trace["tables"].get("dense")
    return table and table["phases"].get("dense")
