"""step layer: device ms per step under dgcph.params_view (the flat
parameter buffer cut into per-tensor views, and the opaque_view copies
that guard them), dgc arm."""


def read(trace, spans, cell):
    table = trace["tables"].get("dgc")
    return table and table["phases"].get("params_view")
