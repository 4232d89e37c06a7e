"""step layer: device ms per step under dgcph.update, dgc arm. The scope
wraps the exchange too, and the innermost scope wins, so this is the
optimizer update plus whatever the exchange runs outside its own phases."""


def read(trace, spans, cell):
    table = trace["tables"].get("dgc")
    return table and table["phases"].get("update")
