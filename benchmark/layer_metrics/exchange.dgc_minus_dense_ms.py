"""exchange layer: the paired difference of the window (median over rounds
of dgc round - dense round, per step), printed in the traced run. It is a
per-layer metric where it is not end to end: on several chips the dense arm
pays a real gradient all-reduce and the difference may sit near zero."""


def read(trace, spans, cell):
    diff = trace["paired"].get("dgc_minus_dense_ms")
    return diff and diff["median"]
