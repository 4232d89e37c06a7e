"""exchange layer: bytes per worker and step the dense arm's engine hands
to its gradient all-reduce, counted where the psum is issued while the
step was traced: what DGC's wire is compared with."""

from benchmark.program_records import collective_bytes


def read(trace, spans, cell):
    if "dense" not in trace["steps"]:
        return None
    return collective_bytes("FlatDenseExchange")
