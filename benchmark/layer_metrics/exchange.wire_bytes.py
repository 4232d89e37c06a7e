"""exchange layer: bytes per worker and step the DGC engine hands to its
collectives (the payload's all-gathers and the dense tail's psum), counted
where each collective is issued while the step was traced."""

from benchmark.program_records import collective_bytes


def read(trace, spans, cell):
    if "dgc" not in trace["steps"]:
        return None
    return collective_bytes("FlatDGCEngine")
