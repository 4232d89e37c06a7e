"""exchange layer (optim/distributed.py -> compression/flat.py): device ms
per step summed over the engine's phases (compensate, forward, threshold,
select, pack, allgather, decode, apply, dense), dgc arm."""

from benchmark.trace_reduce import EXCHANGE_PHASES


def read(trace, spans, cell):
    table = trace["tables"].get("dgc")
    if table is None:
        return None
    return sum(table["phases"].get(p, 0.0) for p in EXCHANGE_PHASES)
