"""exchange layer: what DGC costs on the device beyond its own phases:
(dgc arm's total device ms - dense arm's) - exchange.device_ms. Carry
copies, conversions and whatever else carries no engine scope."""

from benchmark.trace_reduce import EXCHANGE_PHASES


def read(trace, spans, cell):
    dgc, dense = trace["tables"].get("dgc"), trace["tables"].get("dense")
    if dgc is None or dense is None:
        return None
    scoped = sum(dgc["phases"].get(p, 0.0) for p in EXCHANGE_PHASES)
    return dgc["total_ms"] - dense["total_ms"] - scoped
