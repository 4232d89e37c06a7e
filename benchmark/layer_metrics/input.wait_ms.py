"""input layer (dgc_tpu/data: Prefetcher, stage_ahead): milliseconds per
step the loop spends inside next() on the staged iterator, dgc arm, over
the run's whole window."""


def read(trace, spans, cell):
    waits = spans.get("dgc", {}).get("input.next")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
