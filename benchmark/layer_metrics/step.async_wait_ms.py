"""step layer: device ms per step the chip waits for an asynchronous copy
it started earlier: self time of the leaf ops named ``*-done`` that are
not collectives (``copy-done``, ``slice-done``), dgc arm."""

from benchmark.trace_reduce import is_collective, is_leaf, sum_ms_per_step


def _is_async_wait(op):
    return (is_leaf(op) and not is_collective(op)
            and op.name.partition(".")[0].endswith("-done"))


def read(trace, spans, cell):
    arm = trace["arms"].get("dgc")
    return arm and sum_ms_per_step(arm, _is_async_wait)
