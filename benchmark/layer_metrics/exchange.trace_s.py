"""exchange layer (optim/distributed.py ``_exchange_flat``): seconds of
``step.trace`` spent in the Python body of ``engine.exchange``, both arms
(the DGC engine's compensate to apply; the dense engine's psums): the sum
of the program's ``exchange.trace`` spans that are children of a
``step.trace`` (the exchange check drives ``step_flat`` under none, after
the window: its span is not the step's). 0.0 where a step was traced and
the program has no such span."""

from benchmark.program_records import span_seconds


def read(trace, spans, cell):
    if not trace["steps"] or not span_seconds("step.trace"):
        return None
    return sum(span_seconds("exchange.trace", under="step.trace"))
