"""step layer (training/step.py): seconds jit spent in the Python body of
the train step, both arms: the sum of the program's ``step.trace`` spans
(the harness's first-step lap also holds the lowering to HLO and the
executable's compile or load)."""

from benchmark.program_records import span_seconds


def read(trace, spans, cell):
    traced = span_seconds("step.trace") if trace["steps"] else []
    return sum(traced) if traced else None
