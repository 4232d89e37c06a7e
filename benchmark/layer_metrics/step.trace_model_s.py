"""step layer (training/step.py ``worker``): seconds of ``step.trace`` spent
tracing the model's forward and backward under the scan, both arms: the sum
of the program's ``step.trace_model`` spans. 0.0 where a step was traced
and the program has no such span."""

from benchmark.program_records import span_seconds


def read(trace, spans, cell):
    if not trace["steps"] or not span_seconds("step.trace"):
        return None
    return sum(span_seconds("step.trace_model"))
